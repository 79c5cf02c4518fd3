"""Telemetry subsystem tests: registry thread-safety (exact counts under N
writers), histogram bucket-edge semantics, Chrome-trace export validity
(``ph``/``ts``/``pid``/``tid`` on every event), the disabled-mode no-op
path, exporter round-trips, and multi-rank ``report`` aggregation."""

import json
import os
import subprocess
import sys
import threading

import pytest

from dmlc_core_tpu import telemetry
from dmlc_core_tpu.telemetry import clock, export, report
from dmlc_core_tpu.telemetry.registry import (DEFAULT_BUCKETS, Histogram,
                                              MetricRegistry)
from dmlc_core_tpu.telemetry.spans import SpanTracer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_telemetry():
    """Every test starts disabled with empty state; afterwards the prior
    enabled/disabled state is restored (the module is process-global, and
    a suite-wide DMLC_TELEMETRY_DIR run — CI — relies on collection staying
    on so the atexit flush produces the artifact)."""
    was_enabled = telemetry.enabled()
    telemetry.disable()
    telemetry.reset()
    yield
    telemetry.disable()
    telemetry.reset()
    if was_enabled:
        telemetry.enable()


# -- registry: thread safety --------------------------------------------------

def test_counter_exact_under_n_writer_threads():
    reg = MetricRegistry()
    n_threads, per_thread = 8, 5000

    def work():
        for _ in range(per_thread):
            reg.counter("hits", worker="shared").inc()
            reg.histogram("lat").observe(0.01)

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert reg.counter("hits", worker="shared").value == n_threads * per_thread
    hist = reg.histogram("lat")
    assert hist.count == n_threads * per_thread
    assert hist.sum == pytest.approx(0.01 * n_threads * per_thread)


def test_gauge_and_labels_are_independent_children():
    reg = MetricRegistry()
    reg.gauge("depth", name="a").set(3)
    reg.gauge("depth", name="b").set(7)
    reg.gauge("depth", name="a").inc(2)
    assert reg.gauge("depth", name="a").value == 5
    assert reg.gauge("depth", name="b").value == 7
    # same family, kind clash is an error, not silent corruption
    with pytest.raises(ValueError):
        reg.counter("depth")


def test_counter_rejects_negative_increment():
    reg = MetricRegistry()
    with pytest.raises(ValueError):
        reg.counter("c").inc(-1)


# -- histogram bucket edges ---------------------------------------------------

def test_histogram_bucket_edges_are_le_inclusive():
    hist = Histogram(buckets=(1.0, 2.0, 5.0))
    for v in (0.5, 1.0, 1.5, 2.0, 5.0, 99.0):
        hist.observe(v)
    # Prometheus `le` semantics: an observation exactly on a bound belongs
    # to that bound's bucket, not the next one up
    assert hist.bucket_counts == [2, 2, 1, 1]  # <=1, <=2, <=5, +Inf
    assert hist.cumulative() == [2, 4, 5, 6]
    assert hist.count == 6
    assert hist.sum == pytest.approx(0.5 + 1.0 + 1.5 + 2.0 + 5.0 + 99.0)


def test_histogram_rejects_bad_buckets():
    with pytest.raises(ValueError):
        Histogram(buckets=())
    with pytest.raises(ValueError):
        Histogram(buckets=(1.0, 1.0))
    with pytest.raises(ValueError):
        Histogram(buckets=(2.0, 1.0))


def test_default_buckets_ascending():
    assert list(DEFAULT_BUCKETS) == sorted(DEFAULT_BUCKETS)


# -- disabled-mode no-op path -------------------------------------------------

def test_disabled_mode_records_nothing():
    assert not telemetry.enabled()
    telemetry.count("dmlc_x_total", 5)
    telemetry.gauge_set("dmlc_x_depth", 3)
    telemetry.observe("dmlc_x_seconds", 0.1)
    with telemetry.span("x", k=1) as sp:
        sp.set(extra=2)
    telemetry.record_span("y", clock.monotonic(), clock.monotonic())
    assert telemetry.get_registry().families() == []
    assert telemetry.get_tracer().events() == []


def test_disabled_span_is_shared_noop_object():
    a = telemetry.span("a")
    b = telemetry.span("b", attr=1)
    assert a is b  # no allocation on the disabled path


def test_enable_disable_round_trip():
    telemetry.enable()
    telemetry.count("dmlc_x_total")
    telemetry.disable()
    telemetry.count("dmlc_x_total")
    telemetry.enable()
    telemetry.count("dmlc_x_total")
    assert telemetry.get_registry().counter("dmlc_x_total").value == 2


# -- spans / Chrome trace -----------------------------------------------------

def test_chrome_trace_event_shape():
    telemetry.enable()
    with telemetry.span("outer", stage="io"):
        with telemetry.span("inner"):
            pass
    trace = telemetry.get_tracer().chrome_trace()
    # must survive a JSON round trip (what Perfetto actually loads)
    trace = json.loads(json.dumps(trace))
    events = [e for e in trace["traceEvents"] if e.get("ph") == "X"]
    assert len(events) == 2
    for event in events:
        for key in ("name", "ph", "ts", "pid", "tid", "dur"):
            assert key in event, f"missing {key}: {event}"
    outer = next(e for e in events if e["name"] == "outer")
    inner = next(e for e in events if e["name"] == "inner")
    assert outer["args"] == {"stage": "io"}
    # inner completed within outer on the same thread
    assert outer["ts"] <= inner["ts"]
    assert outer["ts"] + outer["dur"] >= inner["ts"] + inner["dur"]
    # thread-name metadata events accompany the spans
    assert any(e.get("ph") == "M" and e.get("name") == "thread_name"
               for e in trace["traceEvents"])


def test_span_records_exception_and_propagates():
    telemetry.enable()
    with pytest.raises(KeyError):
        with telemetry.span("boom"):
            raise KeyError("x")
    [event] = telemetry.get_tracer().events()
    assert event["args"]["error"] == "KeyError"


# -- the profiler's clock: spans as jax.profiler.TraceAnnotations --------------

@pytest.fixture
def annotations(monkeypatch):
    """Every ``jax.profiler.TraceAnnotation`` entered and exited, in order,
    as ``("enter" | "exit", name)``."""
    import jax

    seen = []

    class Recorder:
        def __init__(self, name, **_):
            self.name = name

        def __enter__(self):
            seen.append(("enter", self.name))
            return self

        def __exit__(self, *exc):
            seen.append(("exit", self.name))

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recorder)
    return seen


def test_enabled_span_enters_and_exits_an_annotation_of_its_name(annotations):
    telemetry.enable()
    with telemetry.span("outer.stage", rows=3):
        with telemetry.span("inner.stage"):
            pass
    assert annotations == [("enter", "outer.stage"), ("enter", "inner.stage"),
                           ("exit", "inner.stage"), ("exit", "outer.stage")]
    # the span itself records as before
    assert [e["name"] for e in telemetry.get_tracer().events()] \
        == ["inner.stage", "outer.stage"]


def test_disabled_span_enters_no_annotation(annotations):
    with telemetry.span("outer.stage"):
        pass
    assert annotations == []


def test_span_closes_its_annotation_when_the_body_raises(annotations):
    telemetry.enable()
    with pytest.raises(KeyError):
        with telemetry.span("doomed"):
            raise KeyError("boom")
    assert annotations == [("enter", "doomed"), ("exit", "doomed")]
    (ev,) = telemetry.get_tracer().events()
    assert ev["args"]["error"] == "KeyError"


def test_span_without_jax_in_the_process_enters_no_annotation(
        annotations, monkeypatch):
    """The tracker and the load generator never import jax, and telemetry
    never imports it for them."""
    monkeypatch.delitem(sys.modules, "jax")
    telemetry.enable()
    with telemetry.span("tracker.start"):
        pass
    assert annotations == []
    assert "jax" not in sys.modules
    assert [e["name"] for e in telemetry.get_tracer().events()] \
        == ["tracker.start"]


def test_record_span_is_not_bridged_to_the_profiler(annotations):
    telemetry.enable()
    start = clock.monotonic()
    telemetry.record_span("serve.queue.wait", start, start + 0.001)
    assert annotations == []
    assert len(telemetry.get_tracer().events()) == 1


def test_record_span_uses_monotonic_domain():
    telemetry.enable()
    start = clock.monotonic()
    end = start + 0.25
    telemetry.record_span("phase", start, end, rank=3)
    [event] = telemetry.get_tracer().events()
    assert event["dur"] == pytest.approx(0.25e6, rel=1e-6)
    assert event["args"]["rank"] == 3


def test_span_buffer_is_bounded():
    tracer = SpanTracer(max_events=10)
    for i in range(15):
        tracer.record("s", float(i), 1.0)
    assert len(tracer.events()) == 10
    assert tracer.dropped == 5


def test_jsonl_one_object_per_line():
    telemetry.enable()
    with telemetry.span("a"):
        pass
    lines = list(telemetry.get_tracer().jsonl())
    assert len(lines) == 1
    assert json.loads(lines[0])["name"] == "a"


# -- exporters ----------------------------------------------------------------

def test_prometheus_text_format():
    telemetry.enable()
    telemetry.count("dmlc_parser_rows_total", 42, parser="LibSVMParser")
    telemetry.gauge_set("dmlc_threadediter_queue_depth", 5, name="p")
    telemetry.observe("dmlc_filesystem_request_seconds", 0.004, fs="s3",
                      op="GET")
    text = telemetry.prometheus_text()
    assert "# TYPE dmlc_parser_rows_total counter" in text
    assert 'dmlc_parser_rows_total{parser="LibSVMParser"} 42' in text
    assert "# TYPE dmlc_threadediter_queue_depth gauge" in text
    assert "# TYPE dmlc_filesystem_request_seconds histogram" in text
    assert 'le="+Inf"' in text
    # cumulative bucket counts: 0.004 lands at le="0.005" and everything up
    assert 'dmlc_filesystem_request_seconds_bucket{fs="s3",op="GET",le="0.005"} 1' in text
    assert 'dmlc_filesystem_request_seconds_bucket{fs="s3",op="GET",le="0.001"} 0' in text
    assert 'dmlc_filesystem_request_seconds_count{fs="s3",op="GET"} 1' in text


def test_json_snapshot_shape():
    telemetry.enable()
    telemetry.count("dmlc_x_total", 3, k="v")
    telemetry.observe("dmlc_y_seconds", 0.2)
    snap = telemetry.snapshot()
    snap = json.loads(json.dumps(snap))  # must be JSON-serializable
    assert snap["metrics"]["dmlc_x_total"]["kind"] == "counter"
    [sample] = snap["metrics"]["dmlc_x_total"]["samples"]
    assert sample == {"labels": {"k": "v"}, "value": 3}
    hist = snap["metrics"]["dmlc_y_seconds"]["samples"][0]
    assert hist["count"] == 1 and len(hist["counts"]) == len(hist["buckets"]) + 1
    assert snap["spans"] == {"recorded": 0, "dropped": 0}


def test_flush_writes_all_forms_atomically(tmp_path):
    telemetry.enable()
    telemetry.count("dmlc_x_total")
    with telemetry.span("s"):
        pass
    written = telemetry.flush(str(tmp_path))
    assert sorted(written) == ["json", "jsonl", "prom", "trace.json"]
    for path in written.values():
        assert os.path.exists(path)
    assert not [p for p in os.listdir(tmp_path) if p.endswith(".tmp")]
    trace = json.load(open(written["trace.json"]))
    assert any(e.get("ph") == "X" for e in trace["traceEvents"])


def test_flush_without_dir_raises(monkeypatch):
    telemetry.enable()
    # neutralize both directory sources: the ambient env var AND the
    # module-level dir latched from it at import (the CI suite itself runs
    # under DMLC_TELEMETRY_DIR)
    monkeypatch.delenv("DMLC_TELEMETRY_DIR", raising=False)
    monkeypatch.setattr(telemetry, "_flush_dir", None)
    with pytest.raises(ValueError):
        telemetry.flush()


def test_env_bring_up_and_atexit_flush(tmp_path):
    """DMLC_TELEMETRY_DIR enables collection in a fresh interpreter and
    flushes every export form at exit without any explicit call."""
    out_dir = tmp_path / "tel"
    code = ("from dmlc_core_tpu import telemetry\n"
            "assert telemetry.enabled()\n"
            "telemetry.count('dmlc_child_total', 2)\n"
            "with telemetry.span('child.work'):\n"
            "    pass\n")
    env = dict(os.environ, DMLC_TELEMETRY_DIR=str(out_dir),
               DMLC_TASK_ID="4", JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    files = sorted(os.listdir(out_dir))
    assert [f for f in files if f.startswith("metrics-r4-") and
            f.endswith(".json")]
    assert [f for f in files if f.endswith(".prom")]
    assert [f for f in files if f.endswith(".trace.json")]
    snap_path = next(str(out_dir / f) for f in files
                     if f.startswith("metrics-r4-") and f.endswith(".json"))
    snap = json.load(open(snap_path))
    assert snap["rank"] == 4
    assert snap["metrics"]["dmlc_child_total"]["samples"][0]["value"] == 2


# -- multi-rank report aggregation --------------------------------------------

def _write_rank_snapshot(dirpath, rank, counter_v, gauge_v, hist_counts):
    reg = MetricRegistry()
    reg.counter("dmlc_parser_rows_total", parser="p").inc(counter_v)
    reg.gauge("dmlc_threadediter_queue_depth").set(gauge_v)
    for v in hist_counts:
        reg.histogram("dmlc_collective_op_seconds",
                      buckets=(0.1, 1.0)).observe(v)
    snap = export.json_snapshot(reg)
    snap["rank"] = rank
    path = os.path.join(dirpath, f"metrics-r{rank}-p{1000 + rank}.json")
    with open(path, "w") as f:
        json.dump(snap, f)


def test_report_aggregates_ranks(tmp_path):
    _write_rank_snapshot(str(tmp_path), 0, 100, 3.0, [0.05, 0.5])
    _write_rank_snapshot(str(tmp_path), 1, 250, 7.0, [2.0])
    merged = report.aggregate(report.load_snapshots(str(tmp_path)))
    counter = merged['dmlc_parser_rows_total{parser="p"}']
    assert counter["total"] == 350 and sorted(counter["ranks"]) == [0, 1]
    gauge = merged["dmlc_threadediter_queue_depth"]
    assert gauge["min"] == 3.0 and gauge["max"] == 7.0
    hist = merged["dmlc_collective_op_seconds"]
    assert hist["count"] == 3
    assert hist["counts"] == [1, 1, 1]  # <=0.1, <=1.0, +Inf summed across ranks
    assert hist["mean"] == pytest.approx((0.05 + 0.5 + 2.0) / 3)
    table = report.render_table(merged)
    assert "dmlc_parser_rows_total" in table and "350" in table


def test_report_skips_corrupt_snapshots(tmp_path):
    (tmp_path / "metrics-r0-p1.json").write_text("{not json")
    (tmp_path / "metrics-r1-p2.json").write_text('{"no_metrics": 1}')
    _write_rank_snapshot(str(tmp_path), 2, 5, 0.0, [])
    snaps = report.load_snapshots(str(tmp_path))
    assert len(snaps) == 1 and snaps[0]["rank"] == 2


def test_report_cli_end_to_end(tmp_path):
    _write_rank_snapshot(str(tmp_path), 0, 10, 1.0, [])
    _write_rank_snapshot(str(tmp_path), 1, 20, 2.0, [])
    proc = subprocess.run(
        [sys.executable, "-m", "dmlc_core_tpu.telemetry", "report",
         str(tmp_path)],
        cwd=REPO, capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stderr
    assert "2 snapshot(s) from rank(s) 0,1" in proc.stdout
    assert "30" in proc.stdout
    # --json form parses and carries the same totals
    proc = subprocess.run(
        [sys.executable, "-m", "dmlc_core_tpu.telemetry", "report",
         str(tmp_path), "--json"],
        cwd=REPO, capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    merged = json.loads(proc.stdout)
    assert merged['dmlc_parser_rows_total{parser="p"}']["total"] == 30


def test_report_cli_empty_dir_exit_code(tmp_path):
    assert report.main(str(tmp_path)) == 1


# -- facades over the registry ------------------------------------------------

def test_throughput_meter_feeds_registry_when_enabled():
    from dmlc_core_tpu.utils.profiler import ThroughputMeter

    telemetry.enable()
    meter = ThroughputMeter("bench", log_every_bytes=1 << 40)
    meter.add(1024, nrows=10)
    meter.add(1024, nrows=5)
    reg = telemetry.get_registry()
    assert reg.counter("dmlc_pipeline_bytes_total", meter="bench").value == 2048
    assert reg.counter("dmlc_pipeline_rows_total", meter="bench").value == 15
    assert meter.mb == pytest.approx(2048 / (1 << 20))


def test_fs_metrics_helper_families():
    from dmlc_core_tpu.io import fs_metrics

    assert fs_metrics.request_start() == 0.0  # disabled: no clock read
    telemetry.enable()
    t0 = fs_metrics.request_start()
    assert t0 > 0.0
    fs_metrics.note_request("s3", "GET", t0, nread=512)
    fs_metrics.note_request("azure", "PUT", t0, nwritten=64)
    reg = telemetry.get_registry()
    assert reg.counter("dmlc_filesystem_read_bytes_total", fs="s3").value == 512
    assert reg.counter("dmlc_filesystem_write_bytes_total",
                       fs="azure").value == 64
    assert reg.histogram("dmlc_filesystem_request_seconds",
                         fs="s3", op="GET").count == 1


def test_net_retry_metrics(monkeypatch):
    import time as time_mod

    from dmlc_core_tpu.io import net_retry

    monkeypatch.setattr(time_mod, "sleep", lambda s: None)
    telemetry.enable()
    calls = {"n": 0}

    def perform():
        calls["n"] += 1
        if calls["n"] < 3:
            return 503, {}, b"busy"
        return 200, {}, b"ok"

    status, _, data = net_retry.request_with_retries(perform, (200,), "GET /x")
    assert status == 200 and data == b"ok"
    reg = telemetry.get_registry()
    assert reg.counter("dmlc_net_retry_retries_total",
                       status_class="5xx").value == 2
    # full-jitter backoff: each sleep is uniform in [0, 0.1) + [0, 0.2),
    # summed by status class — bounded by the pre-jitter doubling windows
    backoff = reg.counter("dmlc_net_retry_backoff_seconds_total",
                          status_class="5xx").value
    assert 0.0 <= backoff < 0.3


# -- review-hardening regressions ---------------------------------------------

def test_prometheus_label_values_escaped():
    telemetry.enable()
    telemetry.count("dmlc_x_total", 1, name='shard "a"\\b\nc')
    text = telemetry.prometheus_text()
    assert 'name="shard \\"a\\"\\\\b\\nc"' in text
    assert "\n\n" not in text  # the raw newline never leaks into the format


def test_report_bucket_clash_marked_not_dropped(tmp_path):
    _write_rank_snapshot(str(tmp_path), 0, 1, 0.0, [0.05])
    # rank 1 registered the same family with a different bucket list
    reg = MetricRegistry()
    reg.histogram("dmlc_collective_op_seconds",
                  buckets=(0.5, 1.0, 2.0, 4.0)).observe(3.0)
    snap = export.json_snapshot(reg)
    snap["rank"] = 1
    with open(os.path.join(str(tmp_path), "metrics-r1-p9.json"), "w") as f:
        json.dump(snap, f)
    merged = report.aggregate(report.load_snapshots(str(tmp_path)))
    hist = merged["dmlc_collective_op_seconds"]
    assert hist["bucket_clash"] is True
    assert hist["counts"] == [1, 0, 0]  # rank 0's fold kept, not overwritten
    assert hist["count"] == 2           # ...while count/sum cover both ranks


def test_fs_metrics_skips_unmeasured_latency_sample():
    from dmlc_core_tpu.io import fs_metrics

    start = fs_metrics.request_start()  # disabled: 0.0 sentinel
    telemetry.enable()                  # enabled mid-request
    fs_metrics.note_request("s3", "GET", start, nread=128)
    reg = telemetry.get_registry()
    # bytes still counted, but no fabricated 0.0-latency observation
    assert reg.counter("dmlc_filesystem_read_bytes_total", fs="s3").value == 128
    assert reg.histogram("dmlc_filesystem_request_seconds",
                         fs="s3", op="GET").count == 0


def test_prometheus_nonfinite_values_export_without_crashing():
    telemetry.enable()
    telemetry.gauge_set("dmlc_x_ratio", float("inf"))
    telemetry.gauge_set("dmlc_y_ratio", float("nan"))
    text = telemetry.prometheus_text()  # must not raise
    assert "dmlc_x_ratio +Inf" in text
    assert "dmlc_y_ratio NaN" in text


def test_histogram_bucket_clash_raises():
    reg = MetricRegistry()
    reg.histogram("h", buckets=(1.0, 2.0)).observe(0.5)
    reg.histogram("h", buckets=(1.0, 2.0)).observe(1.5)  # same buckets: fine
    reg.histogram("h").observe(0.5)                      # unspecified: fine
    with pytest.raises(ValueError):
        reg.histogram("h", buckets=(0.5, 1.0))


def test_net_retry_exhausted_counts_status_exhaustion(monkeypatch):
    import time as time_mod

    from dmlc_core_tpu.io import net_retry

    monkeypatch.setattr(time_mod, "sleep", lambda s: None)
    telemetry.enable()
    status, _, _ = net_retry.request_with_retries(
        lambda: (503, {}, b"busy"), (200,), "GET /always-busy")
    assert status == 503  # returned to the caller after exhaustion
    reg = telemetry.get_registry()
    assert reg.counter("dmlc_net_retry_exhausted_total",
                       status_class="5xx").value == 1


def test_report_warns_on_duplicate_rank_snapshots(tmp_path, capsys):
    _write_rank_snapshot(str(tmp_path), 0, 10, 1.0, [])
    reg = MetricRegistry()
    reg.counter("dmlc_parser_rows_total", parser="p").inc(5)
    snap = export.json_snapshot(reg)
    snap["rank"] = 0
    with open(os.path.join(str(tmp_path), "metrics-r0-p2.json"), "w") as f:
        json.dump(snap, f)
    assert report.main(str(tmp_path)) == 0
    out = capsys.readouterr().out
    assert "multiple snapshots" in out
    assert "15" in out  # still sums — the note explains, it doesn't hide


# -- histogram quantile estimation (serving SLOs) ------------------------------

def test_estimate_quantiles_uniform_counts_interpolate_exactly():
    # 10 observations per decade bucket: quantile ranks land on bucket
    # boundaries and interior points with closed-form expectations
    buckets = (10.0, 20.0, 30.0, 40.0)
    counts = [10, 10, 10, 10, 0]
    p25, p50, p99 = report.estimate_quantiles(buckets, counts,
                                              (0.25, 0.50, 0.99))
    assert p25 == pytest.approx(10.0)
    assert p50 == pytest.approx(20.0)
    assert p99 == pytest.approx(39.6)


def test_estimate_quantiles_first_bucket_lower_edge_is_zero():
    # everything in the first bucket: interpolation starts at 0, not at the
    # first bound (latency observations are non-negative)
    (p50,) = report.estimate_quantiles((0.1, 1.0), [100, 0, 0], (0.5,))
    assert p50 == pytest.approx(0.05)


def test_estimate_quantiles_inf_bucket_floors_at_last_finite_bound():
    # mass past the last finite bound cannot be resolved: the estimate
    # reports the highest finite bound (histogram_quantile convention),
    # never an invented extrapolation
    buckets = (0.1, 1.0)
    qs = report.estimate_quantiles(buckets, [0, 0, 7], (0.5, 0.99))
    assert qs == [1.0, 1.0]
    # mixed: p50 resolves inside the finite buckets, p99 floors
    p50, p99 = report.estimate_quantiles(buckets, [6, 0, 4], (0.5, 0.99))
    assert p50 == pytest.approx(0.1 * (5.0 / 6.0))
    assert p99 == pytest.approx(1.0)


def test_estimate_quantiles_degenerate_inputs_are_none():
    assert report.estimate_quantiles((1.0,), [0, 0], (0.5,)) == [None]
    # counts length not bounds+1 (a cross-rank bucket clash)
    assert report.estimate_quantiles((1.0, 2.0), [1, 1], (0.5,)) == [None]
    assert report.estimate_quantiles((), [], (0.5,)) == [None]
    # out-of-range q
    assert report.estimate_quantiles((1.0,), [3, 0], (1.5,)) == [None]


def test_estimate_quantiles_tracks_numpy_percentile_within_bucket_width():
    import numpy as np

    rng = np.random.RandomState(7)
    sample = rng.gamma(2.0, 0.05, size=5000)  # latency-shaped
    bounds = tuple(np.linspace(0.01, 1.0, 100))
    h = telemetry.Histogram(buckets=bounds)
    for v in sample:
        h.observe(v)
    width = bounds[1] - bounds[0]
    for q in (0.5, 0.95, 0.99):
        (est,) = report.estimate_quantiles(bounds, h.bucket_counts, (q,))
        assert abs(est - float(np.percentile(sample, q * 100))) <= width


def test_report_aggregate_emits_quantiles(tmp_path):
    _write_rank_snapshot(str(tmp_path), 0, 1, 0.0, [0.05] * 9)
    _write_rank_snapshot(str(tmp_path), 1, 1, 0.0, [2.0])
    merged = report.aggregate(report.load_snapshots(str(tmp_path)))
    hist = merged["dmlc_collective_op_seconds"]
    # 9 of 10 samples land <= 0.1, the last in +Inf: p50 interpolates in
    # the first bucket, p99 floors at the last finite bound (1.0)
    assert hist["p50"] == pytest.approx(0.1 * (5.0 / 9.0))
    assert hist["p99"] == pytest.approx(1.0)
    table = report.render_table(merged)
    assert "p50=" in table and "p99=" in table
