"""Contract tests for bench.py — the driver-facing benchmark artifact.

bench.py is ONE process: it runs on the device ``init_device`` states,
says which in its result, and fails (non-zero exit, no result line) when
it finds no accelerator and nobody asked for the CPU.  These tests run the
real bench.py in a subprocess on a tiny workload — on the CPU, asked for
explicitly — and assert the one-JSON-line stdout contract the driver
parses, the device-feed accounting, and the telemetry snapshot.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_bench(**env_overrides):
    env = os.environ.copy()
    env.update({"BENCH_ROWS": "2000", "BENCH_TPU_ROUNDS": "2"})
    env.pop("XLA_FLAGS", None)
    for key, value in env_overrides.items():
        if value is None:
            env.pop(key, None)
        else:
            env[key] = value
    return subprocess.run([sys.executable, "bench.py"], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=600)


@pytest.fixture(scope="module")
def bench_run():
    return _run_bench(JAX_PLATFORMS="cpu")


def test_emits_exactly_one_json_line_naming_its_device(bench_run):
    assert bench_run.returncode == 0, bench_run.stderr[-3000:]
    lines = [l for l in bench_run.stdout.splitlines() if l.strip()]
    assert len(lines) == 1, lines
    result = json.loads(lines[0])
    for key in ("metric", "value", "unit", "platform", "detail"):
        assert key in result, key
    assert result["metric"] == "gbdt_hist_train_rows_per_sec_per_chip"
    assert result["value"] > 0
    # the result says what it ran on, as JAX reports it
    assert result["platform"] == "cpu"
    assert result["detail"]["device_kind"] == "cpu"
    assert result["detail"]["device_count"] == 1
    assert result["detail"]["hist_method"] == "scatter"
    # a same-process CPU ratio and a platform-keyed roofline are gone:
    # no peak is assumed for a device the benchmark does not know
    assert "vs_baseline" not in result
    assert "roofline" not in result["detail"]
    assert "last_live_capture" not in result["detail"]


def test_is_a_single_process_with_no_fallback_machinery():
    """The probe-and-retry harness stays deleted: no re-exec, no probe
    child, no CPU retry, no stage files, no knobs for any of them."""
    with open(os.path.join(REPO, "bench.py")) as f:
        source = f.read()
    for gone in ("subprocess", "--probe", "--child", "force_cpu",
                 "SoftDeadline", "chunked_device_put", "last_live_capture",
                 "collect_flight", "persist_stage", "BENCH_STAGE_DIR",
                 "BENCH_PROBE_TIMEOUT_S", "BENCH_ATTEMPT_TIMEOUT_S",
                 "BENCH_CHILD_DEADLINE_S", 'devices("cpu")'):
        assert gone not in source, gone


def test_no_chip_and_no_explicit_cpu_request_is_a_failure():
    """Without an accelerator and without JAX_PLATFORMS=cpu: non-zero
    exit, the reason on stderr, and NO result line."""
    proc = _run_bench(JAX_PLATFORMS=None)
    assert proc.returncode != 0
    assert "JAX found no accelerator" in proc.stderr
    assert not [l for l in proc.stdout.splitlines() if l.startswith("{")]


def test_compile_cache_dir_is_reported_and_fixed(bench_run):
    detail = json.loads(bench_run.stdout.strip().splitlines()[-1])["detail"]
    wanted = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or os.path.join(REPO, ".jax_cache")
    assert detail["compile_cache_dir"] == wanted


def test_detail_carries_device_feed_accounting(bench_run):
    """ISSUE 9: the staged-once wire cost travels with the train figure —
    `transfer_bytes` (uint8 bins + labels + weights actually shipped) and
    `feed_rows_per_sec` (staging rate), against `float_path_bytes` (the
    pre-PR device-side-binning wire cost: x f32 up + bins i32 back + bins
    i32 up).  The acceptance bar: binned wire <= 1/8 of the float path."""
    proc = bench_run
    [line] = [l for l in proc.stdout.splitlines() if l.strip()]
    detail = json.loads(line)["detail"]
    assert detail["wire_dtype"] == "uint8"
    n, f = 2000, 28
    # bins shipped narrow + labels/weights f32; nothing else on the wire
    assert detail["transfer_bytes"] == n * f + 2 * n * 4
    assert detail["float_path_bytes"] == 3 * n * f * 4
    assert detail["transfer_bytes"] * 8 <= detail["float_path_bytes"]
    assert detail["feed_rows_per_sec"] > 0
    assert detail["stage_seconds"] >= 0
    # the staged bytes landed in the run's telemetry, so the merged trace
    # can split transfer from compute
    spans = json.loads(line)["detail"]["telemetry"]
    assert counter_sum(spans, "dmlc_transfer_bytes_total") \
        == detail["transfer_bytes"]


def counter_sum(families, name):
    return sum(s["value"] for s in families[name]["samples"])


def test_detail_carries_telemetry_snapshot(bench_run):
    """ISSUE 2 satellite: each emitted metric's detail carries the telemetry
    registry snapshot, so BENCH rounds have per-stage attribution (parser
    rows, pipeline bytes) — not just the headline rows/sec."""
    proc = bench_run
    [line] = [l for l in proc.stdout.splitlines() if l.strip()]
    result = json.loads(line)
    families = result["detail"].get("telemetry")
    assert isinstance(families, dict) and families, result["detail"].keys()
    # the untimed pipeline smoke parses 2000 libsvm rows through the real
    # text parser — that attribution must be present and exact
    rows = sum(s["value"]
               for s in families["dmlc_parser_rows_total"]["samples"])
    assert rows == 2000, families["dmlc_parser_rows_total"]
