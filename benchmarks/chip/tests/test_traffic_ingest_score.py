"""The ``ingest`` and ``score`` traffic kinds end to end at a rehearsal
size on the CPU, untraced and traced (the traced run exercises every
per-layer reader that needs no device plane)."""

import jax
import pytest

from benchmarks.chip import harness
from benchmarks.chip.tests import rehearsal

INGEST = {"name": "r.ingest", "kind": "ingest", "chips": 1, "rows": 3000,
          "batch_rows": 512, "prefetch": 2}
SCORE = {"name": "r.score", "kind": "score", "chips": 1, "rate_per_s": 60.0,
         "rows_per_request": [{"share": 0.5, "min": 1, "max": 1},
                              {"share": 0.5, "min": 2, "max": 8}],
         "workers": 4, "timeout_s": 10.0, "warmup_s": 0.2,
         "min_requests": 10}


def test_ingest_cell(tmp_path):
    cfg = rehearsal.config(hist_method="scatter")
    result, lines = rehearsal.run(INGEST, cfg, tmp_path, jax.devices()[:1])
    assert result["correct"], lines
    assert result["attempted"] >= 6 and result["failed"] == 0
    assert set(result["metrics"]) == {"ingest_rows_per_s", "setup_s"}
    # the same seed finds its file again; another seed replaces it
    result, lines = rehearsal.run(INGEST, cfg, tmp_path, jax.devices()[:1])
    assert any(line.startswith("reusing") for line in lines)
    rehearsal.run(INGEST, cfg, tmp_path, jax.devices()[:1], seed=8)
    assert len(list((tmp_path / "cache").glob("*.libsvm"))) == 1


def test_score_cell(tmp_path):
    cfg = rehearsal.config(hist_method="scatter")
    result, lines = rehearsal.run(SCORE, cfg, tmp_path, jax.devices()[:1],
                                  seconds=1.5)
    assert result["correct"], lines
    assert result["attempted"] >= 10 and result["failed"] == 0
    assert set(result["metrics"]) == {"score_p50_ms", "score_p99_ms",
                                      "setup_s"}
    assert 0 < result["metrics"]["score_p50_ms"]["value"] \
        <= result["metrics"]["score_p99_ms"]["value"]


@pytest.mark.parametrize("cell, readers", [
    (INGEST, {"parse_mb_per_s", "bin_batch_ms", "transfer_exposed_share"}),
    (SCORE, {"serve_transport_p50_ms", "serve_queue_p99_ms",
             "serve_batch_rows_mean", "serve_predict_p50_ms",
             "loadgen_late_p99_ms"}),
])
def test_span_and_counter_readers(cell, readers, tmp_path, monkeypatch):
    """A traced rehearsal: telemetry on, every span/counter reader of the
    kind finds its evidence.  The device trace is stubbed (a CPU run has
    no device plane; ``tracereduce`` is tested on a recorded chip trace)."""
    from benchmarks.chip import tracereduce

    class NoDeviceTrace:
        busy_s, window_s, chips = 0.0, 1.0, []

        def breakdown(self):
            return {"device_ops": [], "idle_gaps": []}

    monkeypatch.setattr(tracereduce, "load", lambda *a: NoDeviceTrace())
    manifest = dict(rehearsal.MANIFEST, per_layer=[
        {"name": m.NAME, "unit": m.UNIT}
        for m in harness.layer_metric_modules()])
    cfg = rehearsal.config(hist_method="scatter")
    cell = dict(cell, trace_after_s=0.1, trace_seconds=0.3)
    ctx, lines = rehearsal.context(cell, cfg, tmp_path, jax.devices()[:1],
                                   seconds=1.5, trace=True)
    result = harness.run_cell(ctx, manifest, 0.0)
    assert result["correct"], lines
    assert set(result["metrics"]) == readers, lines
    assert all(m["value"] >= 0 for m in result["metrics"].values())
    assert "breakdown" in result and "busy_s" in result["device"]
