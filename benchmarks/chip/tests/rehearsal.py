"""Tiny rehearsal configurations and cells for the tests.  They exist to
walk every line of the traffic kinds on the CPU; they are never listed in
``BENCHMARK.json`` and nothing they time is a result."""

import copy
import time

from benchmarks.chip import harness

CONFIG = {
    "name": "rehearsal-hist", "rows": 4096, "num_feature": 5, "num_bins": 16,
    "max_depth": 3, "learning_rate": 0.3, "reg_lambda": 1.0,
    "min_child_weight": 1.0, "objective": "logistic",
    "hist_method": "pallas", "expect_hist_method": "pallas",
    "bin_sample_rows": 2000, "mesh": None,
    "data": {"cardinality": [0, 0, 0, 0, 0], "label_noise": 0.3},
    "serve": {"trees": 6, "train_rows": 2048, "max_batch": 8,
              "max_delay_ms": 2.0},
    "check": {"hist_rows": 1024, "hist_rtol": 0.02, "hist_atol": 0.06,
              "sample_rows": 2048, "logloss_tolerance": 0.01,
              "full_vs_sample_band": 0.05, "margin_atol": 1e-4, "score_requests": 20,
              "score_atol": 1e-5, "hlo_has": ["all-reduce"]},
}

MANIFEST = {
    "workloads": [],
    "configs": [],
    "end_to_end": [
        {"name": "train_rows_per_s", "unit": "rows/s",
         "workloads": ["r.fit", "r.fit.dp4"]},
        {"name": "ingest_rows_per_s", "unit": "rows/s",
         "workloads": ["r.ingest"]},
        {"name": "score_p50_ms", "unit": "ms", "workloads": ["r.score"]},
        {"name": "score_p99_ms", "unit": "ms", "workloads": ["r.score"]},
        {"name": "setup_s", "unit": "s"},
    ],
    "per_layer": [],
}


def config(**changes):
    out = copy.deepcopy(CONFIG)
    out.update(changes)
    return out


def missing_config(**changes):
    """The rehearsal table with absent entries, from a column half empty
    to one 90% empty, and a model that handles them."""
    shares = [0.5, 0.6, 0.7, 0.8, 0.9]
    out = config(model={"handle_missing": True},
                 data={"cardinality": [0] * 5, "label_noise": 0.3,
                       "missing_share": shares, "missing_effect": 1.0})
    out["check"]["min_default_left"] = 2
    out.update(changes)
    return out


def context(cell, cfg, tmp_path, devices, seconds=0.5, trace=False, seed=7):
    """``(ctx, lines)``: a context whose ``say`` appends to ``lines``."""
    lines = []
    cache = tmp_path / "cache"
    work = tmp_path / "work"
    cache.mkdir(exist_ok=True)
    work.mkdir(exist_ok=True)
    ctx = harness.Context(cell=cell, config=cfg, seed=seed, seconds=seconds,
                          trace=trace, devices=devices,
                          cache_dir=str(cache), work_dir=str(work),
                          say=lines.append)
    return ctx, lines


def run(cell, cfg, tmp_path, devices, **kw):
    ctx, lines = context(cell, cfg, tmp_path, devices, **kw)
    return harness.run_cell(ctx, MANIFEST, time.perf_counter()), lines
