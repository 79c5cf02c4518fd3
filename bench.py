#!/usr/bin/env python
"""Benchmark: hist-GBDT training throughput on the chip (BASELINE.json
metric "HIGGS rows/sec/chip (XGBoost hist)").

Workload: HIGGS-shaped synthetic data (28 dense features), quantile-binned to
256 bins, boosted depth-6 trees — the XGBoost hist configuration of the
north star.  The full stack is exercised (libsvm text -> parser -> RowBlock ->
dense batch -> HOST binning to uint8 (bridge/binning.py) -> staged-once
device feed -> jit'd boosting rounds); the timed region is training,
matching how XGBoost reports hist rows/sec.  The wire carries the binned
uint8 ids once (~1/12 the float path's host<->device bytes); the emitted
JSON's detail records `transfer_bytes` / `feed_rows_per_sec` next to the
train figure so a transfer-bound round is attributable.

One process, one device.  It runs on what ``dmlc_core_tpu.device.
init_device`` finds and says so in the result (``platform``,
``detail.device_kind``, ``detail.device_count``).  There is no probe child,
no retry and no fallback: without an accelerator, and without an explicit
``JAX_PLATFORMS=cpu``, it exits non-zero and prints no result.  The last
line of stdout is exactly one JSON object.
"""

import json
import os
import sys
import tempfile
import time

N_ROWS = int(os.environ.get("BENCH_ROWS", 200_000))
N_FEATURES = 28
NUM_BINS = 256
MAX_DEPTH = 6
ROUNDS = int(os.environ.get("BENCH_TPU_ROUNDS", 10))

_T0 = time.perf_counter()


def make_higgs_like(n, f, seed=0):
    import numpy as np

    rng = np.random.RandomState(seed)
    x = rng.randn(n, f).astype(np.float32)
    w = rng.randn(f).astype(np.float32)
    y = ((x @ w + 0.3 * rng.randn(n)) > 0).astype(np.float32)
    return x, y


def pipeline_smoke(tmpdir):
    """Prove the text pipeline end-to-end on a small shard (not timed)."""
    from dmlc_core_tpu.bridge.batching import dense_batches
    from dmlc_core_tpu.data.factory import create_parser

    x, y = make_higgs_like(2000, N_FEATURES, seed=3)
    path = os.path.join(tmpdir, "smoke.libsvm")
    with open(path, "w") as f:
        for yi, row in zip(y, x):
            feats = " ".join(f"{j}:{v:.4f}" for j, v in enumerate(row))
            f.write(f"{int(yi)} {feats}\n")
    parser = create_parser(path, type="libsvm")
    rows = 0
    for batch in dense_batches(parser, 512, N_FEATURES, drop_remainder=False):
        rows += batch.num_rows
    if rows != 2000:
        raise RuntimeError(f"pipeline smoke parsed {rows} of 2000 rows")


def log_stage(msg):
    """Timestamped progress marker on stderr."""
    print(f"[bench +{time.perf_counter() - _T0:8.1f}s] {msg}",
          file=sys.stderr, flush=True)


def time_fit(model, bins, y, rounds, device, method):
    """Time one compiled fit on ``device``.

    `bins` arrives in the binned wire dtype (uint8 at 256 bins — the
    device-feed format, bridge/binning.py).  The dataset is STAGED
    DEVICE-SIDE ONCE, outside the timed region, under a ``bench.stage``
    span with transfer accounting; the fit widens to int32 on device
    inside the compiled program (models/gbdt.py ``_widen_bins``), so PCIe
    and HBM carry the narrow bytes end to end.
    Returns ``(rows/sec, fit seconds, train acc, feed stats dict)``.
    """
    import jax
    import numpy as np

    from dmlc_core_tpu import telemetry

    fit = model._fit_fn(rounds, method)
    w = np.ones(len(y), np.float32)
    nbytes = int(bins.nbytes + y.nbytes + w.nbytes)
    log_stage(f"staging on {device.platform}: bins "
              f"{bins.nbytes / 1e6:.0f} MB ({bins.dtype}) + "
              f"labels/weights {(y.nbytes + w.nbytes) / 1e6:.0f} MB")
    stage_start = time.perf_counter()
    with telemetry.span("bench.stage", device=device.platform,
                        nbytes=nbytes, path="bench_stage"):
        b, yy, ww = jax.device_put((bins, y, w), device)
        jax.block_until_ready((b, yy, ww))
    stage_s = time.perf_counter() - stage_start
    telemetry.count("dmlc_transfer_bytes_total", nbytes, path="bench_stage")
    telemetry.count("dmlc_transfer_seconds_total", stage_s,
                    path="bench_stage", phase="dispatch")
    feed = {
        "transfer_bytes": nbytes,
        "stage_seconds": round(stage_s, 3),
        "feed_rows_per_sec": (round(len(y) / stage_s, 1) if stage_s > 0
                              else None),
        "wire_dtype": str(bins.dtype),
    }
    log_stage(f"staged once in {stage_s:.2f}s; compiling+warming fit")
    _, margin = fit(b, yy, ww)
    jax.block_until_ready(margin)  # compile + warm
    log_stage("warm fit done; timing")
    start = time.perf_counter()
    with telemetry.span("bench.timed_fit", device=device.platform,
                        rounds=rounds, method=method):
        _, margin = fit(b, yy, ww)
        jax.block_until_ready(margin)
    elapsed = time.perf_counter() - start
    log_stage(f"timed fit done: {elapsed:.3f}s")
    acc = float(((np.asarray(margin) > 0) == np.asarray(y)).mean())
    return len(y) * rounds / elapsed, elapsed, acc, feed


def run_bench(info):
    """Run the workload on the device ``info`` describes; return the
    result dict ``main`` prints."""
    import jax

    from dmlc_core_tpu import telemetry
    from dmlc_core_tpu.bridge.binning import HostBinner
    from dmlc_core_tpu.models.gbdt import GBDT, GBDTParam
    from dmlc_core_tpu.ops.histogram import resolve_hist_method

    # per-stage attribution: collect the whole run (parser/threadediter/
    # transfer metric families land in the registry) and attach the
    # registry snapshot to the emitted metric's detail below
    telemetry.enable()

    with tempfile.TemporaryDirectory() as tmpdir:
        pipeline_smoke(tmpdir)
    log_stage("pipeline smoke done")

    x, y = make_higgs_like(N_ROWS, N_FEATURES)
    param = GBDTParam(num_boost_round=ROUNDS, max_depth=MAX_DEPTH,
                      num_bins=NUM_BINS, learning_rate=0.3)
    model = GBDT(param, num_feature=N_FEATURES)
    model.make_bins(x[:50_000])
    log_stage(f"data + quantile boundaries ready ({N_ROWS} rows)")

    # binning is untimed setup and runs ON THE HOST (bridge/binning.py's
    # numpy searchsorted — no device round-trip at all): the device then
    # receives the uint8 bins once, 1/12 of the bytes the device-side
    # binning path moved (x f32 up + bins i32 back + bins i32 up again)
    binner = HostBinner(model.boundaries, NUM_BINS,
                        handle_missing=param.handle_missing)
    with telemetry.span("bench.host_binning", rows=N_ROWS):
        bins = binner.transform(x)
    log_stage(f"host-side binning done ({bins.dtype}, "
              f"{bins.nbytes / 1e6:.0f} MB)")

    device = jax.devices()[0]
    method = resolve_hist_method("auto")
    rps, seconds, acc, feed = time_fit(model, bins, y, ROUNDS, device, method)
    return {
        "metric": "gbdt_hist_train_rows_per_sec_per_chip",
        "value": round(rps, 1),
        "unit": (f"rows/sec ({N_ROWS} rows x {N_FEATURES} feat, "
                 f"depth-{MAX_DEPTH}, {NUM_BINS}-bin hist)"),
        "platform": info.platform,
        "detail": {
            "device": str(device),
            "device_kind": info.device_kind,
            "device_count": info.count,
            "compile_cache_dir": info.cache_dir,
            "hist_method": method,
            "rounds": ROUNDS,
            "seconds": round(seconds, 3),
            "train_acc": round(acc, 4),
            # device-feed accounting: the staged-once wire cost and feed
            # rate travel with the train figure, against the float path's
            # bytes for the same shape (x f32 up + bins i32 back + bins
            # i32 up) — the >=8x wire-reduction contract is asserted in
            # tests/test_bench_contract.py
            "transfer_bytes": feed["transfer_bytes"],
            "feed_rows_per_sec": feed["feed_rows_per_sec"],
            "stage_seconds": feed["stage_seconds"],
            "wire_dtype": feed["wire_dtype"],
            "float_path_bytes": 3 * N_ROWS * N_FEATURES * 4,
            # the registry snapshot — parser rows/bytes, threadediter
            # queue/stall counts, transfer bytes — one families dict,
            # keyed exactly like docs/observability.md's catalog
            "telemetry": telemetry.snapshot()["metrics"],
        },
    }


def main():
    from dmlc_core_tpu.device import init_device

    result = run_bench(init_device())
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
