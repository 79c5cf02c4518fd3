#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

``python chip_smoke.py`` (no arguments, one process, no network) drives the
repo's main path once through the entry points a user calls, at the flagship
GBDT's full width (28 features, 256 bins, depth 6, 10 rounds, 200k rows):

    seeded HIGGS-shaped rows -> libsvm text -> native parser -> fit_binner /
    HostBinner (uint8 wire) -> DeviceFeedLoader -> one compiled
    GBDT.fit_binned (Pallas hist kernel) -> serving_state through
    CheckpointManager -> build_runtime -> ScoringServer answering
    POST /v1/score

and checks what comes out by the repo's own means (see ``check_*``).  It
requires ``jax.devices()[0].platform == "tpu"``: any other platform, any
exception in any phase, or any failed check ends the run non-zero with no
result line.  On success the last line of stdout is one JSON object naming
the device as JAX reports it.  On a host with more than one chip it also
runs the sharded fits (``mesh_phase``).

The wall times printed are orientation for whoever reads the log — cold
numbers from a single run, compile included — NOT benchmark results.
"""

import contextlib
import faulthandler
import json
import os
import sys
import tempfile
import time
import urllib.request
from typing import NamedTuple

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, ROOT)


class Config(NamedTuple):
    rows: int
    num_feature: int
    num_bins: int
    max_depth: int
    rounds: int
    batch_rows: int          # device-feed batch (host rows per transfer)
    max_batch: int           # serving bucket ladder top
    request_sizes: tuple     # rows per POST /v1/score
    hist_method: str
    acc_floor: float
    seed: int = 0


# The flagship at full width; the size of the only driver chip run on record.
# acc_floor: the exact-f32 scatter fit of these same seeded rows on the CPU
# reaches train accuracy 0.8553 (this script's train_phase under
# JAX_PLATFORMS=cpu, PR 21); the floor leaves 0.01 for bf16 near-tie flips.
FLAGSHIP = Config(rows=200_000, num_feature=28, num_bins=256, max_depth=6,
                  rounds=10, batch_rows=8192, max_batch=64,
                  request_sizes=(1, 7, 64), hist_method="auto",
                  acc_floor=0.845)

# bf16-W kernel vs exact f32 scatter: the tolerance livetests/ uses
HIST_RTOL, HIST_ATOL = 2e-2, 6e-2

_T0 = time.perf_counter()


def say(msg):
    print(f"[smoke +{time.perf_counter() - _T0:7.1f}s] {msg}", flush=True)


def check(cond, what):
    if not cond:
        raise AssertionError(f"chip_smoke check failed: {what}")
    say(f"ok: {what}")


@contextlib.contextmanager
def timed(times, name):
    """``with timed(times, "fit"):`` records wall seconds under a name."""
    start = time.perf_counter()
    try:
        yield
    finally:
        times[name] = round(time.perf_counter() - start, 3)


def cache_entries(cache_dir):
    return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0


# -- phases ------------------------------------------------------------------

def device_phase():
    """State the device and the installation; never choose either."""
    import jax
    import jaxlib

    from dmlc_core_tpu.device import init_device

    info = init_device()
    try:
        import libtpu
        libtpu_version = getattr(libtpu, "__version__", "unknown")
    except ImportError:
        libtpu_version = "not installed"
    say(f"device: platform={info.platform} kind={info.device_kind} "
        f"count={info.count}; jax {jax.__version__} jaxlib "
        f"{jaxlib.__version__} libtpu {libtpu_version}; compile cache "
        f"{info.cache_dir} ({cache_entries(info.cache_dir)} entries)")
    return info


def native_phase():
    """The C++ parser core, built from native/*.cc in this run."""
    from dmlc_core_tpu import native_bridge

    check(native_bridge.available(),
          "native_bridge.available() (make -C native, then dlopen)")


def data_phase(cfg, workdir):
    """Seeded HIGGS-shaped rows written as libsvm text."""
    rng = np.random.RandomState(cfg.seed)
    x = rng.randn(cfg.rows, cfg.num_feature).astype(np.float32)
    w = rng.randn(cfg.num_feature).astype(np.float32)
    y = ((x @ w + 0.3 * rng.randn(cfg.rows)) > 0).astype(np.float32)
    path = os.path.join(workdir, "train.libsvm")
    with open(path, "w") as f:
        for yi, row in zip(y, x):
            feats = " ".join(f"{j}:{v:.4f}" for j, v in enumerate(row))
            f.write(f"{int(yi)} {feats}\n")
    say(f"wrote {cfg.rows} x {cfg.num_feature} libsvm rows "
        f"({os.path.getsize(path) / 1e6:.0f} MB)")
    return path


def feed(parser, binner, cfg, sharding=None):
    """libsvm rows -> uint8 BinnedBatch stream -> DeviceFeedLoader -> one
    device-resident (bins, label, weight); padding rows carry weight 0."""
    import jax.numpy as jnp

    from dmlc_core_tpu.bridge.binning import binned_batches
    from dmlc_core_tpu.bridge.loader import DeviceFeedLoader

    def epoch():
        parser.before_first()
        return binned_batches(parser, binner, cfg.batch_rows)

    placed = list(DeviceFeedLoader(epoch, sharding=sharding, prefetch=2))
    check(all(str(b.bins.dtype) == "uint8" for b in placed),
          f"device feed shipped uint8 bins ({len(placed)} batches)")
    return tuple(jnp.concatenate([getattr(b, name) for b in placed])
                 for name in ("bins", "label", "weight"))


def make_model(cfg, binner, model_axis=None):
    from dmlc_core_tpu.models.gbdt import GBDT, GBDTParam

    model = GBDT(GBDTParam(num_boost_round=cfg.rounds,
                           max_depth=cfg.max_depth, num_bins=cfg.num_bins,
                           learning_rate=0.3, hist_method=cfg.hist_method),
                 num_feature=cfg.num_feature, model_axis=model_axis)
    model.set_boundaries(binner.boundaries)
    return model


def accuracy(margin, label, weight):
    real = np.asarray(weight) > 0
    return float(((np.asarray(margin) > 0)
                  == (np.asarray(label) > 0.5))[real].mean())


def train_phase(cfg, libsvm_path, times):
    """text -> parser -> fit_binner -> device feed -> one compiled fit."""
    import jax

    from dmlc_core_tpu.bridge.binning import fit_binner
    from dmlc_core_tpu.data.factory import create_parser

    parser = create_parser(libsvm_path, type="libsvm")
    try:
        with timed(times, "parse_bin_feed_s"):
            binner = fit_binner(parser, cfg.num_bins,
                                num_feature=cfg.num_feature)
            bins, label, weight = feed(parser, binner, cfg)
            jax.block_until_ready(bins)
    finally:
        parser.close()
    check(int(np.asarray(weight).sum()) == cfg.rows,
          f"all {cfg.rows} rows reached the device")
    model = make_model(cfg, binner)
    method = model._fit_method(bins)
    with timed(times, "fit_compile_and_first_run_s"):
        ensemble, margin = model.fit_binned(bins, label, weight)
        jax.block_until_ready(margin)
    with timed(times, "fit_second_run_s"):
        ensemble, margin = model.fit_binned(bins, label, weight)
        jax.block_until_ready(margin)
    return {"model": model, "binner": binner, "ensemble": ensemble,
            "bins": bins, "label": label, "weight": weight,
            "method": method, "acc": accuracy(margin, label, weight)}


def check_train(cfg, trained, expect_method):
    from dmlc_core_tpu.ops.histogram import grad_histogram

    check(trained["method"] == expect_method,
          f"hist_method={cfg.hist_method!r} resolved to "
          f"{trained['method']!r} (want {expect_method!r})")
    ens = trained["ensemble"]
    n_internal = 2 ** cfg.max_depth - 1
    check(tuple(ens.split_feat.shape) == (cfg.rounds, n_internal)
          and tuple(ens.leaf_value.shape) == (cfg.rounds, n_internal + 1)
          and bool(np.isfinite(np.asarray(ens.leaf_value)).all()),
          f"ensemble is {cfg.rounds} finite depth-{cfg.max_depth} trees")
    check(trained["acc"] > cfg.acc_floor,
          f"train accuracy {trained['acc']:.4f} > floor {cfg.acc_floor}")
    # the kernel against the exact f32 scatter histogram of the same bins
    # on the same device, at the deepest level's node count.  Histograms
    # and accuracy, not split-by-split trees: bf16 can flip a near-tie.
    rows = min(8192, cfg.rows)
    nodes = 2 ** (cfg.max_depth - 1)
    rng = np.random.RandomState(cfg.seed + 1)
    args = (trained["bins"][:rows],
            rng.randint(0, nodes, rows).astype(np.int32),
            rng.randn(rows).astype(np.float32),
            np.abs(rng.randn(rows)).astype(np.float32))
    got = grad_histogram(*args, num_nodes=nodes, num_bins=cfg.num_bins,
                         method=trained["method"])
    ref = grad_histogram(*args, num_nodes=nodes, num_bins=cfg.num_bins,
                         method="scatter")
    for name, a, b in zip("GH", got, ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=HIST_RTOL, atol=HIST_ATOL,
                                   err_msg=f"{name} histogram vs scatter")
    say(f"ok: {trained['method']} histogram == scatter at {nodes} nodes x "
        f"{cfg.num_feature} x {cfg.num_bins} (rtol {HIST_RTOL}, atol "
        f"{HIST_ATOL})")


def http_json(url, payload=None):
    data = None if payload is None else json.dumps(payload).encode()
    req = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=60) as resp:
        return json.loads(resp.read())


def serve_phase(cfg, trained, workdir, times):
    """publish (serving_state via CheckpointManager) -> reload
    (build_runtime) -> serve (in-process ScoringServer; this process holds
    the chip, so there is no ``python -m dmlc_core_tpu.serve`` child)."""
    from dmlc_core_tpu import telemetry
    from dmlc_core_tpu.bridge.checkpoint import CheckpointManager
    from dmlc_core_tpu.serve.model_runtime import build_runtime
    from dmlc_core_tpu.serve.server import ScoringServer

    model, ensemble = trained["model"], trained["ensemble"]
    manager = CheckpointManager(os.path.join(workdir, "ckpt"))
    manager.save(1, model.serving_state(ensemble), async_=False)
    manager.wait_until_finished()
    check(manager.latest_valid(verify=True)[0] == 1,
          "checkpoint step 1 published and matches its manifest")
    runtime = build_runtime("gbdt", cfg.num_feature,
                            checkpoint=manager.step_uri(1))
    telemetry.enable()    # the warm-up counter is the repo's own witness

    def warmups():
        # the counter is the process's: a caller that served before (a
        # test worker) has counted already, so the witness is the difference
        family = telemetry.snapshot()["metrics"].get(
            "dmlc_serve_warmup_total", {"samples": []})
        return sum(s["value"] for s in family["samples"])

    before = warmups()
    server = ScoringServer(runtime, max_batch=cfg.max_batch)
    with timed(times, "serve_warmup_s"):
        server.start()
    try:
        buckets = server.batcher.buckets
        warmed = warmups() - before
        check(warmed == len(buckets),
              f"warm-up compiled every bucket ({int(warmed)} of "
              f"{len(buckets)}: {buckets})")
        health = http_json(server.url + "/healthz")
        check(health["status"] == "ok" and health["model"] == "gbdt"
              and health["num_feature"] == cfg.num_feature,
              f"/healthz ok ({health['model']}, "
              f"{health['num_feature']} features)")
        rng = np.random.RandomState(cfg.seed + 2)
        with timed(times, "requests_s"):
            for n in cfg.request_sizes:
                rows = rng.randn(n, cfg.num_feature).astype(np.float32)
                body = http_json(server.url + "/v1/score",
                                 {"instances": rows.tolist()})
                served = np.asarray(body["predictions"], np.float32)
                want = np.asarray(model.predict(
                    ensemble, trained["binner"].transform(rows)))
                np.testing.assert_allclose(
                    served, want, rtol=1e-6, atol=1e-6,
                    err_msg=f"/v1/score x{n} vs GBDT.predict")
                check(served.shape == (n,) and np.isfinite(served).all(),
                      f"POST /v1/score x{n} == GBDT.predict")
    finally:
        server.close()


def mesh_phase(cfg, libsvm_path, trained, info, times):
    """More than one chip: the same fit, rows sharded over every chip, and
    the model-sharded shard_map kernel — both compiled by Mosaic."""
    import jax

    from dmlc_core_tpu.data.factory import create_parser
    from dmlc_core_tpu.parallel.mesh import data_sharding, make_mesh

    def sharded_fit(mesh, model_axis, name):
        model = make_model(cfg, trained["binner"], model_axis=model_axis)
        parser = create_parser(libsvm_path, type="libsvm")
        try:
            bins, label, weight = feed(parser, trained["binner"], cfg,
                                       sharding=data_sharding(mesh))
        finally:
            parser.close()
        dp = mesh.shape["data"]
        bins = jax.device_put(bins, data_sharding(mesh, ndim=2))
        shard_rows = sorted({s.data.shape[0]
                             for s in bins.addressable_shards})
        check(shard_rows == [bins.shape[0] // dp]
              and len({s.device for s in bins.addressable_shards})
              == info.count,
              f"{name}: every chip holds 1/{dp} of the rows "
              f"({shard_rows[0]} of {bins.shape[0]})")
        with mesh:
            method = model._fit_method(bins)
            check(method == "pallas", f"{name}: method {method!r}")
            fit = model._fit_fn(cfg.rounds, method)
            hlo = fit.lower(bins, label, weight).compile().as_text()
            with timed(times, f"{name}_fit_second_run_s"):
                ensemble, margin = model.fit_binned(bins, label, weight)
                jax.block_until_ready(margin)
        check("tpu_custom_call" in hlo and "all-reduce" in hlo
              and f"[{bins.shape[0]},{cfg.num_feature}]" not in hlo,
              f"{name}: Mosaic kernel on per-chip row shards + all-reduce "
              f"(no full [{bins.shape[0]},{cfg.num_feature}] operand "
              f"anywhere in the optimized HLO)")
        acc = accuracy(margin, label, weight)
        same = all(np.array_equal(np.asarray(a), np.asarray(b)) for a, b in
                   zip(ensemble[:2], trained["ensemble"][:2]))
        check(abs(acc - trained["acc"]) < 0.005,
              f"{name}: train accuracy {acc:.4f} vs one chip "
              f"{trained['acc']:.4f}; splits identical to one chip: {same}")

    sharded_fit(make_mesh(), None, f"data={info.count}")
    if info.count % 2 == 0:
        sharded_fit(make_mesh({"data": info.count // 2, "model": 2}),
                    "model", f"data={info.count // 2} x model=2")


# -- the run -----------------------------------------------------------------

def main():
    # a wedged phase must end the run (non-zero, with every thread's stack)
    # inside the driver's 1200 s, not hold the chip
    faulthandler.dump_traceback_later(1100, exit=True)
    from dmlc_core_tpu.ops import hist_pallas

    times = {}
    info = device_phase()
    check(info.platform == "tpu",
          f"platform is 'tpu' (JAX reports {info.platform!r})")
    check(not hist_pallas._INTERPRET
          and "DMLC_TPU_PALLAS_INTERPRET" not in os.environ,
          "Pallas interpret mode is off and DMLC_TPU_PALLAS_INTERPRET unset")
    entries_before = cache_entries(info.cache_dir)
    with timed(times, "native_build_s"):
        native_phase()
    cfg = FLAGSHIP
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as workdir:
        with timed(times, "write_libsvm_s"):
            libsvm_path = data_phase(cfg, workdir)
        trained = train_phase(cfg, libsvm_path, times)
        check_train(cfg, trained, expect_method="pallas")
        blocks = trained["model"]._hist_blocks("pallas")
        say("hist_level calls, root first: node slots built "
            f"{blocks['built_nodes']} (the sibling is parent - built), "
            f"bin splits HxL {blocks['bin_split']}")
        serve_phase(cfg, trained, workdir, times)
        if info.count > 1:
            mesh_phase(cfg, libsvm_path, trained, info, times)
    times["total_s"] = round(time.perf_counter() - _T0, 3)
    say(f"compile cache entries: {entries_before} before, "
        f"{cache_entries(info.cache_dir)} after")
    say("wall seconds, orientation only, NOT benchmark numbers: "
        + json.dumps(times))
    faulthandler.cancel_dump_traceback_later()
    print(json.dumps({"ok": True, "device": info.as_dict()}), flush=True)


if __name__ == "__main__":
    main()
