"""Traffic kind ``score``: open-loop ``POST /v1/score`` at a fixed rate.

Set-up is the repo's own train -> publish -> serve loop: boost the
configuration's served ensemble on seeded rows made on the device,
``serving_state`` -> ``CheckpointManager.save`` -> ``build_runtime("gbdt",
checkpoint=...)`` -> ``ScoringServer`` with the program's defaults (default
transport, full bucket warm-up).  The published checkpoint is kept per
configuration in the cache directory and reused while the seed is the
same.  Load comes from ``loadgen.py`` in a child process that never imports
JAX (this process holds the chip): a short warm-up, then the measured
window, the samples handed back through a file.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np

from benchmarks.chip import datagen, loadgen, stats
from benchmarks.chip.reference import tree_walk
from benchmarks.chip.traffic import fit

HERE = os.path.dirname(os.path.abspath(__file__))


def _published(ctx):
    """Directory of the checkpoint published for (config, seed): trained
    and saved on a miss, manifest-verified either way."""
    import jax

    from dmlc_core_tpu.bridge.binning import wire_dtype
    from dmlc_core_tpu.bridge.checkpoint import CheckpointManager

    config, serve = ctx.config, ctx.config["serve"]
    name = (f"{config['name']}.trees{serve['trees']}.seed{ctx.seed}.ckpt")
    path = os.path.join(ctx.cache_dir, name)
    manager = CheckpointManager(path)
    if os.path.isdir(path) and manager.latest_valid(verify=True)[0] == 1:
        ctx.say(f"reusing the published checkpoint {path}")
        return manager
    for old in os.listdir(ctx.cache_dir):
        if old.startswith(f"{config['name']}.trees") and \
                old.endswith(".ckpt"):
            shutil.rmtree(os.path.join(ctx.cache_dir, old))
    manager = CheckpointManager(path)
    start = time.perf_counter()
    model = fit.make_model(config, int(serve["trees"]))
    fit.fit_bins(config, ctx.seed, model)
    *data, _ = datagen.device_binned(config, ctx.seed,
                                     int(serve["train_rows"]),
                                     model.boundaries,
                                     wire_dtype(config["num_bins"]))
    ensemble, margin = model.fit_binned(*data)
    jax.block_until_ready(margin)
    manager.save(1, model.serving_state(ensemble), async_=False)
    manager.wait_until_finished()
    ctx.say(f"boosted {serve['trees']} trees on {serve['train_rows']} rows "
            f"and published them in {time.perf_counter() - start:.2f} s")
    return manager


def setup(ctx):
    from dmlc_core_tpu.bridge.checkpoint import load_checkpoint
    from dmlc_core_tpu.serve.model_runtime import build_runtime
    from dmlc_core_tpu.serve.server import ScoringServer

    config, serve = ctx.config, ctx.config["serve"]
    manager = _published(ctx)
    verified = manager.latest_valid(verify=True)[0] == 1
    runtime = build_runtime("gbdt", config["num_feature"],
                            checkpoint=manager.step_uri(1))
    server = ScoringServer(runtime, max_batch=int(serve["max_batch"]),
                           max_delay_ms=float(serve["max_delay_ms"]))
    start = time.perf_counter()
    server.start()
    ctx.say(f"server up on {server.url} (transport {server.transport}, "
            f"buckets {server.batcher.buckets} warmed in "
            f"{time.perf_counter() - start:.2f} s)")
    cell_file = os.path.join(ctx.work_dir, "cell.json")
    with open(cell_file, "w") as f:
        json.dump(ctx.cell, f)
    out = os.path.join(ctx.work_dir, "samples.jsonl")
    cmd = [sys.executable, os.path.join(os.path.dirname(HERE), "loadgen.py"),
           "--url", server.url, "--cell", cell_file,
           "--num-feature", str(config["num_feature"]),
           "--seed", str(ctx.seed), "--seconds", str(ctx.seconds),
           "--keep", str(config["check"]["score_requests"]), "--out", out]
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("JAX_", "XLA_", "TPU_"))}
    child = subprocess.Popen(cmd, stdout=subprocess.PIPE, text=True, env=env)
    state = {"server": server, "child": child, "out": out,
             "verified": verified,
             "published": load_checkpoint(manager.step_uri(1))}
    try:
        line = child.stdout.readline()        # its warm-up is set-up
        if line.strip() != "START":
            raise RuntimeError(f"the load generator said {line!r}, not "
                               f"START (exit {child.poll()})")
    except BaseException:
        _stop(state)
        raise
    return state


def _stop(state):
    child = state["child"]
    if child.poll() is None:
        child.kill()
    child.wait()
    state["server"].close()


def window(ctx, state, t_start):
    t0 = time.perf_counter()
    child = state["child"]
    try:
        tail = child.stdout.read()
        rc = child.wait(timeout=ctx.seconds + 60)
    finally:
        _stop(state)
    if rc != 0:
        raise RuntimeError(f"the load generator exited {rc}: {tail!r}")
    summary = json.loads(tail.strip().splitlines()[-1])
    with open(state["out"]) as f:
        samples = [json.loads(line) for line in f]
    failed = sum(1 for s in samples if s["outcome"] != "ok") \
        + summary["offered"] - len(samples)
    latencies = stats.request_latencies_ms(samples)
    ctx.say(f"offered {summary['offered']} requests at "
            f"{summary['rate_per_s']:.1f}/s in {summary['wall_s']:.2f} s: "
            f"{len(latencies)} ok, {failed} failed; "
            f"p50 {stats.percentile(latencies, 0.5)} ms, "
            f"p99 {stats.percentile(latencies, 0.99)} ms; generator late "
            f"p99 {stats.percentile(stats.dispatch_lateness_ms(samples), 0.99)}"
            f" ms")
    return {"setup_s": t0 - t_start, "samples": samples,
            "window_s": summary["wall_s"], "offered": summary["offered"],
            "attempted": summary["offered"], "failed": failed}


def end_to_end(ctx, state, window):
    latencies = stats.request_latencies_ms(window["samples"])
    return {"setup_s": window["setup_s"],
            "score_p50_ms": stats.percentile(latencies, 0.50),
            "score_p95_ms": stats.percentile(latencies, 0.95),
            "score_p99_ms": stats.percentile(latencies, 0.99),
            "score_rows_per_s": sum(s["rows"] for s in window["samples"]
                                    if s["outcome"] == "ok")
            / window["window_s"]}


def check(ctx, state, window):
    config, samples = ctx.config, window["samples"]
    yield state["verified"], ("the served checkpoint matches its manifest "
                              "(CheckpointManager.latest_valid(verify))")
    ok = [s for s in samples if s["outcome"] == "ok"]
    yield (all(s["n_predictions"] == s["rows"] for s in ok) and bool(ok),
           f"every one of {len(ok)} ok answers carried its request's row "
           f"count")
    need = int(ctx.cell.get("min_requests", 0))
    yield (len(samples) >= need,
           f"the window held {len(samples)} requests (the tail needs "
           f">= {need})")
    flat = state["published"]
    arrays = [np.asarray(flat[f"['{k}']"]) for k in
              ("boundaries", "split_feat", "split_bin", "leaf_value")]
    kept = [s for s in ok if "predictions" in s]
    worst = 0.0
    for s in kept:
        rows = loadgen.request_rows(ctx.seed, s["index"], s["rows"],
                                    config["num_feature"])
        want = tree_walk.predict_logistic(rows, *arrays)
        worst = max(worst, float(np.abs(
            np.asarray(s["predictions"], np.float64) - want).max()))
    atol = config["check"]["score_atol"]
    yield (bool(kept) and worst <= atol,
           f"{len(kept)} sampled answers re-scored by a numpy tree walk "
           f"over the published arrays: worst difference {worst:.2e} "
           f"(atol {atol})")
