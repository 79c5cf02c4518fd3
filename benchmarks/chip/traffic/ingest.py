"""Traffic kind ``ingest``: libsvm text -> the device-resident uint8 set.

Set-up writes the cell's seeded libsvm file (kept per configuration in the
cache directory and reused while the seed is the same; only the newest is
kept), fits the binner on a seeded sample and runs one warm epoch.  The
window loops whole epochs of the program's own path

    create_parser(path, type="libsvm") -> binned_batches(parser, binner,
    batch_rows) -> DeviceFeedLoader(prefetch) -> concatenate on the device

with the program's default thread counts, each ending in
``block_until_ready``; the epoch in flight when the clock runs out is
finished and counted.  The consumer keeps an epoch's batches and
concatenates them into the resident ``(bins, label, weight)``, as
``chip_smoke.feed`` does, dropping the previous epoch's.
"""

from __future__ import annotations

import glob
import os
import time

import numpy as np

from benchmarks.chip import datagen, stats
from benchmarks.chip.reference import tree_walk


def _libsvm_file(ctx, rows):
    name = f"{ctx.config['name']}.rows{rows}.seed{ctx.seed}.libsvm"
    path = os.path.join(ctx.cache_dir, name)
    if os.path.exists(path):
        ctx.say(f"reusing {path}")
        return path
    for old in glob.glob(os.path.join(
            ctx.cache_dir, f"{ctx.config['name']}.rows*.libsvm*")):
        os.remove(old)
    start = time.perf_counter()
    datagen.write_libsvm(path, ctx.config, ctx.seed, rows)
    ctx.say(f"wrote {rows} rows, {os.path.getsize(path) / 1e6:.0f} MB of "
            f"libsvm text in {time.perf_counter() - start:.2f} s")
    return path


def setup(ctx):
    from dmlc_core_tpu.bridge.binning import fit_binner
    from dmlc_core_tpu.data.factory import create_parser

    config, cell = ctx.config, ctx.cell
    rows = int(cell["rows"])
    path = _libsvm_file(ctx, rows)
    sample, _ = datagen.host_rows(config, ctx.seed,
                                  min(rows, config["bin_sample_rows"]))
    binner = fit_binner(datagen.printed_values(sample), config["num_bins"])
    parser = create_parser(path, type="libsvm")
    state = {"path": path, "rows": rows, "binner": binner, "parser": parser,
             "batch_rows": int(cell["batch_rows"]),
             "prefetch": int(cell["prefetch"]), "timer": _NextTimer()}
    start = time.perf_counter()
    state["warm"] = _epoch(ctx, state)
    ctx.say(f"warm epoch: {time.perf_counter() - start:.3f} s")
    return state


class _NextTimer:
    """Wraps the host-batch iterator handed to the loader and times every
    ``next()``: densify + bin of one batch, with any wait on the parser."""

    def __init__(self):
        self.seconds = []

    def wrap(self, batches):
        it = iter(batches)
        while True:
            start = time.perf_counter()
            try:
                batch = next(it)
            except StopIteration:
                return
            self.seconds.append(time.perf_counter() - start)
            yield batch


def _epoch(ctx, state):
    import jax
    import jax.numpy as jnp

    from dmlc_core_tpu.bridge.binning import binned_batches
    from dmlc_core_tpu.bridge.loader import DeviceFeedLoader

    parser, binner = state["parser"], state["binner"]

    def source():
        parser.before_first()
        return state["timer"].wrap(
            binned_batches(parser, binner, state["batch_rows"]))

    placed = list(DeviceFeedLoader(source, device=ctx.devices[0],
                                   prefetch=state["prefetch"]))
    resident = tuple(jnp.concatenate([getattr(b, name) for b in placed])
                     for name in ("bins", "label", "weight"))
    jax.block_until_ready(resident)
    return resident, len(placed)


def window(ctx, state, t_start):
    seconds, batches = [], 0
    state["timer"].seconds.clear()
    t0 = time.perf_counter()
    while True:
        start = time.perf_counter()
        resident, n = _epoch(ctx, state)
        end = time.perf_counter()
        seconds.append(end - start)
        batches += n
        if end - t0 >= ctx.seconds:
            break
    state["parser"].close()
    ctx.say(f"{len(seconds)} epochs in {end - t0:.3f} s; the first: "
            f"{[round(s, 4) for s in seconds[:12]]}")
    return {"setup_s": t0 - t_start, "epoch_seconds": seconds,
            "window_s": end - t0, "resident": resident,
            "bin_batch_seconds": list(state["timer"].seconds),
            "attempted": batches, "failed": 0}


def end_to_end(ctx, state, window):
    return {"setup_s": window["setup_s"],
            "ingest_rows_per_s":
                state["rows"] / stats.median(window["epoch_seconds"])}


def check(ctx, state, window):
    config = ctx.config
    bins, label, weight = (np.asarray(a) for a in window["resident"])
    real = weight > 0
    yield (int(real.sum()) == state["rows"]
           and int((np.asarray(state["warm"][0][2]) > 0).sum())
           == state["rows"],
           f"rows delivered == rows in the file ({int(real.sum())} of "
           f"{state['rows']}) in the warm and the last epoch")
    yield (bins.dtype == state["binner"].dtype,
           f"the resident set is in the wire dtype ({bins.dtype})")
    # a seeded sample of delivered rows against searchsorted(side="right")
    # over the printed values (rows arrive in file order)
    rng = np.random.default_rng([ctx.seed, 0x1AE5])
    pick = np.sort(rng.choice(state["rows"], size=min(2000, state["rows"]),
                              replace=False))
    x, y = datagen.host_rows(config, ctx.seed, state["rows"])
    want = tree_walk.bin_rows(datagen.printed_values(x[pick]),
                              state["binner"].boundaries)
    got = bins[real][pick].astype(np.int64)
    yield (np.array_equal(got, want)
           and np.array_equal(label[real][pick], y[pick]),
           f"{pick.size} sampled delivered rows: bins equal "
           f"searchsorted(boundaries, printed value, side='right') and "
           f"labels equal the file's")
