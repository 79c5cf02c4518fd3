#!/usr/bin/env python
"""The readings a fit cell's ``check`` limits are set from, on the chip at
the cell's own size: for each seed the numbers the check compares in a
sound run (the lower readings), and beside them what each CONTROL reads
(the upper ones):

- ``hist_float8``: the exact histogram of g and h rounded to float8 e4m3,
  the precision below the kernel's bfloat16, against the exact one (and
  ``hist_bfloat16``, the same rounding the kernel does, for comparison);
- ``logloss_level_short``: the program one tree level short, against the
  reference; ``logloss_blind_reference``: the program against a reference
  that ignores default directions (configurations with ``handle_missing``);
- ``band_learned_nothing``: the loss of the fit's starting margin (the
  objective's ``learned_nothing``: ln 2 for logistic) against the sample's
  loss, a whole fit that learned nothing;
- ``walk_without_directions``: the fit's margins against a walk of its
  trees that sends every absent row right.

    python3 benchmarks/chip/controls.py --workload <cell> --seeds 1,2,3

One process for all seeds (set-up is most of a run); one JSON line a seed.
The loss is the configuration's objective's (``objectives/<objective>.py``).
``--root <dir>`` reads the manifest, the cell and the configuration from
another tree (a scratch copy with a cell that is not admitted).  The
benchmark's own runs never call this.
"""

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

WINDOW_SECONDS = 2.0     # a short window at the cell's own load


def rounded(a, dtype):
    import jax.numpy as jnp

    return np.asarray(jnp.asarray(a).astype(dtype).astype(jnp.float32))


def readings(ctx):
    import jax.numpy as jnp

    from benchmarks.chip import datagen, objectives
    from benchmarks.chip.reference import gbdt_hist, tree_walk
    from benchmarks.chip.traffic import fit
    from dmlc_core_tpu.ops.histogram import grad_histogram

    config, spec = ctx.config, ctx.config["check"]
    objective = objectives.load(config["objective"])
    state = fit.setup(ctx)
    window = fit.window(ctx, state, time.perf_counter())
    ensemble, margin = window["last"]
    miss = datagen.reserved_bin(config)
    out = {"seed": ctx.seed, "method": state["method"]}

    bins = config["num_bins"]
    hb, node, g, h, nodes = fit.hist_case(ctx, state)
    exact = gbdt_hist.histogram(hb, node, g, h, nodes, bins)
    got = grad_histogram(hb, node, g, h, num_nodes=nodes, num_bins=bins,
                         method=state["method"])
    out["hist_program"] = fit.hist_excess(got, exact, spec["hist_rtol"])
    for name, dtype in (("hist_bfloat16", jnp.bfloat16),
                        ("hist_float8", jnp.float8_e4m3fn)):
        low = gbdt_hist.histogram(hb, node, rounded(g, dtype),
                                  rounded(h, dtype), nodes, bins)
        out[name] = fit.hist_excess(low, exact, spec["hist_rtol"])
    del exact, got, low

    sb, sl, sx = fit.sample(ctx, state)
    m = sb.shape[0]
    kw = dict(fit.reference_params(config), extras=sx)

    def program_loss(model):
        _, sub = model.fit_binned(sb, sl, **objective.fit_args(**sx))
        return objective.loss(np.asarray(sub), sl, **sx)

    _, ref = gbdt_hist.boost(sb, sl, state["rounds"],
                             missing=miss is not None, **kw)
    ref_loss = objective.loss(ref, sl, **sx)
    sub_loss = program_loss(state["model"])
    out["logloss_program"] = abs(sub_loss - ref_loss)
    shallow = fit.make_model({**config, "max_depth": config["max_depth"] - 1},
                             state["rounds"])
    shallow.set_boundaries(state["model"].boundaries)
    out["logloss_level_short"] = abs(program_loss(shallow) - ref_loss)
    full_loss = objective.loss(
        np.asarray(margin), np.asarray(state["data"][1]),
        **{name: np.asarray(rows) for name, rows in state["extras"].items()})
    out["band_program"] = abs(full_loss - sub_loss)
    out["band_learned_nothing"] = abs(
        objective.learned_nothing(sl, config, **sx) - sub_loss)
    out["loss"] = {"sample": sub_loss, "reference": ref_loss,
                   "whole": full_loss}

    trees = [np.asarray(a) for a in ensemble[:3]]
    fitted = np.asarray(margin[:m])
    rows64 = sb.astype(np.int64)
    out["walk_program"] = float(np.abs(tree_walk.margins(
        rows64, *trees, default_left=np.asarray(ensemble[3]), miss_id=miss)
        - fitted).max())
    if miss is not None:
        _, blind = gbdt_hist.boost(sb, sl, state["rounds"], **kw)
        out["logloss_blind_reference"] = abs(
            sub_loss - objective.loss(blind, sl, **sx))
        out["walk_without_directions"] = float(np.abs(
            tree_walk.margins(rows64, *trees) - fitted).max())
        out["default_left_splits"] = int(np.asarray(ensemble[3]).sum())
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--root", default=ROOT)
    args = ap.parse_args(argv)

    import jax

    from benchmarks.chip import harness
    from dmlc_core_tpu.device import init_device

    manifest = harness.load_manifest(args.root)
    cell, config = harness.load_cell(manifest, args.workload, args.root)
    info = init_device()
    if info.platform != "tpu" or info.count < cell["chips"]:
        print(f"controls of {args.workload} are read on the chip: JAX "
              f"reports {info.count} device(s) of platform "
              f"{info.platform!r}", file=sys.stderr)
        return 3
    for seed in (int(s) for s in args.seeds.split(",")):
        start = time.perf_counter()
        ctx = harness.Context(
            cell=cell, config=config, seed=seed, seconds=WINDOW_SECONDS,
            trace=False, devices=jax.devices()[:cell["chips"]],
            cache_dir="", work_dir="",
            say=lambda msg: print(f"[controls] {msg}", flush=True))
        out = readings(ctx)
        out["seconds"] = round(time.perf_counter() - start, 1)
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
