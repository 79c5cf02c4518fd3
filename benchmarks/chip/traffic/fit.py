"""Traffic kind ``fit``: whole ``GBDT.fit_binned`` calls, back to back.

Set-up makes the configuration's rows on the device from the seed, bins
them with the model's own boundaries into the uint8 wire form (sharded
over the mesh's ``data`` axis when the cell takes more than one chip) and
runs one warm fit.  The window calls ``fit_binned`` until the time is up;
the fit in flight when the clock runs out is finished and counted.  Every
fit ends in ``block_until_ready``.

``check`` (outside the window) holds the program to the plain numpy
reference in ``reference/gbdt_hist.py``; see each check's message.

What the configuration's ``objective`` means (labels, further per-row
arrays, the reference's gradient, the loss both sides are compared by) is
``objectives/<objective>.py``'s, found by name; nothing here knows an
objective.
"""

from __future__ import annotations

import contextlib
import time

import numpy as np

from benchmarks.chip import datagen, objectives, stats
from benchmarks.chip.reference import gbdt_hist, tree_walk


# the GBDTParam fields a configuration states at its top level (the rounds
# are the cell's); whatever else its model needs comes in its "model" block
_TOP_LEVEL = ("max_depth", "num_bins", "learning_rate", "reg_lambda",
              "min_child_weight", "objective", "hist_method")


def model_params(config):
    """The configuration's ``model`` block: further ``GBDTParam`` fields
    by name (``handle_missing``, ``num_class``, ``subsample``, ...)."""
    from dmlc_core_tpu.models.gbdt import GBDTParam

    extra = dict(config.get("model") or {})
    for name in extra:
        if name in _TOP_LEVEL or name == "num_boost_round":
            raise ValueError(f"model.{name}: {name!r} has a key of its own "
                             f"(the configuration's top level, or the "
                             f"cell's rounds_per_fit)")
        if name not in GBDTParam.__fields__:
            raise ValueError(f"model.{name}: GBDTParam has no field "
                             f"{name!r} (has: {sorted(GBDTParam.__fields__)})")
    return extra


def make_model(config, rounds):
    """The model a configuration states: its top-level fields, the cell's
    rounds, and what its ``model`` block names."""
    from dmlc_core_tpu.models.gbdt import GBDT, GBDTParam

    return GBDT(GBDTParam(
        num_boost_round=rounds, **{k: config[k] for k in _TOP_LEVEL},
        **model_params(config)), num_feature=config["num_feature"])


def reference_params(config):
    """The same parameters as ``reference.gbdt_hist.boost`` takes them."""
    return {**{k: config[k] for k in _TOP_LEVEL if k != "hist_method"},
            "num_class": (config.get("model") or {}).get("num_class", 1)}


def fit_bins(config, seed, model):
    """Quantile boundaries from a seeded sample, as a user's job fits them
    (``GBDT.make_bins``)."""
    model.make_bins(datagen.device_sample(config, seed,
                                          config["bin_sample_rows"]))


def setup(ctx):
    import jax

    from dmlc_core_tpu.bridge.binning import wire_dtype
    from dmlc_core_tpu.parallel.mesh import data_sharding, make_mesh

    config, cell = ctx.config, ctx.cell
    rows = int(cell.get("rows", config["rows"]))
    model = make_model(config, int(cell["rounds_per_fit"]))
    fit_bins(config, ctx.seed, model)
    ctx.say(f"{config['num_bins']} quantile bins from a "
            f"{config['bin_sample_rows']}-row sample")
    mesh = sharding = None
    if len(ctx.devices) > 1:
        mesh = make_mesh(dict(config["mesh"]), devices=ctx.devices)
        sharding = data_sharding(mesh)
    *data, extras = datagen.device_binned(
        config, ctx.seed, rows, model.boundaries,
        wire_dtype(config["num_bins"]), sharding)
    jax.block_until_ready((data, extras))
    ctx.say(f"{rows} x {config['num_feature']} rows made and binned on the "
            f"device ({data[0].dtype}, {len(ctx.devices)} chip(s))")
    fit_args = objectives.load(config["objective"]).fit_args(**extras)
    state = {"model": model, "data": tuple(data), "extras": extras,
             "fit_args": fit_args, "mesh": mesh, "rows": rows,
             "rounds": int(cell["rounds_per_fit"])}
    with _under(mesh):
        state["method"] = model._fit_method(data[0])
        start = time.perf_counter()
        state["warm"] = _fit(state)
    ctx.say(f"warm fit ({state['method']}): "
            f"{time.perf_counter() - start:.3f} s")
    return state


def _under(mesh):
    return mesh if mesh is not None else contextlib.nullcontext()


def _fit(state):
    import jax

    ensemble, margin = state["model"].fit_binned(*state["data"],
                                                 **state["fit_args"])
    jax.block_until_ready(margin)
    return ensemble, margin


def window(ctx, state, t_start):
    seconds = []
    with _under(state["mesh"]):
        t0 = time.perf_counter()
        while True:
            start = time.perf_counter()
            last = _fit(state)
            end = time.perf_counter()
            seconds.append(end - start)
            if end - t0 >= ctx.seconds:
                break
    ctx.say(f"{len(seconds)} fits of {state['rounds']} rounds in "
            f"{end - t0:.3f} s; the first: "
            f"{[round(s, 4) for s in seconds[:12]]}")
    return {"setup_s": t0 - t_start, "fit_seconds": seconds, "last": last,
            "attempted": len(seconds), "failed": 0}


def end_to_end(ctx, state, window):
    per_fit = state["rows"] * state["rounds"]
    return {"setup_s": window["setup_s"],
            "train_rows_per_s":
                per_fit / stats.median(window["fit_seconds"])}


def hist_case(ctx, state):
    """What the histogram line compares on: the first ``check.hist_rows``
    of the fit's own bins under seeded node ids, g and h at the deepest
    level's node count: ``(bins, node, g, h, num_nodes)``."""
    n = min(int(ctx.config["check"]["hist_rows"]), state["rows"])
    nodes = 2 ** (ctx.config["max_depth"] - 1)
    rng = np.random.default_rng([ctx.seed, 0xC4EC])
    hb = np.asarray(state["data"][0][:n])
    node = rng.integers(0, nodes, n).astype(np.int32)
    g = rng.standard_normal(n).astype(np.float32)
    h = np.abs(rng.standard_normal(n)).astype(np.float32)
    return hb, node, g, h, nodes


def sample(ctx, state):
    """What the loss line compares on: the first ``check.sample_rows`` of
    the fit's own rows, cut where the objective allows, on the host:
    ``(bins, label, extras)``."""
    objective = objectives.load(ctx.config["objective"])
    m = objective.sample(min(int(ctx.config["check"]["sample_rows"]),
                             state["rows"]), **state["extras"])
    bins, label, _ = state["data"]
    return (np.asarray(bins[:m]), np.asarray(label[:m]),
            {name: np.asarray(rows[:m])
             for name, rows in state["extras"].items()})


def hist_excess(got, ref, rtol):
    """The least atol at which ``got`` (G, H) would pass against ``ref`` at
    ``rtol``: the worst ``|got - ref| - rtol |ref|`` over every bucket."""
    return max(float((np.abs(np.asarray(a) - b) - rtol * np.abs(b)).max())
               for a, b in zip(got, ref))


def check(ctx, state, window):
    import jax

    from dmlc_core_tpu.ops.histogram import grad_histogram

    config, model = ctx.config, state["model"]
    spec = config["check"]
    objective = objectives.load(config["objective"])
    miss = datagen.reserved_bin(config)
    bins, label, weight = state["data"]
    extras = state["extras"]
    ensemble, margin = window["last"]
    want = config["expect_hist_method"]
    yield (state["method"] == want,
           f"hist_method={config['hist_method']!r} resolved to "
           f"{state['method']!r} (want {want!r})")

    # a fit is deterministic in the seed: the warm fit and the window's
    # last fit chose the same splits
    same = all(np.array_equal(np.asarray(state["warm"][0][i]),
                              np.asarray(ensemble[i]))
               for i in (0, 1, 3))        # features, thresholds, directions
    yield same, "two fits of the same rows chose identical splits"

    # the kernel against the exact bincount histogram, deepest level's nodes
    hb, node, g, h, nodes = hist_case(ctx, state)
    n = hb.shape[0]
    got = grad_histogram(hb, node, g, h, num_nodes=nodes,
                         num_bins=config["num_bins"],
                         method=state["method"])
    ref = gbdt_hist.histogram(hb, node, g, h, nodes, config["num_bins"])
    need = hist_excess(got, ref, spec["hist_rtol"])
    yield (need <= spec["hist_atol"],
           f"{state['method']} histogram == bincount histogram at "
           f"{n} rows x {nodes} nodes (rtol {spec['hist_rtol']}): worst "
           f"excess over rtol {need:.4f} (atol {spec['hist_atol']})")

    # the program's fit of a seeded subsample against the plain reference's
    # fit of the same bins: same parameters, same rounds
    sb, sl, sx = sample(ctx, state)
    m = sb.shape[0]
    _, ref_margin = gbdt_hist.boost(sb, sl, state["rounds"],
                                    missing=miss is not None, extras=sx,
                                    **reference_params(config))
    ref_loss = objective.loss(ref_margin, sl, **sx)
    _, sub_margin = model.fit_binned(sb, sl, **objective.fit_args(**sx))
    sub_loss = objective.loss(sub_margin, jax.numpy.asarray(sl), **sx)
    tol = spec["logloss_tolerance"]
    yield (abs(sub_loss - ref_loss) <= tol,
           f"train {objective.LOSS} after {state['rounds']} rounds on {m} "
           f"sampled rows: program {sub_loss:.5f} vs reference "
           f"{ref_loss:.5f}, "
           f"{abs(sub_loss - ref_loss):.2e} apart (tolerance {tol})")
    # the whole fit: its returned margins are what a plain walk of its own
    # trees gives on the sampled rows, and its loss is the sample's but for
    # the sample's overfit
    rows64 = sb.astype(np.int64)
    trees = [np.asarray(a) for a in ensemble[:3]]
    directions = np.asarray(ensemble[3])
    fitted = np.asarray(margin[:m])
    walked = tree_walk.margins(rows64, *trees, default_left=directions,
                               miss_id=miss)
    worst = float(np.abs(walked - fitted).max())
    yield (worst <= spec["margin_atol"],
           f"the {state['rows']}-row fit's margins equal a numpy walk of "
           f"its own trees on {m} rows: worst difference {worst:.2e} "
           f"(atol {spec['margin_atol']})")
    if miss is not None:
        # the mechanism worked in the fit that was timed: some splits send
        # their absent rows left, and the margins say so
        apart = float(np.abs(tree_walk.margins(rows64, *trees)
                             - fitted).max())
        least = int(spec["min_default_left"])
        yield (apart > spec["margin_atol"] and directions.sum() >= least,
               f"{int(directions.sum())} of the fit's "
               f"{int((trees[0] >= 0).sum())} splits send absent rows left "
               f"(at least {least}), and a walk that sends them all right "
               f"differs from the fit's margins by {apart:.2e} (more than "
               f"{spec['margin_atol']})")
    full_loss = objective.loss(margin, label, **extras)
    band = spec["full_vs_sample_band"]
    yield (abs(full_loss - sub_loss) <= band and np.isfinite(full_loss),
           f"train {objective.LOSS} of the whole {state['rows']}-row fit "
           f"{full_loss:.5f}: {abs(full_loss - sub_loss):.5f} from the "
           f"sample's (band {band})")

    if state["mesh"] is not None:
        with state["mesh"]:
            hlo = model._fit_fn(state["rounds"], state["method"]).lower(
                bins, label, weight).compile().as_text()
        full = f"[{bins.shape[0]},{config['num_feature']}]"
        shard_rows = {s.data.shape[0] for s in bins.addressable_shards}
        yield (all(mark in hlo for mark in spec["hlo_has"])
               and full not in hlo
               and shard_rows == {state["rows"] // len(ctx.devices)},
               f"optimized HLO has {spec['hlo_has']} and no full {full} "
               f"operand; every chip holds "
               f"{state['rows'] // len(ctx.devices)} rows")
