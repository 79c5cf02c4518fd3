"""``epsilon400k.d8.fit``, 256-leaf trees on the wide table, without the
chip: its fit compiled at full size for a DESCRIBED v5e (one kernel call a
tree level though the last level runs 2 node blocks x 16 feature blocks; a
chip's memory holds it, and a quarter of the chip is in use), and the
configuration's own limits put to their controls: a histogram one precision
lower, a fit one level short and a whole fit that learned nothing each
come out as not correct.

The described topology is ``test_compile_rehearsal.py``'s fixture: one
process describes it once, whichever file asks first.
"""

import jax

from benchmarks.chip import harness
from benchmarks.chip.reference import gbdt_hist
from benchmarks.chip.tests import rehearsal
from benchmarks.chip.tests.test_compile_rehearsal import (  # noqa: F401
    HBM_BYTES, compiled_fit, topo, total_bytes)
from benchmarks.chip.tests.test_epsilon_wide import KERNEL_CALL, _rounded
from benchmarks.chip.traffic import fit
from dmlc_core_tpu.ops import hist_pallas

CELL = {"name": "r.fit", "kind": "fit", "chips": 1, "rounds_per_fit": 1}


def _d8_config(**changes):
    """The configuration's file as the cell runs it, its ``check`` limits
    above all, with the exact CPU histogram in the kernel's place."""
    _, config = harness.load_cell(harness.load_manifest(),
                                  "epsilon400k.d8.fit")
    return {**config, "hist_method": "scatter",
            "expect_hist_method": "scatter", **changes}


def test_the_configuration_is_epsilon_at_depth_8():
    manifest = harness.load_manifest()
    _, deep = harness.load_cell(manifest, "epsilon400k.d8.fit")
    _, wide = harness.load_cell(manifest, "epsilon400k.fit")
    assert deep["max_depth"] == 8 and wide["max_depth"] == 6
    same = ("rows", "num_feature", "num_bins", "learning_rate", "reg_lambda",
            "min_child_weight", "objective", "hist_method",
            "expect_hist_method", "bin_sample_rows", "mesh", "data")
    assert all(deep[k] == wide[k] for k in same)
    assert deep["reduced_reason"] == {} and "model" not in deep
    plan = hist_pallas.hist_kernel_plan(None, deep["num_feature"],
                                        deep["max_depth"], deep["num_bins"])
    assert plan["level_node_blocks"] == "1,1,1,1,1,1,1,2"
    assert plan["feature_blocks"] == 16
    assert plan["bin_split"].endswith("6x48,4x64,2x128,2x128")
    # the check's histogram case: 128 nodes, 4 node blocks of the same call
    assert hist_pallas.hist_block_plan(
        2 ** (deep["max_depth"] - 1), deep["num_feature"],
        deep["num_bins"]) == (32, 128)
    assert deep["check"]["hist_rows"] % hist_pallas.BLOCK_ROWS == 0


def test_epsilon400k_d8_fit_compiles_for_one_described_chip(topo):  # noqa: F811
    from jax.sharding import SingleDeviceSharding

    one = SingleDeviceSharding(topo.devices[0])
    deep, config = compiled_fit("epsilon400k.d8.fit", (one, one))
    # the scan's body holds one kernel call a tree level: the last level's
    # two node blocks are steps of its call's grid, not calls
    assert deep.as_text().count(KERNEL_CALL) == config["max_depth"] == 8
    assert 0.25 * 16e9 < total_bytes(deep) < HBM_BYTES


def test_fit_check_fails_a_float8_histogram_at_the_cell_limits(
        tmp_path, monkeypatch):
    """The cell's ``hist_rows`` under 128 nodes x 256 bins, two rows a
    bucket, at ``hist_rtol`` / ``hist_atol`` of the configuration's file: a
    histogram of bfloat16 g and h (what the kernel computes) is inside
    them, one of float8 e4m3 (the precision below) is not.  Eight features
    instead of 2,000 keep the bincount to seconds; a bucket's error does
    not know how many features lie beside it."""
    import jax.numpy as jnp

    from dmlc_core_tpu.ops import histogram

    cfg = _d8_config(num_feature=8, max_depth=8,
                     data={"cardinality": [0] * 8, "label_noise": 0.3})
    cfg["check"] = {**cfg["check"], "sample_rows": 2048}
    ctx, _ = rehearsal.context(CELL, cfg, tmp_path, jax.devices()[:1])
    state = fit.setup(ctx)
    window = fit.window(ctx, state, 0.0)
    line = (f"histogram == bincount histogram at {cfg['check']['hist_rows']} "
            f"rows x 128 nodes")

    def hist_line(dtype):
        monkeypatch.setattr(histogram, "grad_histogram", _rounded(dtype))
        (ok,) = [ok for ok, what in fit.check(ctx, state, window)
                 if line in what]
        return ok

    assert hist_line(jnp.bfloat16)
    assert not hist_line(jnp.float8_e4m3fn)


def test_fit_check_fails_a_dropped_level_at_the_cell_limits(tmp_path,
                                                            monkeypatch):
    """The configuration's 2,000 features, 256 bins, depth 8, 3 rounds and
    ``logloss_tolerance`` on its ``sample_rows``: the program's fit is
    within the tolerance of the plain reference's, and a fit that grows
    seven levels is outside it; and at ``full_vs_sample_band`` a whole fit
    that learned nothing is outside the band."""
    plain, fitted = gbdt_hist.boost, []

    def once(*args, **kw):
        """The reference's two minutes, paid once: both walks of the check
        below hand it the same sample."""
        if not fitted:
            fitted.append(plain(*args, **kw))
        return fitted[0]

    monkeypatch.setattr(gbdt_hist, "boost", once)
    cfg = _d8_config()
    rows = cfg["check"]["sample_rows"]
    cell = {**CELL, "rounds_per_fit": 3, "rows": rows}
    ctx, _ = rehearsal.context(cell, cfg, tmp_path, jax.devices()[:1],
                               seconds=0.0)
    state = fit.setup(ctx)
    window = fit.window(ctx, state, 0.0)
    line = f"train logloss after 3 rounds on {rows} sampled rows"

    def logloss_line(model):
        (ok,) = [ok for ok, what in fit.check(ctx, {**state, "model": model},
                                              window) if line in what]
        return ok

    assert logloss_line(state["model"])
    shallow = fit.make_model({**cfg, "max_depth": cfg["max_depth"] - 1}, 3)
    shallow.set_boundaries(state["model"].boundaries)
    assert not logloss_line(shallow)

    # ``full_vs_sample_band`` on the same sample: a whole fit that learned
    # nothing (margins of 0, a loss of ln 2) stands further from the
    # sample's loss than the band allows.  (Here the timed fit IS the
    # sample's, so the sound reading is 0; on the chip it is the sample's
    # overfit, and the file gives both readings.)
    band = "train logloss of the whole"

    def band_line(last):
        (ok,) = [ok for ok, what in fit.check(ctx, state,
                                              {**window, "last": last})
                 if band in what]
        return ok

    ensemble, margin = window["last"]
    assert band_line((ensemble, margin))
    assert not band_line((ensemble, jax.numpy.zeros_like(margin)))
