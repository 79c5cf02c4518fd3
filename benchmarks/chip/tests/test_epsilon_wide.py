"""``epsilon400k.fit``, the wide cell, without the chip: its fit compiled at
full size for a DESCRIBED v5e (one kernel call a tree level, not one a
feature block; no one-hot of the bins; a chip's memory holds it), the
``fit`` traffic kind walked end to end on a wide rehearsal table whose
features are blocked, and ``hist_ns_per_row_feature`` on the trace recorded
on the chip.

The described topology is ``test_compile_rehearsal.py``'s fixture: one
process describes it once, whichever of the two files asks first.
"""

import os

import jax
import numpy as np
import pytest

from benchmarks.chip import harness, tracereduce
from benchmarks.chip.layer_metrics import (hist_ms_per_level,
                                           hist_ns_per_row_feature)
from benchmarks.chip.reference import gbdt_hist
from benchmarks.chip.tests import rehearsal
from benchmarks.chip.tests.test_compile_rehearsal import (  # noqa: F401
    HBM_BYTES, compiled_fit, topo, total_bytes)
from benchmarks.chip.traffic import fit
from dmlc_core_tpu.ops import hist_pallas

SCOPED = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "testdata", "smallfit_scoped.xplane.pb.gz")
KERNEL_CALL = 'custom_call_target="tpu_custom_call"'


def test_epsilon400k_fit_compiles_for_one_described_chip(topo):  # noqa: F811
    from jax.sharding import SingleDeviceSharding

    one = SingleDeviceSharding(topo.devices[0])
    wide, config = compiled_fit("epsilon400k.fit", (one, one))
    hlo = wide.as_text()
    narrow, _ = compiled_fit("higgs11m.fit", (one, one))
    # the scan's body holds one kernel call a tree level in both fits: 16
    # feature blocks are 16 steps of one call's grid, not 16 calls
    assert hlo.count(KERNEL_CALL) == narrow.as_text().count(KERNEL_CALL) \
        == config["max_depth"]
    # no one-hot of the bins: [rows, F * bins] bf16 would be 410 GB
    rows = -(-config["rows"] // hist_pallas.BLOCK_ROWS) \
        * hist_pallas.BLOCK_ROWS
    width = config["num_feature"] * config["num_bins"]
    assert f"[{rows},{width}]" not in hlo
    assert f"[{config['rows']},{width}]" not in hlo
    assert 0.25 * 16e9 < total_bytes(wide) < HBM_BYTES


@pytest.fixture()
def blocked(monkeypatch):
    """Interpret mode, and a VMEM budget of 8 node slots x 128 features x
    16 bins: the rehearsal's 260 features run as three feature blocks."""
    monkeypatch.setattr(hist_pallas, "_INTERPRET", True)
    monkeypatch.setattr(hist_pallas, "_ACC_BYTES_LIMIT",
                        2 * 8 * 128 * 16 * 4)


WIDE = dict(num_feature=260, rows=2048,
            data={"cardinality": [0] * 260, "label_noise": 0.3})
CELL = {"name": "r.fit", "kind": "fit", "chips": 1, "rounds_per_fit": 2}


def test_fit_cell_on_a_wide_table_walks_every_check(blocked, tmp_path):
    cfg = rehearsal.config(**WIDE)
    assert hist_pallas.hist_block_plan(
        2 ** (cfg["max_depth"] - 1), 260, cfg["num_bins"]) == (4, 128)
    result, lines = rehearsal.run(CELL, cfg, tmp_path, jax.devices()[:1])
    assert result["correct"], lines
    assert result["failed"] == 0 and result["attempted"] >= 1
    said = "\n".join(lines)
    for what in ("resolved to 'pallas'", "identical splits",
                 "pallas histogram == bincount histogram at 1024 rows x 4 "
                 "nodes", "train logloss after 2 rounds",
                 "equal a numpy walk of its own trees",
                 "nothing compiled inside the window"):
        assert what in said, what


def test_fit_check_catches_a_dropped_feature_block(blocked, tmp_path,
                                                   monkeypatch):
    """A kernel that leaves a feature block's histogram columns empty fails
    the histogram check, which walks every block against the reference."""
    cfg = rehearsal.config(**WIDE)
    ctx, _ = rehearsal.context(CELL, cfg, tmp_path, jax.devices()[:1])
    state = fit.setup(ctx)
    window = fit.window(ctx, state, 0.0)
    whole = hist_pallas.hist_matmul_pallas

    def without_the_last_block(w, bins, num_bins, **kw):
        out = whole(w, bins, num_bins, **kw)
        return out.at[:, 256 * num_bins:].set(0.0)

    monkeypatch.setattr(hist_pallas, "hist_matmul_pallas",
                        without_the_last_block)
    failed = [what for ok, what in fit.check(ctx, state, window) if not ok]
    assert any("histogram == bincount histogram" in what
               for what in failed), failed


def test_hist_ns_per_row_feature_on_the_recorded_trace():
    """Two fits of 2 rounds, depth 6, 32,768 x 28 rows, traced on a TPU v5
    lite (my chip run, PR 24): 24 kernel calls."""
    trace = tracereduce.from_profile(tracereduce.read_profile(SCOPED))
    evidence = {"trace": trace, "config": {"num_feature": 28},
                "state": {"rows": 32768}}
    per_level_ms = hist_ms_per_level.reduce(evidence)
    value = hist_ns_per_row_feature.reduce(evidence)
    assert value == pytest.approx(per_level_ms * 1e6 / (32768 * 28))
    assert 0.2 < value < 0.3
    # a trace without the kernel gives nothing to read, and does not raise
    (chip,) = trace.chips
    bare = tracereduce.Trace([tracereduce.ChipTrace(
        chip.chip, [o for o in chip.ops if not o.is_mosaic], [], [])])
    assert hist_ns_per_row_feature.reduce({**evidence,
                                           "trace": bare}) is None


# -- the configuration's own limits have power: the precision below the
# -- stated one, and a tree level left out, each come out as not correct

def _epsilon_config(**changes):
    """The configuration's file as the cell runs it — its ``check`` limits
    above all — with the exact CPU histogram in the kernel's place."""
    _, config = harness.load_cell(harness.load_manifest(), "epsilon400k.fit")
    return {**config, "hist_method": "scatter",
            "expect_hist_method": "scatter", **changes}


def _rounded(dtype):
    """``grad_histogram``'s stand-in: the exact histogram of g and h rounded
    to ``dtype``, which is what a kernel multiplying in ``dtype`` returns."""
    import jax.numpy as jnp

    def through(a):
        return np.asarray(jnp.asarray(a).astype(dtype).astype(jnp.float32))

    def grad_histogram(bins, node, g, h, num_nodes, num_bins, method):
        return gbdt_hist.histogram(bins, node, through(g), through(h),
                                   num_nodes, num_bins)
    return grad_histogram


def test_fit_check_fails_a_float8_histogram_at_the_cell_limits(
        tmp_path, monkeypatch):
    """The cell's ``hist_rows`` under 32 nodes x 256 bins, 49 rows a bucket,
    at ``hist_rtol`` / ``hist_atol`` of the configuration's file:
    a histogram of bfloat16 g and h (what the kernel computes) is inside
    them, one of float8 e4m3 (the precision below) is not.  Eight features
    instead of 2,000 keep the bincount to seconds; a bucket's error does
    not know how many features lie beside it, and the 250 times as many
    buckets of the cell only reach further into float8's tail."""
    import jax.numpy as jnp

    from dmlc_core_tpu.ops import histogram

    cfg = _epsilon_config(num_feature=8, data={"cardinality": [0] * 8,
                                               "label_noise": 0.3})
    cfg["check"] = {**cfg["check"], "sample_rows": 2048}
    cell = {**CELL, "rounds_per_fit": 1}
    ctx, _ = rehearsal.context(cell, cfg, tmp_path, jax.devices()[:1])
    state = fit.setup(ctx)
    window = fit.window(ctx, state, 0.0)
    line = (f"histogram == bincount histogram at {cfg['check']['hist_rows']} "
            f"rows x 32 nodes")

    def hist_line(dtype):
        monkeypatch.setattr(histogram, "grad_histogram", _rounded(dtype))
        (ok,) = [ok for ok, what in fit.check(ctx, state, window)
                 if line in what]
        return ok

    assert hist_line(jnp.bfloat16)
    assert not hist_line(jnp.float8_e4m3fn)


def test_fit_check_fails_a_dropped_level_at_the_cell_limits(tmp_path,
                                                            monkeypatch):
    """The configuration's 2,000 features, 256 bins, 3 rounds and
    ``logloss_tolerance`` on a 16,384-row sample: the program's fit is
    within the tolerance of the plain reference's, and a fit that grows
    one level fewer is outside it."""
    plain, fitted = gbdt_hist.boost, []

    def once(*args, **kw):
        """The reference's 35 s, paid once: both walks of the check below
        hand it the same sample."""
        if not fitted:
            fitted.append(plain(*args, **kw))
        return fitted[0]

    monkeypatch.setattr(gbdt_hist, "boost", once)
    cfg = _epsilon_config()
    cell = {**CELL, "rounds_per_fit": 3, "rows": cfg["check"]["sample_rows"]}
    ctx, _ = rehearsal.context(cell, cfg, tmp_path, jax.devices()[:1],
                               seconds=0.0)
    state = fit.setup(ctx)
    window = fit.window(ctx, state, 0.0)
    line = "train logloss after 3 rounds on 16384 sampled rows"

    def logloss_line(model):
        (ok,) = [ok for ok, what in fit.check(ctx, {**state, "model": model},
                                              window) if line in what]
        return ok

    assert logloss_line(state["model"])
    shallow = fit.make_model({**cfg, "max_depth": cfg["max_depth"] - 1}, 3)
    shallow.set_boundaries(state["model"].boundaries)
    assert not logloss_line(shallow)
