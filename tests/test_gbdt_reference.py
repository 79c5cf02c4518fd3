"""The program's fit against the benchmark's plain numpy reference
(``benchmarks/chip/reference/gbdt_hist.boost``) at small sizes on seeded
rows: the exact ``scatter`` histogram grows the reference's trees, node for
node, and the Pallas kernel (interpret mode) stays within a stated
tolerance of them.  And what the benchmark's readers count on: a compiled
fit holds one ``hist_level`` call a tree level, whatever blocks the level
runs in.

Cases: a dense table whose every level is one accumulator block (depth 6);
256 features at depth 8 and 256 bins, where the real 8 MiB budget cuts the
last level's 64 built nodes into 2 node blocks x 2 feature blocks and
levels 6 and 7 run the ``2x128`` split; a table with absent entries
(``handle_missing``: the reserved bin, both default directions scored).
"""

import numpy as np
import pytest

from benchmarks.chip.reference import gbdt_hist
from dmlc_core_tpu.models.gbdt import GBDT, GBDTParam
from dmlc_core_tpu.ops import hist_pallas
from dmlc_core_tpu.ops.histogram import hist_built_nodes

ROUNDS = 3
CASES = {
    "depth6_one_block": dict(rows=4096, features=12, bins=32, depth=6,
                             absent=0.0, seed=3),
    "depth8_node_and_feature_blocks": dict(rows=8192, features=256, bins=256,
                                           depth=8, absent=0.0, seed=5),
    "depth4_absent_entries": dict(rows=4096, features=5, bins=16, depth=4,
                                  absent=0.7, seed=4),
}
# |program loss - reference loss| a kernel fit may show: bf16 g and h flip
# near-tie splits, and a flip regrows the tree below it.  The widest the
# benchmark's own cells allow (bosch1m.fit's logloss_tolerance).
KERNEL_LOGLOSS_TOLERANCE = 0.005


@pytest.fixture()
def interpret(monkeypatch):
    """The kernel in the interpreter, its tile body unrolled over 16
    features and looped over the rest of a 128-feature block: an eighth of
    the trace; the blocks are those of the real budget."""
    monkeypatch.setattr(hist_pallas, "_INTERPRET", True)
    monkeypatch.setattr(hist_pallas, "_UNROLL", 16)


def _case(name, method):
    """``(model, bins uint8 [rows, F], label)`` of a case: standard-normal
    columns, a seeded linear teacher with 0.3 noise; with ``absent``,
    column j's entries are NaN with probability rising to ``absent`` and
    an absent entry moves the margin, so directions matter."""
    c = CASES[name]
    rng = np.random.RandomState(c["seed"])
    x = rng.randn(c["rows"], c["features"]).astype(np.float32)
    w = rng.randn(c["features"]).astype(np.float32)
    w /= np.sqrt(c["features"])
    margin = x @ w
    if c["absent"]:
        share = np.linspace(0.5, c["absent"], c["features"])
        gone = rng.rand(*x.shape) < share
        effect = rng.randn(c["features"]).astype(np.float32)
        margin = np.where(gone, effect, x * w).sum(axis=1)
        margin -= margin.mean()
        x[gone] = np.nan
    y = (margin + 0.3 * rng.randn(c["rows"]) > 0).astype(np.float32)
    model = GBDT(GBDTParam(num_boost_round=ROUNDS, max_depth=c["depth"],
                           num_bins=c["bins"], hist_method=method,
                           handle_missing=bool(c["absent"])),
                 num_feature=c["features"])
    model.make_bins(x)
    return model, np.asarray(model.bin_features(x), np.uint8), y


def _reference(name, bins, label):
    c, p = CASES[name], GBDTParam()
    return gbdt_hist.boost(
        bins, label, ROUNDS, max_depth=c["depth"], num_bins=c["bins"],
        learning_rate=p.learning_rate, reg_lambda=p.reg_lambda,
        min_child_weight=p.min_child_weight, missing=bool(c["absent"]))


def _goes_right(bins, sf, sb, dl, miss):
    """Which of ``bins``' rows a node's split ``(sf, sb, dl)`` sends right."""
    value = bins[:, sf].astype(np.int64)
    right = value > sb
    if miss is not None and dl:
        right &= value != miss
    return right


def _rows_by_node(bins, tree, depth, miss):
    """Row indices of every internal node of a reference tree, level order."""
    sf, sb, _, dl = tree
    rows = {0: np.arange(bins.shape[0])}
    for k in range(2 ** (depth - 1) - 1):
        mine = rows[k]
        if sf[k] < 0:                    # no split: every row goes left
            right = np.zeros(mine.size, bool)
        else:
            right = _goes_right(bins[mine], sf[k], sb[k], dl[k], miss)
        rows[2 * k + 1], rows[2 * k + 2] = mine[~right], mine[right]
    return rows


@pytest.mark.parametrize("name", sorted(CASES))
def test_scatter_fit_grows_the_references_trees(name):
    """Same split feature, threshold and default direction at every node of
    every tree, margins to float32 rounding (a row in a wrong leaf is off
    by a whole leaf value, 0.05 or more).  Where a node's rows are few, two
    candidates can cut them identically and tie to the last bit, and the
    last bit is the order of f32 additions (the program derives a sibling
    as parent - built): such a node may name the other candidate, if it
    sends every one of the node's rows the same way.  The shallow cases
    have none."""
    c = CASES[name]
    miss = c["bins"] - 1 if c["absent"] else None
    model, bins, label = _case(name, "scatter")
    ensemble, margin = model.fit_binned(bins, label)
    trees, ref_margin = _reference(name, bins, label)
    splits = ties = 0
    for t, tree in enumerate(trees):
        sf, sb, _, dl = tree
        got = [np.asarray(a[t]) for a in (ensemble.split_feat,
                                          ensemble.split_bin,
                                          ensemble.default_left)]
        np.testing.assert_array_equal(got[0] >= 0, sf >= 0)
        split = sf >= 0
        splits += int(split.sum())
        other = split & ((got[0] != sf) | (got[1] != sb) | (got[2] != dl))
        ties += int(other.sum())
        rows = _rows_by_node(bins, tree, c["depth"], miss) if other.any() \
            else {}
        for k in np.flatnonzero(other):
            mine = bins[rows[k]]
            np.testing.assert_array_equal(
                _goes_right(mine, got[0][k], got[1][k], got[2][k], miss),
                _goes_right(mine, sf[k], sb[k], dl[k], miss))
    # the trees are grown, not stumps: most nodes of every level split
    assert splits > 0.6 * ROUNDS * (2 ** c["depth"] - 1)
    assert ties <= (0.01 * splits if c["depth"] > 6 else 0), (ties, splits)
    if c["absent"]:
        assert sum(int(t[3].sum()) for t in trees) >= 3
    assert np.abs(np.asarray(margin) - ref_margin).max() < 5e-5


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_fit_is_within_the_tolerance_of_the_reference(name,
                                                             interpret):
    """The Pallas kernel sums bf16-rounded g and h, which may flip a
    near-tie: the train losses agree within the tolerance.  In the shallow
    cases nothing flips and every row gets the reference's margin; at
    depth 8 a flip regrows the tree below it (the losses stand 6e-4
    apart, 29% of the rows keep the reference's margin)."""
    c = CASES[name]
    model, bins, label = _case(name, "pallas")
    assert model._fit_method(bins) == "pallas"
    if name == "depth8_node_and_feature_blocks":
        blocks = model._hist_blocks("pallas")
        assert blocks["level_node_blocks"] == "1,1,1,1,1,1,1,2"
        # 256 features are two whole blocks of 128: none divides them better
        assert blocks["feature_blocks"] == 2
        assert blocks["block_features"] == 128
        assert blocks["level_kernels"].endswith(
            "hist_level_L6_n32,hist_level_L7_n64")
        assert blocks["bin_split"].endswith("6x48,4x64,2x128,2x128")
    _, margin = model.fit_binned(bins, label)
    _, ref_margin = _reference(name, bins, label)
    margin = np.asarray(margin)
    assert abs(gbdt_hist.logloss(margin, label)
               - gbdt_hist.logloss(ref_margin, label)) \
        <= KERNEL_LOGLOSS_TOLERANCE
    if c["depth"] <= 6:
        assert np.abs(margin - ref_margin).max() < 1e-3


def _hist_level_calls(jaxpr):
    """The names of the ``hist_level`` kernel calls in a jaxpr, in program
    order, sub-jaxprs (the scan over rounds, nested jits) included: each is
    traced once."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            assert eqn.params["name"].startswith("hist_level")
            found.append(eqn.params["name"])
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (list, tuple))
                        else (value,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    found += _hist_level_calls(sub)
    return found


@pytest.mark.parametrize("depth,features,steps", [
    (6, 256, "1,1,1,1,1,1"),
    (8, 256, "1,1,1,1,1,1,1,2"),
    (8, 28, "1,1,1,1,1,1,1,1"),
])
def test_a_compiled_fit_holds_one_kernel_call_a_level(interpret, depth,
                                                      features, steps):
    """Exactly ``max_depth`` ``hist_level`` calls in the body of the scan
    over rounds: a level of two node blocks is still one call, which is
    what ``rounds_traced`` (Mosaic calls / ``max_depth``) and every
    per-level reader of the benchmark divide by.  Their names, root first,
    are the plan's ``level_kernels``: what the whole-round readers find a
    round by."""
    import jax
    import jax.numpy as jnp

    rows = hist_pallas.BLOCK_ROWS
    model = GBDT(GBDTParam(num_boost_round=2, max_depth=depth, num_bins=256,
                           hist_method="pallas"), num_feature=features)
    bins = jnp.zeros((rows, features), jnp.uint8)
    plan = model._fit_plan(bins)
    assert plan.method == "pallas" and plan.level_node_blocks == steps
    jaxpr = jax.make_jaxpr(model._build_fit(2, plan, with_eval=False))(
        bins, jnp.zeros(rows), jnp.ones(rows))
    names = _hist_level_calls(jaxpr.jaxpr)
    assert len(names) == depth
    assert names == plan.level_kernels.split(",") == [
        hist_pallas.hist_kernel_name(n, level)
        for level, n in enumerate(hist_built_nodes(depth))]
