"""Sibling subtraction in the level loop (``HistPlan.level``): below the
root one child of every pair is built by summation, the lighter one, and
the other is parent - built.  Both methods go through the same loop: the
exact ``scatter`` and the kernel in interpret mode.
"""

import numpy as np
import pytest

from dmlc_core_tpu import telemetry
from dmlc_core_tpu.models import gbdt
from dmlc_core_tpu.models.gbdt import GBDT, GBDTParam
from dmlc_core_tpu.ops import hist_pallas
from dmlc_core_tpu.ops.histogram import HistPlan, grad_histogram, hist_plan

METHODS = ["scatter", "pallas"]
NBINS = 16


@pytest.fixture(autouse=True)
def interpret_mode():
    hist_pallas._INTERPRET = True
    yield
    hist_pallas._INTERPRET = False


def _bf16(a):
    import jax.numpy as jnp

    return np.asarray(jnp.asarray(a).astype(jnp.bfloat16).astype(jnp.float32))


def _exact(bins, node, g, h, n):
    """The exact histogram of all ``n`` nodes, every one built."""
    G, H = grad_histogram(bins, node, g, h, n, NBINS, method="scatter")
    return np.asarray(G), np.asarray(H)


class Recorded:
    """Stands where ``_build_tree`` expects its plan and keeps what every
    level's call was given and gave."""

    def __init__(self, plan):
        self.plan, self.levels = plan, []

    def level(self, hist_bins, keys, g, h, num_bins, parent=None,
              built_right=None, *, level=None):
        out = self.plan.level(hist_bins, keys, g, h, num_bins, parent,
                              built_right, level=level)
        self.levels.append({
            "keys": np.asarray(keys),
            "built_right": None if parent is None else np.asarray(built_right),
            "G": np.asarray(out[0]), "H": np.asarray(out[1])})
        return out

    def leaf_sums(self, *args):
        return self.plan.leaf_sums(*args)


def _grow(method, bins, g, h, depth=6, missing=False, **kw):
    """One eager ``_build_tree`` over row-major ``bins``; returns the tree
    and the recorded levels."""
    plan = Recorded(hist_plan(method, None, bins.shape[1], depth, NBINS,
                              rows=bins.shape[0]))
    hist_bins, bins_fm = plan.plan.layouts(bins)
    kw = {"reg_lambda": 1.0, "min_child_weight": 1.0, "learning_rate": 0.3,
          **kw}
    tree = gbdt._build_tree(hist_bins, bins_fm, g, h, plan, depth, NBINS,
                            missing=missing, **kw)
    return [np.asarray(t) for t in tree], plan.levels


def _route(tree, bins, depth, missing=False):
    """Every level's node id of every row, by the finished tree's tables."""
    sf, sb, _, dl = tree[:4]
    node = np.zeros(bins.shape[0], np.int64)
    rows = np.arange(bins.shape[0])
    out = []
    for d in range(depth):
        out.append(node)
        at = 2 ** d - 1 + node
        feat = sf[at]
        row_bin = bins[rows, np.maximum(feat, 0)]
        right = (feat >= 0) & (row_bin > sb[at])
        if missing:
            right &= ~((row_bin == NBINS - 1) & dl[at])
        node = node * 2 + right
    return out


def _balanced(seed=0, b=3000, f=3):
    rng = np.random.RandomState(seed)
    bins = rng.randint(0, NBINS, (b, f)).astype(np.int32)
    g = (bins[:, 0] - 7.5 + 3.0 * (bins[:, 1] > 5) + rng.randn(b)
         ).astype(np.float32)
    return bins, _bf16(g), _bf16(rng.rand(b).astype(np.float32) + 0.5)


def _skewed(seed=1, b=4000, f=3):
    """Three rows of 4,000 alone in bin 0 of feature 0, with a gradient that
    makes the root split them off: a 99.9 / 0.1 split, whose light child
    cannot split again (``min_child_weight`` 2 of hessian mass 3)."""
    rng = np.random.RandomState(seed)
    bins = rng.randint(1, NBINS, (b, f)).astype(np.int32)
    g = rng.randn(b).astype(np.float32)
    bins[:3, 0] = 0
    g[:3] = 60.0
    return bins, _bf16(g), np.ones(b, np.float32)


CASES = {"balanced": (_balanced, {}),
         "skewed": (_skewed, {"min_child_weight": 2.0})}


@pytest.mark.parametrize("method", METHODS)
@pytest.mark.parametrize("case", sorted(CASES))
def test_every_level_of_a_fit_is_the_histogram_of_all_its_nodes(case, method):
    """At every level of a depth-6 tree what ``level`` returns is the exact
    histogram of all ``n`` nodes (g and h bf16-rounded, so both methods sum
    the same terms); a node that did not split has a right child of exact
    zeros and a left child that is the parent bit for bit."""
    make, kw = CASES[case]
    bins, g, h = make()
    tree, levels = _grow(method, bins, g, h, **kw)
    assert [lv["G"].shape[0] for lv in levels] == [1, 2, 4, 8, 16, 32]
    nodes = _route(tree, bins, 6)
    dead = 0
    for d, (lv, node) in enumerate(zip(levels, nodes)):
        G, H = _exact(bins, node.astype(np.int32), g, h, 2 ** d)
        np.testing.assert_allclose(lv["G"], G, rtol=1e-5, atol=1e-4)
        np.testing.assert_allclose(lv["H"], H, rtol=1e-5, atol=1e-4)
        if d == 0:
            continue
        above = levels[d - 1]
        for p in np.flatnonzero(tree[0][2 ** (d - 1) - 1:2 ** d - 1] < 0):
            dead += 1
            assert lv["built_right"][p]
            for name in ("G", "H"):
                assert not lv[name][2 * p + 1].any()
                np.testing.assert_array_equal(lv[name][2 * p], above[name][p])
    if case == "skewed":
        # the root's split is the 99.9 / 0.1 one, and its light child is dead
        assert (tree[0][0], tree[1][0]) == (0, 0) and tree[0][1] == -1
        assert levels[1]["H"][0, 0].sum() == 3.0 and dead >= 5


def _assert_the_lighter_child_is_built(levels, h):
    for d in range(1, len(levels)):
        lv = levels[d]
        mass = lv["H"][:, 0, :].sum(axis=-1)                  # [n]
        left, right = mass[0::2], mass[1::2]
        half = len(left)
        assert lv["keys"].min() >= -1 and lv["keys"].max() < half
        keyed = np.bincount(lv["keys"][lv["keys"] >= 0],
                            weights=h[lv["keys"] >= 0], minlength=half)
        built = np.where(lv["built_right"], right, left)
        np.testing.assert_allclose(keyed, built, rtol=1e-4, atol=1e-3)
        # never the heavier one, but for a tie's rounding
        assert (built <= np.where(lv["built_right"], left, right)
                * (1 + 1e-5) + 1e-3).all()


@pytest.mark.parametrize("method", METHODS)
def test_the_lighter_child_is_the_one_built(method):
    bins, g, h = _balanced(seed=5)
    tree, levels = _grow(method, bins, g, h)
    assert (tree[0] >= 0).sum() > 20
    _assert_the_lighter_child_is_built(levels, h)
    # both children get their turn
    flags = np.concatenate([lv["built_right"] for lv in levels[1:]])
    assert flags.any() and not flags.all()


@pytest.mark.parametrize("method", METHODS)
def test_the_lighter_child_counts_missing_rows_sent_left(method):
    """With ``missing`` the missing bin's mass goes where ``default_left``
    sends it.  At the root the missing rows, 40% of all, go left and decide
    it: without them the left child is the lighter one, with them the
    right, which is the one built."""
    rng = np.random.RandomState(7)
    b = 3000
    bins = rng.randint(0, NBINS - 1, (b, 3)).astype(np.int32)
    miss = rng.rand(b) < 0.4
    bins[miss, 0] = NBINS - 1
    g = _bf16(np.where(miss, -1.5, bins[:, 0] / 4.0 - 1.0)
              + 0.5 * rng.randn(b))
    h = _bf16(rng.rand(b) + 0.5)
    tree, levels = _grow(method, bins, g, h, depth=4, missing=True)
    assert tree[0][0] == 0 and tree[3][0], "the root sends missing rows left"
    left_seen = h[~miss & (bins[:, 0] <= tree[1][0])].sum()
    assert left_seen < h.sum() - left_seen - h[miss].sum() < left_seen \
        + h[miss].sum()
    assert levels[1]["built_right"][0]
    nodes = _route(tree, bins, 4, missing=True)
    for d, (lv, node) in enumerate(zip(levels, nodes)):
        G, H = _exact(bins, node.astype(np.int32), g, h, 2 ** d)
        np.testing.assert_allclose(lv["G"], G, rtol=1e-5, atol=1e-4)
        np.testing.assert_allclose(lv["H"], H, rtol=1e-5, atol=1e-4)
    _assert_the_lighter_child_is_built(levels, h)


@pytest.mark.parametrize("method", METHODS)
def test_level_drops_rows_of_no_node_slot(method):
    """Rows whose id lies outside the level (below 0, or past it: rows of
    another node block) are in no histogram, built or derived, whatever id
    outside ``[0, n / 2)`` stands for "not in the built child"."""
    import jax.numpy as jnp

    rng = np.random.RandomState(11)
    b, f, n = 1300, 3, 8
    bins = rng.randint(0, NBINS, (b, f)).astype(np.int32)
    node = rng.randint(0, n, b).astype(np.int32)
    g, h = _bf16(rng.randn(b)), _bf16(rng.rand(b))
    node[::7] = -1
    node[3::11] = n + 5
    inside = (node >= 0) & (node < n)
    above = np.where(inside, node // 2, -1).astype(np.int32)
    parent = _exact(bins, above, g, h, n // 2)
    built_right = np.array([True, False, False, True])
    in_built = inside & ((node % 2 == 1) == built_right[np.maximum(above, 0)])
    keys = np.where(in_built, above,
                    np.where(np.arange(b) % 2, -1, n // 2 + 3)
                    ).astype(np.int32)
    plan = hist_plan(method, None, f, 4, NBINS, rows=b)
    hist_bins, _ = plan.layouts(bins)
    G, H = plan.level(hist_bins, jnp.asarray(keys), g, h, NBINS,
                      tuple(jnp.asarray(p) for p in parent),
                      jnp.asarray(built_right))
    Gr, Hr = _exact(bins[inside], node[inside], g[inside], h[inside], n)
    np.testing.assert_allclose(np.asarray(G), Gr, rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(np.asarray(H), Hr, rtol=1e-5, atol=1e-4)


def test_the_one_shot_histogram_drops_out_of_range_rows_under_scatter():
    """``grad_histogram`` keeps its contract, every node built, and under
    ``scatter`` too a negative id no longer wraps into the last node."""
    bins, g, h = _balanced(seed=2, b=500)
    node = np.random.RandomState(2).randint(0, 4, 500).astype(np.int32)
    node[::5] = -1
    node[1::9] = 4
    keep = (node >= 0) & (node < 4)
    G, H = _exact(bins, node, g, h, 4)
    Gk, Hk = _exact(bins[keep], node[keep], g[keep], h[keep], 4)
    np.testing.assert_array_equal(G, Gk)
    np.testing.assert_array_equal(H, Hk)


# -- whole fits against the build of every node ------------------------------

@pytest.fixture
def every_node_built(monkeypatch):
    """While on, ``HistPlan.level`` builds all ``n`` nodes of a level by
    summation, as the loop did before it carried a parent: the rows' node
    ids are kept beside the loop, from the keys and flags it hands over."""
    at = {}

    def level(self, hist_bins, keys, g, h, num_bins, parent=None,
              built_right=None, *, level=None):
        import jax.numpy as jnp

        if parent is None:
            at["node"] = jnp.zeros_like(keys)
            return self.histogram(hist_bins, keys, g, h, 1, num_bins,
                                  level=level)
        flag = built_right[at["node"]]
        at["node"] = 2 * at["node"] + jnp.where(keys >= 0, flag, ~flag)
        return self.histogram(hist_bins, at["node"], g, h,
                              2 * parent[0].shape[0], num_bins, level=level)

    def switch(on):
        if on:
            monkeypatch.setattr(HistPlan, "level", level)
        else:
            monkeypatch.undo()

    return switch


def _rows(n=1500, f=6, seed=21, nan=0.0, classes=0):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, f).astype(np.float32)
    score = x[:, 0] + x[:, 1] * x[:, 2] + 0.3 * rng.randn(n)
    if nan:
        x[rng.rand(n, f) < nan] = np.nan
        score = score + 1.5 * np.isnan(x[:, 3])
    if classes:
        y = np.digitize(score, np.quantile(score, [1 / 3, 2 / 3]))
    else:
        y = (score > 0)
    return x, y.astype(np.float32)


def _fit_binned(model, bins, y):
    return model.fit_binned(bins, y)[0]


def _boost_rounds(model, bins, y):
    import jax.numpy as jnp

    margin = jnp.zeros(bins.shape[0], jnp.float32)
    weight = jnp.ones(bins.shape[0], jnp.float32)
    trees = []
    for _ in range(3):
        margin, tree = model.boost_round(margin, bins, y, weight)
        trees.append(tree[:4])
    return gbdt.TreeEnsemble(*(np.stack([np.asarray(t[i]) for t in trees])
                               for i in range(4)))


def _fit_sharded(model, bins, y):
    import jax
    from dmlc_core_tpu.parallel.mesh import data_sharding, make_mesh

    mesh = make_mesh({"data": 4}, devices=jax.devices()[:4])
    with mesh:
        ens = model.fit_binned(
            jax.device_put(bins, data_sharding(mesh, ndim=2)),
            jax.device_put(y, data_sharding(mesh)))[0]
        return gbdt.TreeEnsemble(*(None if a is None else np.asarray(a)
                                   for a in ens))


FITS = {
    "fit_binned": ({}, {}, _fit_binned),
    "boost_round": ({}, {}, _boost_rounds),
    "softmax": ({"objective": "softmax", "num_class": 3}, {"classes": 3},
                _fit_binned),
    "missing": ({"handle_missing": True}, {"nan": 0.2}, _fit_binned),
    "monotone": ({"monotone_constraints": "(1,0,0,-1,0,0)"}, {}, _fit_binned),
    "sharded_data4": ({}, {"n": 1600}, _fit_sharded),
}


@pytest.mark.parametrize("method", METHODS)
@pytest.mark.parametrize("fit", sorted(FITS))
def test_whole_fits_choose_the_splits_of_the_all_nodes_build(
        fit, method, every_node_built):
    """Every way into ``_build_tree`` grows, with one child of each pair
    derived, the trees it grows with every node built: the same splits and
    default directions, leaf values to f32 rounding.  (No near-tie on these
    rows: a derived histogram differs from a built one by the order of f32
    additions alone, 1e-7 of a bin's sum.)"""
    param, data, run = FITS[fit]
    x, y = _rows(**data)
    if method == "pallas" and fit == "sharded_data4":
        x, y = x[:1024], y[:1024]         # interpreted tiles of 4 shards
    trees = []
    for all_nodes in (False, True):
        every_node_built(all_nodes)
        model = GBDT(GBDTParam(num_boost_round=3, max_depth=4, num_bins=NBINS,
                               hist_method=method, **param),
                     num_feature=x.shape[1])
        model.make_bins(x)
        bins = np.asarray(model.bin_features(x), np.uint8)
        trees.append(run(model, bins, y))
    derived, built = trees
    assert (np.asarray(derived.split_feat) >= 0).sum() > 15
    for name in ("split_feat", "split_bin", "default_left"):
        np.testing.assert_array_equal(np.asarray(getattr(derived, name)),
                                      np.asarray(getattr(built, name)), name)
    np.testing.assert_allclose(np.asarray(derived.leaf_value),
                               np.asarray(built.leaf_value),
                               rtol=1e-4, atol=1e-5)


# -- the plan and the span ---------------------------------------------------

def test_the_plan_follows_the_nodes_a_fit_builds():
    plan = hist_pallas.hist_kernel_plan(None, 28, 6, 256)
    assert plan["bin_split"] == "16x16,16x16,8x32,8x32,6x48,4x64"
    assert plan["built_nodes"] == "1,1,2,4,8,16"
    assert plan["level_node_blocks"] == "1,1,1,1,1,1"
    assert plan["level_kernels"] == (
        "hist_level_L0_n1,hist_level_L1_n1,hist_level_L2_n2,"
        "hist_level_L3_n4,hist_level_L4_n8,hist_level_L5_n16")
    # the deepest level's count is the last of level_node_blocks: no entry
    # of its own, in the plan or on the span
    assert "node_blocks" not in plan
    assert "node_blocks" not in hist_plan("pallas", None, 28, 6,
                                          256).blocks()
    # scatter builds the same node slots; it has no kernel to shape or name
    assert hist_plan("scatter", None, 28, 6, 256).blocks() == {
        "level_node_blocks": "", "feature_blocks": 0, "block_features": 0,
        "row_tile": 0,
        "bin_split": "", "built_nodes": "1,1,2,4,8,16", "level_kernels": ""}
    # a depth-1 fit has no level below the root
    assert hist_plan("scatter", None, 28, 1, 256).built_nodes == "1"


def test_the_dispatch_span_carries_split_and_built_nodes():
    x, y = _rows(n=300)
    model = GBDT(GBDTParam(num_boost_round=1, max_depth=6, num_bins=256,
                           hist_method="pallas"), num_feature=x.shape[1])
    model.make_bins(x)
    bins = np.asarray(model.bin_features(x), np.uint8)
    was_enabled = telemetry.enabled()
    telemetry.reset()
    telemetry.enable()
    try:
        model.fit_binned(bins, y)
        args = [e["args"] for e in telemetry.get_tracer().events()
                if e["name"] == "gbdt.fit.dispatch"][-1]
    finally:
        telemetry.disable()
        telemetry.reset()
        if was_enabled:
            telemetry.enable()
    assert args["method"] == "pallas"
    assert args["built_nodes"] == "1,1,2,4,8,16"
    assert args["bin_split"] == "16x16,16x16,8x32,8x32,6x48,4x64"
    assert args["level_node_blocks"] == "1,1,1,1,1,1"
    assert args["level_kernels"].split(",") == [
        hist_pallas.hist_kernel_name(n, level)
        for level, n in enumerate((1, 1, 2, 4, 8, 16))]
    assert "node_blocks" not in args


def test_one_kernel_call_a_level_of_half_the_nodes():
    """The fit's jaxpr holds exactly ``max_depth`` ``hist_level`` calls a
    tree, the first two of one node, each named by its level and its built
    nodes as the plan's ``level_kernels`` says: what ``rounds_traced``
    counts on, and what the whole-round readers find a round by."""
    import jax
    import jax.numpy as jnp

    depth, f, b = 5, 4, hist_pallas.BLOCK_ROWS
    plan = hist_plan("pallas", None, f, depth, NBINS, rows=b)

    def grow(bins, g, h):
        hist_bins, bins_fm = plan.layouts(bins)
        return gbdt._build_tree(hist_bins, bins_fm, g, h, plan, depth, NBINS,
                                1.0, 1.0, 0.3)

    jaxpr = jax.make_jaxpr(grow)(jnp.zeros((b, f), jnp.uint8),
                                 jnp.zeros(b), jnp.zeros(b))
    calls = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert [c.params["name"] for c in calls] == \
        plan.level_kernels.split(",") == [
            "hist_level_L0_n1", "hist_level_L1_n1", "hist_level_L2_n2",
            "hist_level_L3_n4", "hist_level_L4_n8"]
    # the accumulator's minor extent is 2 x the key rows of the built nodes
    slots = [c.outvars[0].aval.shape[-1] for c in calls]
    assert slots == [2 * hist_pallas._key_rows(
        n, hist_pallas.hist_split_plan(n, NBINS)[0]) for n in (1, 1, 2, 4, 8)]
