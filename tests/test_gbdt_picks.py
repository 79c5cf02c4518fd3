"""The per-row picks of ``_build_tree``: every one reduces over a leading
axis with rows on the minor axis (``_feature_pick`` over feature-major
bins, ``_table_pick`` over a per-node table), and none is a gather.

The picks are held to plain numpy indexing, ``_build_tree`` to a tree
grower written here with nothing but numpy indexing, and the compiled fit
to a jaxpr with no gather left under ``gbdt.route`` / ``gbdt.leaf``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dmlc_core_tpu.models.gbdt import (GBDT, GBDTParam, _build_tree,
                                       _feature_pick, _table_pick)
from dmlc_core_tpu.ops.histogram import HistPlan

SCATTER = HistPlan("scatter")

ROWS = 333                      # not a multiple of 128


def _table(dtype, n, rng):
    if dtype == np.bool_:
        return rng.integers(0, 2, n).astype(np.bool_)
    table = rng.integers(-9, 10, n).astype(dtype)
    table[0] = -1               # the "no split" entry of a split_feat table
    return table if dtype == np.int32 else table * np.float32(0.37)


@pytest.mark.parametrize("n", [1, 2, 8, 64])
@pytest.mark.parametrize("dtype", [np.int32, np.float32, np.bool_])
def test_table_pick_is_the_gather(dtype, n):
    rng = np.random.default_rng(n)
    table = _table(dtype, n, rng)
    node = rng.integers(0, n, ROWS).astype(np.int32)
    node[:n] = np.arange(n)     # every entry read at least once
    got = np.asarray(_table_pick(jnp.asarray(table), jnp.asarray(node)))
    assert got.dtype == table.dtype and got.shape == (ROWS,)
    np.testing.assert_array_equal(got, table[node])


@pytest.mark.parametrize("features", [1, 13, 28, 300])
@pytest.mark.parametrize("dtype", [np.uint8, np.uint16, np.int32])
def test_feature_pick_is_take_along_axis(dtype, features):
    rng = np.random.default_rng(features)
    top = {np.uint8: 256, np.uint16: 1024, np.int32: 1024}[dtype]
    bins = rng.integers(0, top, (ROWS, features)).astype(dtype)
    feat = rng.integers(-1, features, ROWS).astype(np.int32)
    feat[:3] = -1
    widened, bins_fm = SCATTER.layouts(bins)
    assert bins_fm.shape == (features, ROWS) and bins_fm.dtype == dtype
    assert widened.shape == bins.shape and widened.dtype == jnp.int32
    got = np.asarray(_feature_pick(bins_fm, jnp.asarray(feat)))
    want = np.take_along_axis(bins.astype(np.int32),
                              np.maximum(feat, 0)[:, None], axis=1)[:, 0]
    want[feat < 0] = 0          # nf == -1 picks nothing
    assert got.dtype == np.int32
    np.testing.assert_array_equal(got, want)


def test_bin_layouts_pad_rows_in_both_layouts():
    bins = np.arange(15, dtype=np.uint8).reshape(5, 3)
    widened, bins_fm = SCATTER.layouts(bins, pad=3)
    assert widened.shape == (8, 3) and bins_fm.shape == (3, 8)
    np.testing.assert_array_equal(np.asarray(widened)[:5], bins)
    np.testing.assert_array_equal(np.asarray(widened).T, np.asarray(bins_fm))
    assert not np.asarray(bins_fm)[:, 5:].any()


# -- _build_tree against a grower of plain numpy indexing -------------------

DEPTH, BINS, LAM, MCW, LR = 4, 16, np.float32(1.0), np.float32(1.0), 0.3


def _oracle_tree(bins, g, h, missing, mono):
    """Level-wise exact greedy growth as ``_build_tree`` documents it, in
    float32 numpy: histograms by ``np.add.at``, the table and feature
    lookups by fancy indexing."""
    f32 = np.float32
    B, F = bins.shape
    miss_id = BINS - 1
    node = np.zeros(B, np.int64)
    lo, hi = np.full(1, -np.inf, f32), np.full(1, np.inf, f32)
    feats, thresholds, defaults = [], [], []

    def weight(Gv, Hv):
        return -Gv / (Hv + LAM)

    def score(Gv, Hv):
        return Gv ** 2 / (Hv + LAM)

    for depth in range(DEPTH):
        n = 2 ** depth
        G = np.zeros((n, F, BINS), f32)
        H = np.zeros((n, F, BINS), f32)
        for f in range(F):
            np.add.at(G[:, f], (node, bins[:, f]), g)
            np.add.at(H[:, f], (node, bins[:, f]), h)
        GL, HL = np.cumsum(G, -1, dtype=f32), np.cumsum(H, -1, dtype=f32)
        GT, HT = GL[..., -1:], HL[..., -1:]

        def gain_of(GLv, HLv):
            GRv, HRv = GT - GLv, HT - HLv
            gn = score(GLv, HLv) + score(GRv, HRv) - score(GT, HT)
            ok = (HLv >= MCW) & (HRv >= MCW)
            if mono is not None:
                ok &= ~(mono[None, :, None]
                        * (weight(GLv, HLv) - weight(GRv, HRv)) > 0)
            return gn, ok

        gain, valid = gain_of(GL, HL)
        if missing:
            GLm = GL + G[..., miss_id:miss_id + 1]
            HLm = HL + H[..., miss_id:miss_id + 1]
            gain_l, valid_l = gain_of(GLm, HLm)
            gain = np.where(valid, gain, -np.inf)
            gain_l = np.where(valid_l, gain_l, -np.inf)
            left_default = gain_l > gain
            gain = np.maximum(gain, gain_l)
            valid = valid | valid_l
        valid = valid & (np.arange(BINS) < BINS - 1)[None, None, :]
        flat = np.where(valid, gain, -np.inf).reshape(n, F * BINS)
        best = np.argmax(flat, axis=-1)
        rows = np.arange(n)
        do_split = flat[rows, best] > 0.0
        bf, bb = best // BINS, best % BINS
        sf = np.where(do_split, bf, -1)
        dl = (left_default.reshape(n, -1)[rows, best] & do_split
              if missing else np.zeros(n, bool))
        feats.append(sf)
        thresholds.append(bb)
        defaults.append(dl)
        if mono is not None:
            GLb = GL.reshape(n, -1)[rows, best]
            HLb = HL.reshape(n, -1)[rows, best]
            if missing:
                GLb = np.where(dl, GLm.reshape(n, -1)[rows, best], GLb)
                HLb = np.where(dl, HLm.reshape(n, -1)[rows, best], HLb)
            wl = np.clip(weight(GLb, HLb), lo, hi)
            wr = np.clip(weight(GT[:, 0, 0] - GLb, HT[:, 0, 0] - HLb),
                         lo, hi)
            mid = f32(0.5) * (wl + wr)
            c = np.where(do_split, mono[bf], 0)
            lo_l = np.where(c < 0, np.maximum(lo, mid), lo)
            hi_l = np.where(c > 0, np.minimum(hi, mid), hi)
            lo_r = np.where(c > 0, np.maximum(lo, mid), lo)
            hi_r = np.where(c < 0, np.minimum(hi, mid), hi)
            lo = np.stack([lo_l, lo_r], 1).reshape(-1)
            hi = np.stack([hi_l, hi_r], 1).reshape(-1)
        # route: the node's feature and threshold, then the row's own bin
        nf = sf[node]
        row_bin = np.where(nf >= 0, bins[np.arange(B), np.maximum(nf, 0)], 0)
        go_right = (row_bin > bb[node]) & (nf >= 0)
        if missing:
            go_right &= ~((row_bin == miss_id) & dl[node])
        node = node * 2 + go_right

    Gl, Hl = np.zeros(2 ** DEPTH, f32), np.zeros(2 ** DEPTH, f32)
    np.add.at(Gl, node, g)
    np.add.at(Hl, node, h)
    leaf_w = weight(Gl, Hl)
    if mono is not None:
        leaf_w = np.clip(leaf_w, lo, hi)
    leaf_value = leaf_w * f32(LR)
    return (np.concatenate(feats), np.concatenate(thresholds),
            np.concatenate(defaults), leaf_value, leaf_value[node])


TREE_CASES = {
    "plain": dict(missing=False, mono=None),
    "missing": dict(missing=True, mono=None),
    "monotone": dict(missing=False, mono=(1, 0, -1, 0, 1)),
    "missing_monotone": dict(missing=True, mono=(-1, 1, 0, 0, 1)),
}


@pytest.mark.parametrize("wire", [np.uint8, np.int32])
@pytest.mark.parametrize("case", sorted(TREE_CASES))
def test_build_tree_matches_a_numpy_indexing_oracle(case, wire):
    missing = TREE_CASES[case]["missing"]
    mono = TREE_CASES[case]["mono"]
    mono = None if mono is None else np.asarray(mono, np.int32)
    rng = np.random.default_rng(sorted(TREE_CASES).index(case))
    rows, features = 1501, 5
    bins = rng.integers(0, BINS - 1, (rows, features))
    if missing:
        bins[rng.random((rows, features)) < 0.15] = BINS - 1
    # dyadic gradients: every histogram sum is exact in float32 whatever
    # the order of the additions, so both growers score the same numbers
    g = (rng.integers(-32, 33, rows) / 32).astype(np.float32)
    g += ((bins[:, 0] > 6) * np.float32(0.5)
          - (bins[:, 2] > 9) * np.float32(0.25))
    h = (rng.integers(8, 33, rows) / 32).astype(np.float32)

    widened, bins_fm = SCATTER.layouts(bins.astype(wire))
    sf, sb, lv, dl, _, _, delta = jax.jit(
        lambda b, bf, g_, h_: _build_tree(
            b, bf, g_, h_, SCATTER, DEPTH, BINS, float(LAM), float(MCW), LR,
            missing=missing, monotone=mono))(
        widened, bins_fm, g, h)
    want_sf, want_sb, want_dl, want_lv, want_delta = _oracle_tree(
        bins, g, h, missing, mono)

    assert (want_sf >= 0).sum() >= 2 ** DEPTH - 2, "the oracle barely split"
    np.testing.assert_array_equal(np.asarray(sf), want_sf)
    split = want_sf >= 0
    np.testing.assert_array_equal(np.asarray(sb)[split], want_sb[split])
    np.testing.assert_array_equal(np.asarray(dl), want_dl)
    if missing:
        assert want_dl.any(), "no node learned default-left"
    np.testing.assert_allclose(np.asarray(lv), want_lv, atol=1e-6, rtol=0)
    np.testing.assert_allclose(np.asarray(delta), want_delta, atol=1e-6,
                               rtol=0)


# -- the streamed round and the compiled fit grow the same trees ------------

@pytest.mark.parametrize("wire", [np.uint8, np.uint16, np.int32])
@pytest.mark.parametrize("objective", ["logistic", "softmax"])
def test_boost_rounds_equal_fit_binned(objective, wire):
    rounds, rows, features = 3, 203, 6
    extra = {"num_class": 3} if objective == "softmax" else {}
    model = GBDT(GBDTParam(num_boost_round=rounds, max_depth=3, num_bins=16,
                           objective=objective, hist_method="scatter",
                           **extra), num_feature=features)
    rng = np.random.default_rng(7)
    bins = rng.integers(0, 16, (rows, features)).astype(wire)
    label = ((bins[:, 1] > 7).astype(np.float32) if objective == "logistic"
             else (bins[:, 1] // 6).astype(np.float32))
    fitted, fit_margin = model.fit_binned(bins, label)
    streamed, margin = model.append_rounds(None, bins, label,
                                           num_rounds=rounds)
    for name in ("split_feat", "split_bin", "default_left"):
        np.testing.assert_array_equal(np.asarray(getattr(fitted, name)),
                                      np.asarray(getattr(streamed, name)))
    np.testing.assert_allclose(np.asarray(fitted.leaf_value),
                               np.asarray(streamed.leaf_value), atol=1e-6)
    np.testing.assert_allclose(np.asarray(fit_margin), np.asarray(margin),
                               atol=1e-6)


# -- the gathers are gone and stay gone --------------------------------------

def _scoped_eqns(jaxpr, outer=""):
    """Every equation of a jaxpr and of the jaxprs nested in it, with the
    name stack it runs under (a sub-jaxpr's stack is relative to the
    equation that calls it)."""
    for eqn in jaxpr.eqns:
        stack = f"{outer}/{eqn.source_info.name_stack}"
        yield stack, eqn
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (list, tuple))
                        else [value]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _scoped_eqns(inner, stack)


@pytest.fixture(scope="module", params=[np.uint8, np.uint16])
def fit_eqns(request):
    rows, features, rounds = 200, 5, 2
    model = GBDT(GBDTParam(num_boost_round=rounds, max_depth=3, num_bins=16,
                           hist_method="scatter", handle_missing=True),
                 num_feature=features)
    jaxpr = jax.make_jaxpr(model._fit_fn(rounds, "scatter"))(
        np.zeros((rows, features), request.param),
        np.zeros(rows, np.float32), np.ones(rows, np.float32))
    return list(_scoped_eqns(jaxpr.jaxpr)), (features, rows), request.param


def test_no_gather_under_route_or_leaf(fit_eqns):
    eqns, _, _ = fit_eqns
    in_scope = [(s, e) for s, e in eqns
                if "gbdt.route" in s or "gbdt.leaf" in s]
    assert len(in_scope) > 20, "the name stacks carry no scope"
    gathers = [s for s, e in in_scope if "gather" in e.primitive.name]
    assert gathers == []
    # the split tables are still read by gathers elsewhere: the walk sees them
    assert any("gather" in e.primitive.name for _, e in eqns)


def test_route_reduces_feature_major_bins_in_the_wire_dtype(fit_eqns):
    eqns, feature_major, wire = fit_eqns
    route = [e for s, e in eqns if "gbdt.route" in s]
    widenings = [e for e in route
                 if e.primitive.name == "convert_element_type"
                 and e.invars[0].aval.shape == feature_major]
    assert widenings, "no [F, rows] operand is widened under gbdt.route"
    assert {e.invars[0].aval.dtype for e in widenings} == {np.dtype(wire)}
    picks = [e for e in route if e.primitive.name == "reduce_sum"
             and e.invars[0].aval.shape == feature_major]
    assert picks and all(e.params["axes"] == (0,) for e in picks)
    # nothing of the row-major shape is reduced any more
    assert not [e for e in route if e.primitive.name.startswith("reduce")
                and e.invars[0].aval.shape == feature_major[::-1]]
