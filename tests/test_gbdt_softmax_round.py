"""The softmax boosting round: K trees from one margin snapshot, grown by
ONE traced body scanned over the class axis on class-major ``[K, rows]``
arrays (``models/gbdt.py:_softmax_round``), held to a frozen copy of the
round it replaced: the Python loop over classes on ``[rows, K]`` arrays,
kept below as PR 40's parent had it.  Same trees: split features,
thresholds and default directions equal, leaf values and margins to
float32 round-off, with and without per-tree sampling, whose draw now takes
a traced class index.  And the label check that every softmax entry makes
before tracing, which reads two scalars of a device array, never the
column.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dmlc_core_tpu.models import gbdt
from dmlc_core_tpu.models.gbdt import GBDT, GBDTParam

ROWS, FEATURES, BINS, DEPTH, ROUNDS = 2000, 6, 16, 3, 2
SAMPLING = {"whole": {}, "sampled": {"subsample": 0.7,
                                     "colsample_bytree": 0.6, "seed": 11}}


# -- the parent's round, frozen ----------------------------------------------

def _frozen_grad_hess(margin, label, num_class):
    pr = jax.nn.softmax(margin, axis=1)
    onehot = (label.astype(jnp.int32)[:, None]
              == jnp.arange(num_class, dtype=jnp.int32)).astype(jnp.float32)
    return pr - onehot, jnp.maximum(2.0 * pr * (1.0 - pr), 1e-16)


def _frozen_tree_sampling(p, rnd, B, F, class_index=0):
    row_w = fmask = None
    if p.subsample < 1.0 or p.colsample_bytree < 1.0:
        key = jax.random.fold_in(jax.random.PRNGKey(p.seed),
                                 jnp.asarray(rnd, jnp.uint32))
        if class_index:
            key = jax.random.fold_in(key, class_index)
        if p.subsample < 1.0:
            row_w = (jax.random.uniform(jax.random.fold_in(key, 0), (B,))
                     < p.subsample).astype(jnp.float32)
        if p.colsample_bytree < 1.0:
            u = jax.random.uniform(jax.random.fold_in(key, 1), (F,))
            fmask = (u < p.colsample_bytree).at[jnp.argmin(u)].set(True)
    return row_w, fmask


def _frozen_softmax_round(p, bins, margin, label, weight, rnd, grow,
                          num_feature):
    """PR 40's parent: ``for k in range(K)`` over ``[B, K]`` arrays."""
    K = p.num_class
    B = margin.shape[0]
    g_all, h_all = _frozen_grad_hess(margin, label, K)
    trees = []
    for k in range(K):
        row_w, fmask = _frozen_tree_sampling(p, rnd, B, num_feature,
                                             class_index=k)
        w = weight if row_w is None else weight * row_w
        trees.append(grow(bins, g_all[:, k] * w, h_all[:, k] * w, rnd, fmask))
    margin = margin + jnp.stack([t[6] for t in trees], axis=1)
    return margin, tuple(jnp.stack([t[i] for t in trees]) for i in range(6))


def _frozen_fit(model, bins, label, weight):
    """``ROUNDS`` frozen rounds from the base margin, through the model's
    own ``_build_tree`` and exact (scatter) histogram."""
    p = model.param
    plan = model._plan("scatter", rows=bins.shape[0])

    @jax.jit
    def fit(bins, label, weight):
        layout, bins_fm = plan.layouts(bins)

        def grow(bins_, g, h, rnd, fmask):
            return gbdt._build_tree(
                bins_, bins_fm, g, h, plan, p.max_depth, p.num_bins,
                p.reg_lambda, p.min_child_weight, p.learning_rate,
                feat_mask=fmask)

        margin = jnp.full((bins.shape[0], p.num_class), p.base_score,
                          jnp.float32)
        rounds = []
        for rnd in range(ROUNDS):
            margin, trees = _frozen_softmax_round(
                p, layout, margin, label, weight, jnp.uint32(rnd), grow,
                bins.shape[1])
            rounds.append(trees)
        return tuple(jnp.stack(a) for a in zip(*rounds)), margin

    return fit(bins, label, weight)


def _data(classes, seed=3):
    rng = np.random.default_rng(seed)
    bins = rng.integers(0, BINS, (ROWS, FEATURES)).astype(np.uint8)
    # labels that depend on the bins: trees with something to find
    score = bins.astype(np.float32) @ rng.standard_normal(
        (FEATURES, classes)).astype(np.float32)
    label = np.argmax(score + rng.standard_normal((ROWS, classes)), axis=1)
    return (jnp.asarray(bins), jnp.asarray(label, jnp.float32),
            jnp.asarray(rng.uniform(0.5, 1.5, ROWS), jnp.float32))


def _model(classes, sampling):
    return GBDT(GBDTParam(num_boost_round=ROUNDS, max_depth=DEPTH,
                          num_bins=BINS, objective="softmax",
                          num_class=classes, hist_method="scatter",
                          **SAMPLING[sampling]), num_feature=FEATURES)


def _same_trees(got, want):
    for i, name in ((0, "split_feat"), (1, "split_bin"), (3, "default_left")):
        assert np.array_equal(np.asarray(got[i]), np.asarray(want[i])), name
    np.testing.assert_allclose(np.asarray(got[2]), np.asarray(want[2]),
                               rtol=2e-5, atol=1e-6)          # leaf values


@pytest.mark.parametrize("sampling", sorted(SAMPLING))
@pytest.mark.parametrize("classes", [3, 23])
def test_the_scanned_round_grows_the_unrolled_rounds_trees(classes, sampling):
    model = _model(classes, sampling)
    bins, label, weight = _data(classes)
    want_trees, want_margin = _frozen_fit(model, bins, label, weight)
    ensemble, margin = model.fit_binned(bins, label, weight)
    assert np.asarray(ensemble.split_feat).shape == (
        ROUNDS, classes, 2 ** DEPTH - 1)
    # the trees have splits to compare, in round 1 (a real softmax) too
    assert (np.asarray(ensemble.split_feat)[1] >= 0).sum() >= classes
    _same_trees(ensemble, want_trees)
    assert margin.shape == (ROWS, classes)                    # the API's
    np.testing.assert_allclose(np.asarray(margin), np.asarray(want_margin),
                               rtol=2e-5, atol=2e-6)
    if sampling == "sampled":
        # the draw is a class's own: trees of one round differ in their
        # feature masks, so the traced index reached the key
        feats = [set(np.asarray(ensemble.split_feat)[0, k].tolist()) - {-1}
                 for k in range(classes)]
        assert len({frozenset(f) for f in feats}) > 1


@pytest.mark.parametrize("sampling", sorted(SAMPLING))
def test_the_streamed_round_takes_and_returns_rows_by_classes(sampling):
    """``boost_round``: ``[rows, K]`` in and out, the same trees as the
    frozen round from the same margin, twice over (the second round starts
    from margins that differ)."""
    classes = 5
    model = _model(classes, sampling)
    bins, label, weight = _data(classes)
    want_trees, want_margin = _frozen_fit(model, bins, label, weight)
    margin = jnp.zeros((ROWS, classes), jnp.float32)
    for rnd in range(ROUNDS):
        margin, trees = model.boost_round(margin, bins, label, weight,
                                          round_index=rnd)
        assert margin.shape == (ROWS, classes)
        _same_trees(trees, [a[rnd] for a in want_trees])
    np.testing.assert_allclose(np.asarray(margin), np.asarray(want_margin),
                               rtol=2e-5, atol=2e-6)


def test_the_traced_class_index_draws_what_the_static_one_drew():
    """``_tree_sampling``: class 0 keeps the round's key (no fold), every
    other class folds its index in, traced or not."""
    p = GBDTParam(objective="softmax", num_class=4, subsample=0.5,
                  colsample_bytree=0.5, seed=5)
    traced = jax.jit(lambda k: gbdt._tree_sampling(p, jnp.uint32(2), 64, 9,
                                                   class_index=k))
    for k in range(4):
        want = _frozen_tree_sampling(p, jnp.uint32(2), 64, 9, class_index=k)
        for got, ref in zip(traced(jnp.int32(k)), want):
            assert np.array_equal(np.asarray(got), np.asarray(ref)), k
        for got, ref in zip(gbdt._tree_sampling(p, jnp.uint32(2), 64, 9,
                                                class_index=k), want):
            assert np.array_equal(np.asarray(got), np.asarray(ref)), k


# -- the label check -----------------------------------------------------------

@pytest.fixture
def fetched(monkeypatch):
    """Sizes of every device array that crosses to the host, by
    ``np.asarray`` or by ``jax.device_get``, while the fixture is live."""
    sizes = []
    asarray, device_get = np.asarray, jax.device_get

    def spy_asarray(a, *args, **kw):
        if isinstance(a, jax.Array):
            sizes.append(a.size)
        return asarray(a, *args, **kw)

    def spy_device_get(tree):
        sizes.extend(leaf.size for leaf in jax.tree_util.tree_leaves(tree))
        return device_get(tree)

    monkeypatch.setattr(np, "asarray", spy_asarray)
    monkeypatch.setattr(jax, "device_get", spy_device_get)
    return sizes


@pytest.mark.parametrize("what", ["labels", "eval labels"])
def test_a_device_label_in_range_passes_on_two_scalars(fetched, what):
    label = jnp.asarray(np.arange(4096) % 23, jnp.float32)
    gbdt._check_softmax_labels(label, 23, what=what)
    assert fetched and sum(fetched) <= 2 and max(fetched) == 1


@pytest.mark.parametrize("bad, said", [(23.0, "[0.0, 23.0]"),
                                       (-1.0, "[-1.0, 22.0]")])
def test_a_device_label_out_of_range_is_refused_by_name(fetched, bad, said):
    label = jnp.asarray(np.arange(4096) % 23, jnp.float32).at[77].set(bad)
    with pytest.raises(Exception) as refused:
        gbdt._check_softmax_labels(label, 23, what="eval labels")
    assert "softmax eval labels must lie in [0, 23)" in str(refused.value)
    assert said in str(refused.value)
    assert sum(fetched) <= 2


def test_an_empty_label_passes_and_fetches_nothing(fetched):
    gbdt._check_softmax_labels(jnp.zeros((0,), jnp.float32), 23)
    gbdt._check_softmax_labels(np.zeros((0,), np.float32), 23)
    assert fetched == []


def test_a_host_label_is_checked_where_it_is(fetched):
    gbdt._check_softmax_labels(np.arange(23, dtype=np.float32), 23)
    with pytest.raises(Exception, match=r"lie in \[0, 23\)"):
        gbdt._check_softmax_labels(np.arange(24, dtype=np.float32), 23)
    assert fetched == []


def test_every_softmax_entry_refuses_a_bad_device_label():
    """``fit_binned`` and ``fit_with_eval`` (its eval labels too) go through
    the one check, before anything is traced."""
    model = _model(3, "whole")
    bins, label, weight = _data(3)
    bad = label.at[5].set(3.0)
    with pytest.raises(Exception, match=r"softmax labels must lie in"):
        model.fit_binned(bins, bad, weight)
    with pytest.raises(Exception, match=r"softmax labels must lie in"):
        model.fit_with_eval(bins, bad, bins, label)
    with pytest.raises(Exception, match=r"softmax eval labels must lie in"):
        model.fit_with_eval(bins, label, bins, bad)
