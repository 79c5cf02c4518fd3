"""Ops + model tests: histogram correctness, logreg/GBDT convergence, sharded runs."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dmlc_core_tpu.bridge.batching import DenseBatch, SparseBatch, block_to_sparse
from dmlc_core_tpu.models.gbdt import GBDT, GBDTParam
from dmlc_core_tpu.models.linear import LinearModel, LinearParam
from dmlc_core_tpu.ops.histogram import apply_bins, grad_histogram, quantile_boundaries
from dmlc_core_tpu.ops.sparse import segment_matvec
from dmlc_core_tpu.parallel.mesh import data_sharding, make_mesh


def make_classification(n=2000, f=10, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, f).astype(np.float32)
    w_true = rng.randn(f).astype(np.float32)
    logits = x @ w_true + 0.5
    y = (logits + rng.randn(n) * 0.3 > 0).astype(np.float32)
    return x, y


def test_quantile_bins():
    rng = np.random.RandomState(1)
    x = rng.randn(5000, 3).astype(np.float32)
    bounds = quantile_boundaries(x, num_bins=16)
    assert bounds.shape == (3, 15)
    assert (np.diff(bounds, axis=1) >= 0).all()
    bins = np.asarray(apply_bins(x, bounds))
    assert bins.min() >= 0 and bins.max() <= 15
    # roughly uniform occupancy
    counts = np.bincount(bins[:, 0], minlength=16)
    assert counts.min() > 5000 / 16 * 0.5


def test_grad_histogram_matches_numpy():
    rng = np.random.RandomState(2)
    B, F, nb, nn = 500, 4, 8, 2
    bins = rng.randint(0, nb, (B, F)).astype(np.int32)
    nodes = rng.randint(0, nn, B).astype(np.int32)
    g = rng.randn(B).astype(np.float32)
    h = rng.rand(B).astype(np.float32)
    G, H = grad_histogram(jnp.asarray(bins), jnp.asarray(nodes),
                          jnp.asarray(g), jnp.asarray(h), nn, nb)
    G, H = np.asarray(G), np.asarray(H)
    expect = np.zeros((nn, F, nb), np.float32)
    for i in range(B):
        for f in range(F):
            expect[nodes[i], f, bins[i, f]] += g[i]
    np.testing.assert_allclose(G, expect, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(H.sum(), h.sum() * F, rtol=1e-4)


def test_segment_matvec():
    w = jnp.asarray(np.array([1.0, 2.0, 3.0, 4.0], np.float32))
    value = jnp.asarray(np.array([1.0, 1.0, 2.0, 0.0], np.float32))
    index = jnp.asarray(np.array([0, 3, 1, 0], np.int32))
    row_id = jnp.asarray(np.array([0, 0, 1, 2], np.int32))  # 2 = padding seg
    out = np.asarray(segment_matvec(w, value, index, row_id, 2))
    np.testing.assert_allclose(out, [5.0, 4.0])


def test_logreg_dense_converges():
    x, y = make_classification()
    param = LinearParam(num_feature=10, learning_rate=0.5)
    model = LinearModel(param)
    params = model.init_params()
    batch = DenseBatch(jnp.asarray(x), jnp.asarray(y),
                       jnp.ones(len(y), jnp.float32))
    losses = []
    for _ in range(60):
        params, loss = model.train_step(params, batch)
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.7
    preds = np.asarray(model.predict(params, batch))
    acc = ((preds > 0.5) == y).mean()
    assert acc > 0.85


def test_logreg_sparse_matches_dense():
    from dmlc_core_tpu.data.row_block import RowBlock

    x, y = make_classification(n=256, f=6)
    # exact same data as dense and flat-COO
    offset = np.arange(257) * 6
    index = np.tile(np.arange(6, dtype=np.uint32), 256)
    block = RowBlock(offset, y, index, x.reshape(-1))
    sparse = block_to_sparse(block, nnz_bucket=2048, batch_size=256)
    dense = DenseBatch(jnp.asarray(x), jnp.asarray(y),
                       jnp.ones(256, jnp.float32))
    param = LinearParam(num_feature=6, learning_rate=0.3)
    model = LinearModel(param)
    p0 = model.init_params()
    pd, ld = model.train_step(p0, dense)
    p0 = model.init_params()
    ps, ls = model.train_step(p0, sparse)
    np.testing.assert_allclose(float(ld), float(ls), rtol=1e-4)
    np.testing.assert_allclose(np.asarray(pd["w"]), np.asarray(ps["w"]),
                               rtol=1e-3, atol=1e-5)


def test_gbdt_learns_nonlinear():
    # XOR-ish target no linear model can fit
    rng = np.random.RandomState(3)
    x = rng.randn(4000, 2).astype(np.float32)
    y = ((x[:, 0] * x[:, 1]) > 0).astype(np.float32)
    param = GBDTParam(num_boost_round=20, max_depth=3, num_bins=32,
                      learning_rate=0.4)
    model = GBDT(param, num_feature=2)
    model.make_bins(x)
    bins = model.bin_features(x)
    ensemble, margin = model.fit_binned(bins, y)
    assert ensemble.split_feat.shape == (20, 7)
    # training margin should classify well
    acc = (np.asarray(margin > 0) == y).mean()
    assert acc > 0.9
    # predict path reproduces the training margin
    pred_margin = np.asarray(model.predict_margin(ensemble, bins))
    np.testing.assert_allclose(pred_margin, np.asarray(margin),
                               rtol=1e-3, atol=1e-3)
    # and generalizes
    x2 = rng.randn(2000, 2).astype(np.float32)
    y2 = ((x2[:, 0] * x2[:, 1]) > 0).astype(np.float32)
    p2 = np.asarray(model.predict(ensemble, model.bin_features(x2)))
    assert (((p2 > 0.5) == y2).mean()) > 0.85


def test_gbdt_weighted_padding_rows_ignored():
    x, y = make_classification(n=512, f=4, seed=5)
    param = GBDTParam(num_boost_round=5, max_depth=3, num_bins=16)
    model = GBDT(param, num_feature=4)
    model.make_bins(x)
    bins = np.asarray(model.bin_features(x))
    # train on first 256 rows; padding rows (weight 0) must not change trees
    w_full = np.ones(512, np.float32)
    w_full[256:] = 0.0
    e1, _ = model.fit_binned(bins, y, w_full)
    e2, _ = model.fit_binned(bins[:256].copy(), y[:256].copy())
    np.testing.assert_array_equal(np.asarray(e1.split_feat),
                                  np.asarray(e2.split_feat))
    np.testing.assert_allclose(np.asarray(e1.leaf_value),
                               np.asarray(e2.leaf_value), rtol=1e-4, atol=1e-5)


def test_gbdt_sharded_matches_single_device():
    x, y = make_classification(n=1024, f=8, seed=7)
    param = GBDTParam(num_boost_round=4, max_depth=4, num_bins=32)
    model = GBDT(param, num_feature=8)
    model.make_bins(x)
    bins = np.asarray(model.bin_features(x))

    e_single, m_single = model.fit_binned(bins, y)

    mesh = make_mesh({"data": 8})
    sh2 = data_sharding(mesh, ndim=2)
    sh1 = data_sharding(mesh, ndim=1)
    bins_s = jax.device_put(jnp.asarray(bins), sh2)
    y_s = jax.device_put(jnp.asarray(y), sh1)
    e_shard, m_shard = model.fit_binned(bins_s, y_s)
    np.testing.assert_array_equal(np.asarray(e_single.split_feat),
                                  np.asarray(e_shard.split_feat))
    np.testing.assert_allclose(np.asarray(m_single), np.asarray(m_shard),
                               rtol=1e-3, atol=1e-3)


def test_gbdt_model_axis_sharding():
    x, y = make_classification(n=512, f=8, seed=9)
    mesh = make_mesh({"data": 4, "model": 2})
    param = GBDTParam(num_boost_round=2, max_depth=3, num_bins=16)
    model = GBDT(param, num_feature=8, model_axis="model")
    model.make_bins(x)
    bins = np.asarray(model.bin_features(x))
    with mesh:
        e, m = model.fit_binned(bins, y)
    assert np.isfinite(np.asarray(m)).all()


def test_gbdt_softmax_data_parallel_agrees_with_single():
    """Multiclass training under a dp mesh agrees with single-device (GSPMD
    turns the per-class hists into per-shard partials + allreduce)."""
    import jax
    import jax.numpy as jnp

    rng = np.random.RandomState(4)
    K, per = 3, 256
    centers = np.eye(3, 8, dtype=np.float32) * 2.5
    x = np.concatenate([rng.randn(per, 8).astype(np.float32) * 0.8 + c
                        for c in centers])
    y = np.repeat(np.arange(K), per).astype(np.float32)
    param = GBDTParam(num_boost_round=3, max_depth=3, num_bins=32,
                      objective="softmax", num_class=K)
    model = GBDT(param, num_feature=8)
    model.make_bins(x)
    bins = np.asarray(model.bin_features(x))
    e_single, m_single = model.fit_binned(bins, y)

    mesh = make_mesh({"data": 8})
    bins_s = jax.device_put(jnp.asarray(bins), data_sharding(mesh, ndim=2))
    y_s = jax.device_put(jnp.asarray(y), data_sharding(mesh, ndim=1))
    e_shard, m_shard = model.fit_binned(bins_s, y_s)
    # per-shard partial hists + allreduce reorder float sums, so near-tied
    # gains may legitimately pick a different (equal-gain) split; require
    # near-total split agreement and matching classifications
    sf1 = np.asarray(e_single.split_feat)
    sf2 = np.asarray(e_shard.split_feat)
    assert (sf1 == sf2).mean() > 0.9, (sf1 != sf2).sum()
    pred1 = np.asarray(m_single).argmax(1)
    pred2 = np.asarray(m_shard).argmax(1)
    assert (pred1 == pred2).mean() > 0.99
