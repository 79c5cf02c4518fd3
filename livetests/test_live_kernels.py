"""Real-Mosaic execution of the pallas hist kernels on an attached chip.

Every test here runs the kernels through the actual Mosaic lowering (no
interpret mode): numerics are diffed against the exact f32 scatter
formulation computed on the same device.  Shapes are kept small so the whole
lane compiles+runs in ~a minute of chip time.

Reference parity anchor: the reference validates its compute kernels only by
running them on hardware (gtest binaries on the build machine); this lane is
that discipline applied to the TPU kernels the main suite can only interpret.
"""

import numpy as np
import pytest


def _scatter_ref(jx, bins, node_ids, grad, hess, num_nodes, num_bins):
    from dmlc_core_tpu.ops.histogram import grad_histogram

    return grad_histogram(bins, node_ids, grad, hess, num_nodes=num_nodes,
                          num_bins=num_bins, method="scatter")


def _rand_problem(rows=4096, F=4, NB=32, num_nodes=4, seed=0):
    rng = np.random.RandomState(seed)
    bins = rng.randint(0, NB, (rows, F)).astype(np.int32)
    node_ids = rng.randint(0, num_nodes, rows).astype(np.int32)
    grad = rng.randn(rows).astype(np.float32)
    hess = np.abs(rng.randn(rows)).astype(np.float32)
    return bins, node_ids, grad, hess


def test_auto_means_pallas_and_interpret_is_refused(jx, monkeypatch):
    """On the chip ``auto`` is the Mosaic kernel, unprobed, and the
    interpreter cannot be switched on behind it."""
    from dmlc_core_tpu.ops import hist_pallas
    from dmlc_core_tpu.ops.histogram import resolve_hist_method

    assert resolve_hist_method("auto") == "pallas"
    assert hist_pallas.interpret_mode() is False
    monkeypatch.setattr(hist_pallas, "_INTERPRET", True)
    with pytest.raises(RuntimeError, match="refused on a TPU backend"):
        hist_pallas.interpret_mode()


def test_grad_hist_matches_scatter_on_chip(jx):
    from dmlc_core_tpu.ops import hist_pallas

    NB, NN = 32, 4
    bins, node_ids, grad, hess = _rand_problem(NB=NB, num_nodes=NN)
    g, h = hist_pallas.grad_hist_pallas(bins.T, node_ids, grad, hess,
                                        num_nodes=NN, num_bins=NB)
    g_ref, h_ref = _scatter_ref(jx, bins, node_ids, grad, hess, NN, NB)
    # kernel accumulates a bf16 one-hot dot in f32; tolerance covers the
    # bf16 rounding of g and h vs the exact-f32 scatter (random-walk error
    # on ~32-row bucket sums reaches a few 1e-2 absolute)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                               rtol=2e-2, atol=6e-2)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref),
                               rtol=2e-2, atol=6e-2)


@pytest.mark.parametrize("F,NN,plan", [
    (28, 512, (128, 28)),     # 4 node blocks, one feature block
    (200, 64, (32, 104)),     # 2 x 2 blocks, the last feature block short
                              # (96 of 104): the last level of a depth-8
                              # fit, 2x128 split
    (200, 80, (32, 104)),     # a short last node block (16 of 32 slots)
])
def test_node_blocked_deep_level_on_chip(jx, F, NN, plan):
    """Deep levels whose accumulator overflows VMEM run in node blocks:
    steps on the outermost axis of the one ``hist_level`` call's grid."""
    from dmlc_core_tpu.ops import hist_pallas

    NB = 256
    assert hist_pallas.hist_block_plan(NN, F, NB) == plan
    bins, node_ids, grad, hess = _rand_problem(rows=5000, F=F, NB=NB,
                                               num_nodes=NN, seed=1)
    g, h = hist_pallas.grad_hist_pallas(bins.T, node_ids, grad, hess,
                                        num_nodes=NN, num_bins=NB)
    g_ref, h_ref = _scatter_ref(jx, bins, node_ids, grad, hess, NN, NB)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                               rtol=2e-2, atol=6e-2)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref),
                               rtol=2e-2, atol=6e-2)


@pytest.mark.parametrize("NN,NB,split", [
    (1, 256, (16, 16)), (4, 256, (8, 32)), (8, 256, (6, 48)),
    (16, 256, (4, 64)), (32, 256, (2, 128)), (64, 256, (2, 128)),
    (8, 255, (6, 48)), (16, 128, (3, 48)),
])
def test_every_split_of_the_bin_index_on_chip(jx, NN, NB, split):
    """Each (H, L) a 256-bin fit runs, rows that are no whole tile, and node
    ids outside the level: the two int32-packed operands and their bitcast
    to bf16 unfold on the chip as the interpreter says they do; so does the
    quotient by an ``L`` of three tiles (48), with rows in the bins either
    side of its ``hi`` steps."""
    from dmlc_core_tpu.ops import hist_pallas
    from dmlc_core_tpu.ops.histogram import grad_histogram

    assert hist_pallas.hist_split_plan(NN, NB) == split
    bins, node_ids, grad, hess = _rand_problem(rows=5000, F=5, NB=NB,
                                               num_nodes=NN, seed=2 + NN)
    bins[:16, 0] = NB - 1
    for k, edge in enumerate((47, 48, 95, 96, NB - 17, NB - 16)):
        bins[16 + 8 * k:24 + 8 * k, 1 + k % 4] = edge
    node_ids[::9] = -1
    node_ids[4::13] = NN + 3
    g, h = grad_histogram(bins.astype(np.uint8), node_ids, grad, hess,
                          num_nodes=NN, num_bins=NB, method="pallas")
    keep = (node_ids >= 0) & (node_ids < NN)
    g_ref, h_ref = _scatter_ref(jx, bins[keep], node_ids[keep], grad[keep],
                                hess[keep], NN, NB)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                               rtol=2e-2, atol=6e-2)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref),
                               rtol=2e-2, atol=6e-2)


@pytest.mark.parametrize("NN,split", [(1, (16, 16)), (16, (4, 64))])
def test_a_41_feature_block_takes_the_4096_row_tile_on_chip(jx, NN, split):
    """The one row tile no other table reaches: 41 features stop
    ``hist_row_tile``'s doubling at 4,096 rows a grid step (2,048 from 65
    features up, 8,192 at 28), at the shallowest and the deepest call of a
    depth-6 fit, over rows that are no whole tile, against the bincount
    histogram of ``benchmarks/chip/reference``.  The kernel rounds g and h
    to bfloat16 and sums in float32: held to the bincount of the rounded
    values tightly, and to the exact one at the rounding's random walk over
    a bucket's ~1,000 rows (at one node)."""
    from benchmarks.chip.reference import gbdt_hist
    from dmlc_core_tpu.ops import hist_pallas

    NB, F, rows = 256, 41, 64 * 4096 + 4321
    assert hist_pallas.hist_row_tile(F, rows) == 4096
    assert hist_pallas.hist_split_plan(NN, NB) == split
    assert hist_pallas.hist_block_plan(NN, F, NB) == (NN, F)
    bins, node_ids, grad, hess = _rand_problem(rows=rows, F=F, NB=NB,
                                               num_nodes=NN, seed=40 + NN)
    g, h = hist_pallas.grad_hist_pallas(bins.T, node_ids, grad, hess,
                                        num_nodes=NN, num_bins=NB)

    def rounded(x):
        return np.asarray(jx.numpy.asarray(x).astype(jx.numpy.bfloat16)
                          .astype(jx.numpy.float32))

    exact = gbdt_hist.histogram(bins, node_ids, grad, hess, NN, NB)
    as_summed = gbdt_hist.histogram(bins, node_ids, rounded(grad),
                                    rounded(hess), NN, NB)
    walk = 2.0 ** -8 * np.sqrt(rows / (NN * NB))     # bf16 steps, a bucket
    for got, low, ref in zip((g, h), as_summed, exact):
        assert got.shape == (NN, F, NB)
        np.testing.assert_allclose(np.asarray(got), low, rtol=1e-4,
                                   atol=2e-3)
        np.testing.assert_allclose(np.asarray(got), ref, rtol=2e-2,
                                   atol=max(6e-2, 4 * walk))


def test_the_six_built_half_calls_of_a_depth_6_fit_on_chip(jx):
    """A level loop as ``_build_tree`` runs it at 256 bins: the root, then
    five levels that build the lighter child of every pair (1, 1, 2, 4, 8,
    16 node slots; the plan's (H, L) of each) and take the sibling as
    parent - built.  Every level is the exact histogram of all its nodes."""
    from dmlc_core_tpu.ops.histogram import hist_plan

    NB, F, rows, depth = 256, 5, 5000, 6
    rng = np.random.RandomState(31)
    bins = rng.randint(0, NB, (rows, F)).astype(np.int32)
    grad = rng.randn(rows).astype(np.float32)
    hess = np.abs(rng.randn(rows)).astype(np.float32)
    plan = hist_plan("pallas", None, F, depth, NB, rows=rows)
    assert plan.built_nodes == "1,1,2,4,8,16"
    assert plan.bin_split == "16x16,16x16,8x32,8x32,6x48,4x64"
    hist_bins, _ = plan.layouts(bins.astype(np.uint8))
    node = np.zeros(rows, np.int32)
    keys, parent, built_right = node, None, None
    for d in range(depth):
        n = 2 ** d
        g, h = plan.level(hist_bins, jx.numpy.asarray(keys), grad, hess, NB,
                          parent, built_right)
        g_ref, h_ref = _scatter_ref(jx, bins, node, grad, hess, n, NB)
        assert g.shape == (n, F, NB)
        np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                                   rtol=2e-2, atol=6e-2)
        np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref),
                                   rtol=2e-2, atol=6e-2)
        # route: an uneven coin a node, one node of each level left whole
        go_right = rng.rand(rows) < rng.rand(n)[node]
        go_right &= node != n - 1
        mass = np.bincount(2 * node + go_right, weights=hess,
                           minlength=2 * n)
        flags = mass[1::2] < mass[0::2]
        keys = np.where(go_right == flags[node], node, -1).astype(np.int32)
        node = (2 * node + go_right).astype(np.int32)
        parent, built_right = (g, h), jx.numpy.asarray(flags)


def test_tiny_gbdt_fit_on_chip(jx):
    """End-to-end: a small GBDT fit through resolve_hist_method('auto') on
    the chip learns a separable problem (the bench.py path in miniature)."""
    from dmlc_core_tpu.models.gbdt import GBDT, GBDTParam
    from dmlc_core_tpu.ops.histogram import apply_bins, resolve_hist_method

    assert resolve_hist_method("auto") == "pallas"
    rng = np.random.RandomState(0)
    rows, F = 8192, 8
    x = rng.randn(rows, F).astype(np.float32)
    w = rng.randn(F).astype(np.float32)
    y = ((x @ w) > 0).astype(np.float32)
    param = GBDTParam(num_boost_round=3, max_depth=4, num_bins=64,
                      learning_rate=0.5, objective="logistic")
    model = GBDT(param, num_feature=F)
    model.make_bins(x)
    bins = apply_bins(x, model.boundaries)
    ensemble, _ = model.fit_binned(bins, y)
    acc = float((np.asarray(model.predict_class(ensemble, bins)) == y).mean())
    assert acc > 0.9, f"on-chip fit failed to learn: acc={acc}"
