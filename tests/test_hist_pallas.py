"""Pallas histogram kernel vs the exact scatter formulation (interpret mode).

The kernel's numerics are bf16 one-hot x bf16-rounded g/h with f32
accumulation, so tolerances below reflect bf16 rounding of g/h, not
algorithmic drift.
"""

import numpy as np
import pytest

from dmlc_core_tpu.ops import hist_pallas
from dmlc_core_tpu.ops.histogram import grad_histogram, hist_plan


@pytest.fixture(autouse=True)
def interpret_mode():
    hist_pallas._INTERPRET = True
    yield
    hist_pallas._INTERPRET = False


def _rand_case(b, f, nbins, nnodes, seed=0):
    rng = np.random.RandomState(seed)
    bins = rng.randint(0, nbins, (b, f)).astype(np.int32)
    node = rng.randint(0, nnodes, b).astype(np.int32)
    g = rng.randn(b).astype(np.float32)
    h = rng.rand(b).astype(np.float32)
    return bins, node, g, h


def _kernel_hist(bins, node, g, h, nnodes, nbins):
    """The kernel's wrapper on row-major test bins: it reads ``[F, B]``."""
    return hist_pallas.grad_hist_pallas(np.ascontiguousarray(bins.T), node,
                                        g, h, nnodes, nbins)


def _bf16(a):
    import jax.numpy as jnp

    return np.asarray(jnp.asarray(a).astype(jnp.bfloat16).astype(jnp.float32))


def _assert_hist_of_rounded(got, bins, node, g, h, nnodes, nbins):
    """The kernel's contract to the last bit but the f32 order of addition:
    the exact histogram of bf16-rounded g and h."""
    Gr, Hr = grad_histogram(bins, node, _bf16(g), _bf16(h), nnodes, nbins,
                            method="scatter")
    assert got[0].shape == (nnodes, bins.shape[1], nbins)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(Gr),
                               rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(np.asarray(got[1]), np.asarray(Hr),
                               rtol=1e-5, atol=1e-4)


# -- the split of the bin index: a pure function of (nodes, bins) ------------

@pytest.mark.parametrize("nbins", [8, 16, 255, 256, 257, 1024])
@pytest.mark.parametrize("nnodes", [1, 2, 3, 4, 8, 12, 16, 32, 64, 128])
def test_split_plan_covers_the_bins_on_whole_tiles(nnodes, nbins):
    hi, lo = hist_pallas.hist_split_plan(nnodes, nbins)
    assert hi * lo >= nbins and (hi - 1) * lo < nbins
    assert lo >= 16 and lo % 16 == 0            # whole bf16 tiles
    # the node side's bf16 rows: whole 16-sublane tiles, every key inside
    rows = 2 * hist_pallas._key_rows(nnodes, hi)
    assert rows % 16 == 0 and rows >= 2 * nnodes * hi
    if nbins <= 16:
        assert (hi, lo) == (1, 16)               # a small table: no split


def test_split_plan_at_256_bins():
    plan = {n: hist_pallas.hist_split_plan(n, 256) for n in PLAN_256}
    assert plan == PLAN_256
    for n, (hi, lo) in plan.items():
        # the two sides of the dot balanced up to 128 rows of the node's
        # side (one pass of the MXU): 2nH between L and 2L, equal at 8
        assert n >= 64 or lo <= 2 * n * hi <= 2 * lo
        assert n != 8 or 2 * n * hi == 2 * lo
    # one kernel call's nodes at every level of a fit, root first: the root,
    # then one child of every pair (the sibling is parent - built); a level
    # cut into node blocks runs a block's plan
    fit = hist_pallas.hist_kernel_plan(None, 28, 6, 256)
    assert fit["built_nodes"] == "1,1,2,4,8,16"
    assert fit["bin_split"] == "16x16,16x16,8x32,8x32,6x48,4x64" \
        == ",".join("%dx%d" % PLAN_256[n] for n in (1, 1, 2, 4, 8, 16))
    assert hist_pallas.hist_kernel_plan(None, 28, 1, 256)[
        "built_nodes"] == "1"
    assert hist_pallas.hist_block_plan(512, 28, 256) == (128, 28)
    assert hist_pallas.hist_kernel_plan(None, 28, 10, 256)[
        "bin_split"].endswith("1x256,1x256")


PLAN_256 = {1: (16, 16), 2: (8, 32), 4: (8, 32), 8: (6, 48), 16: (4, 64),
            32: (2, 128), 64: (2, 128), 128: (1, 256), 512: (1, 256)}


def _assert_exact_split(hi, lo):
    b = np.arange(hi * lo, dtype=np.int32)
    high, low = hist_pallas._bin_split(hi, lo)(b)
    assert high.dtype == low.dtype == np.int32
    np.testing.assert_array_equal(high, b // lo)
    np.testing.assert_array_equal(low, b % lo)


@pytest.mark.parametrize("nnodes", [1, 2, 3, 4, 8, 12, 16, 32, 64, 128])
def test_the_bodys_quotient_by_the_plans_lo_is_exact(nnodes):
    """The body's ``bin -> (hi, lo)`` by every ``L`` the plan returns for 8
    to 1,024 bins, in the int32 arithmetic the chip does (numpy's wraps the
    same way), on every bin of ``[0, H * L)``: a multiply and a shift where
    ``L`` is no power of two."""
    plans = {hist_pallas.hist_split_plan(nnodes, nbins)
             for nbins in range(8, 1025)}
    for hi, lo in plans:
        _assert_exact_split(hi, lo)
    # from two nodes up the plan does leave the powers of two
    assert nnodes == 1 or any(lo & (lo - 1) for _, lo in plans)


def test_the_quotient_stays_inside_int32_at_any_bins_that_fit_vmem():
    # one feature x 8 node slots fits VMEM up to 131,072 bins
    for hi, lo in ((2731, 48), (1366, 96), (745, 176), (8, 16368)):
        _assert_exact_split(hi, lo)


# -- the body against scatter -------------------------------------------------

@pytest.mark.parametrize("nbins", [8, 16, 255, 256, 257])
@pytest.mark.parametrize("nnodes", [1, 2, 4, 8, 16, 32, 64])
def test_every_level_shape_matches_scatter(nnodes, nbins):
    """Every (H, L) a fit can run, rows no multiple of the tile, node ids
    below 0 and at or past ``nnodes`` (rows of other node blocks)."""
    b, f = 1300, 3
    bins, node, g, h = _rand_case(b, f, nbins, nnodes, seed=nnodes + nbins)
    bins[:8, 0] = nbins - 1                      # the last (hi, lo) pair
    node[::7] = -1
    node[3::11] = nnodes + 5
    got = _kernel_hist(bins, node, g, h, nnodes, nbins)
    keep = (node >= 0) & (node < nnodes)
    _assert_hist_of_rounded(got, bins[keep], node[keep], g[keep], h[keep],
                            nnodes, nbins)


@pytest.mark.parametrize("nnodes,nbins,split", [
    (8, 255, (6, 48)), (8, 256, (6, 48)), (8, 257, (6, 48)),
    (16, 128, (3, 48)), (4, 257, (9, 32)), (2, 1024, (22, 48)),
])
def test_a_split_by_no_power_of_two_at_its_edges(nnodes, nbins, split):
    """``L = 48``: rows in the bins either side of every ``hi`` step (47 |
    48, 239 | 240: ``hi`` 4 | 5, ``lo`` 47 | 0) and in the last bins (255:
    ``hi`` 5, ``lo`` 15), whose pairs past ``num_bins`` (up to 287) no row
    has.  With h = 1 the hessian histogram is the rows' count, exact."""
    assert hist_pallas.hist_split_plan(nnodes, nbins) == split
    hi, lo = split
    b, f = 1500, 3
    bins, node, g, h = _rand_case(b, f, nbins, nnodes, seed=nbins)
    edges = [e for k in range(1, hi) for e in (k * lo - 1, k * lo)
             if e < nbins] + [nbins - 1, nbins - 2, 0]
    for k, e in enumerate(edges):
        bins[5 * k:5 * k + 5, k % f] = e
    node[::7] = -1
    h[:] = 1.0
    got = _kernel_hist(bins, node, g, h, nnodes, nbins)
    keep = node >= 0
    _assert_hist_of_rounded(got, bins[keep], node[keep], g[keep], h[keep],
                            nnodes, nbins)
    count = np.zeros((nnodes, f, nbins), np.float32)
    for j in range(f):
        np.add.at(count, (node[keep], j, bins[keep, j]), 1.0)
    np.testing.assert_array_equal(np.asarray(got[1]), count)


@pytest.mark.parametrize("f", [1, 13, 28])
def test_feature_counts_of_the_cells_match_scatter(f):
    bins, node, g, h = _rand_case(2100, f, 256, 8, seed=f)
    got = _kernel_hist(bins, node, g, h, 8, 256)
    _assert_hist_of_rounded(got, bins, node, g, h, 8, 256)


@pytest.mark.parametrize("method", ["pallas", "scatter"])
def test_grad_histogram_is_the_plans_histogram_of_its_own_layout(method):
    """Row-major bins in (the contract; the benchmark's check calls it so
    on numpy uint8 bins): the histogram a fit gets from the layout its plan
    keeps, which for the kernel is ``[F, rows]`` int32."""
    bins, node, g, h = _rand_case(1500, 5, 256, 4, seed=61)
    narrow = bins.astype(np.uint8)               # 255 must not wrap
    row_major = grad_histogram(narrow, node, g, h, num_nodes=4,
                               num_bins=256, method=method)
    plan = hist_plan(method, None, 5, 3, 256)
    hist_bins, bins_fm = plan.layouts(narrow)
    assert hist_bins.dtype == np.int32 and hist_bins.shape == (
        (5, 1500) if method == "pallas" else (1500, 5))
    assert bins_fm.dtype == np.uint8 and bins_fm.shape == (5, 1500)
    kept = plan.histogram(hist_bins, node, g, h, 4, 256)
    for a, b in zip(row_major, kept):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    if method == "pallas":
        _assert_hist_of_rounded(kept, bins, node, g, h, 4, 256)


def test_pallas_is_the_name_of_the_one_kernel():
    from dmlc_core_tpu.models.gbdt import GBDT, GBDTParam
    from dmlc_core_tpu.ops.histogram import resolve_hist_method

    assert resolve_hist_method("pallas") == "pallas"
    model = GBDT(GBDTParam(max_depth=3, num_bins=16, hist_method="pallas"),
                 num_feature=4)
    assert model._method() == "pallas"
    bins, node, g, h = _rand_case(256, 3, 8, 4, seed=9)
    G, H = grad_histogram(bins, node, g, h, 4, 8, method="pallas")
    _assert_hist_of_rounded((G, H), bins, node, g, h, 4, 8)


def _model_axis_with_no_mesh():
    from dmlc_core_tpu.models.gbdt import GBDT, GBDTParam

    GBDT(GBDTParam(max_depth=6, num_bins=256, hist_method="pallas"),
         num_feature=28, model_axis="model")._method()


def _features_that_do_not_divide_the_model_axis():
    with _mesh_2d():
        hist_pallas.hist_kernel_plan("model", 7, 3, 16)


def _rows_that_do_not_divide_the_data_axis():
    from dmlc_core_tpu.parallel.mesh import make_mesh

    bins, node, g, h = _rand_case(510, 8, 16, 4, seed=31)
    with make_mesh({"data": 8}):
        grad_histogram(bins, node, g, h, 4, 16, method="pallas")


def _bins_no_accumulator_block_fits():
    hist_pallas.hist_kernel_plan(None, 2000, 4, 2 ** 15)


def _a_retired_method_name(name):
    from dmlc_core_tpu.models.gbdt import GBDTParam

    GBDTParam(hist_method=name)


@pytest.mark.parametrize("ask,says", [
    (_model_axis_with_no_mesh,
     r"model_axis='model' is not an axis of an enclosing `with mesh:`"),
    (_features_that_do_not_divide_the_model_axis,
     r"num_feature=7 does not divide over the 2 shards of model axis"),
    (_rows_that_do_not_divide_the_data_axis,
     r"510 rows do not divide over the 8 shards .* pad rows as `fit_binned`"),
    (_bins_no_accumulator_block_fits,
     r"no accumulator block fits VMEM at num_bins=32768"),
    (lambda: _a_retired_method_name("onehot"),
     r"Invalid value 'onehot' for parameter 'hist_method'"),
    (lambda: _a_retired_method_name("pallas_fused"),
     r"Invalid value 'pallas_fused' for parameter 'hist_method'"),
], ids=["model_axis_no_mesh", "features_vs_model_axis", "rows_vs_data_axis",
        "no_block_fits", "enum_onehot", "enum_pallas_fused"])
def test_what_the_kernel_cannot_run_raises_and_names_it(ask, says):
    """Nothing falls back from the kernel: each condition of the plan, and
    each retired method name, is a ``ValueError`` that says what is wrong."""
    with pytest.raises(ValueError, match=says):
        ask()


def test_a_fit_plans_its_histograms_once(monkeypatch):
    """The mesh conditions and the blocking are settled once per fit, before
    tracing — not again at each of the six levels inside the trace."""
    from dmlc_core_tpu.models.gbdt import GBDT, GBDTParam

    calls = []
    plan = hist_pallas.hist_kernel_plan

    def counted(*args, **kwargs):
        calls.append(args)
        return plan(*args, **kwargs)

    monkeypatch.setattr(hist_pallas, "hist_kernel_plan", counted)
    rng = np.random.RandomState(3)
    x = rng.randn(300, 4).astype(np.float32)
    model = GBDT(GBDTParam(num_boost_round=2, max_depth=6, num_bins=16,
                           hist_method="pallas"), num_feature=4)
    model.make_bins(x)
    model.fit_binned(np.asarray(model.bin_features(x), np.uint8),
                     (x[:, 0] > 0).astype(np.float32))
    assert calls == [(None, 4, 6, 16)]


@pytest.mark.parametrize("b,f,nbins,nnodes", [
    (2048, 3, 8, 4),     # one tile exactly (block_rows padding no-op path)
    (300, 5, 16, 2),     # row padding inside the wrapper
    (4500, 2, 4, 8),     # accumulation across three grid steps, the last short
])
def test_matches_scatter(b, f, nbins, nnodes):
    bins, node, g, h = _rand_case(b, f, nbins, nnodes)
    got = _kernel_hist(bins, node, g, h, nnodes, nbins)
    _assert_hist_of_rounded(got, bins, node, g, h, nnodes, nbins)
    Gr, _ = grad_histogram(bins, node, g, h, nnodes, nbins, method="scatter")
    # against the exact f32 histogram: bf16 rounding of g, a random walk
    # over a bucket's rows
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(Gr),
                               rtol=2e-2, atol=6e-2)


def test_negative_node_ids_drop_out():
    bins, node, g, h = _rand_case(128, 2, 4, 2, seed=1)
    node[:50] = -1
    G, H = _kernel_hist(bins, node, g, h, 2, 4)
    mask = node >= 0
    Gr, Hr = grad_histogram(bins[mask], node[mask], g[mask], h[mask], 2, 4,
                            method="scatter")
    np.testing.assert_allclose(np.asarray(G), np.asarray(Gr),
                               rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(np.asarray(H), np.asarray(Hr),
                               rtol=2e-2, atol=2e-2)


def test_grad_histogram_dispatches_pallas():
    bins, node, g, h = _rand_case(256, 3, 8, 4, seed=2)
    G, H = grad_histogram(bins, node, g, h, 4, 8, method="pallas")
    Gr, Hr = grad_histogram(bins, node, g, h, 4, 8, method="scatter")
    np.testing.assert_allclose(np.asarray(G), np.asarray(Gr),
                               rtol=2e-2, atol=2e-2)


def test_vmem_overflow_blocks():
    """Deep trees keep the kernel via node-blocked sweeps, wide ones via
    feature blocks."""
    from dmlc_core_tpu.models.gbdt import GBDT, GBDTParam
    from dmlc_core_tpu.ops.hist_pallas import hist_block_plan, hist_fits_vmem

    assert hist_fits_vmem(32, 28, 256)
    assert not hist_fits_vmem(512, 28, 256)       # depth-10 deepest level
    assert hist_block_plan(512, 28, 256) == (128, 28)   # 4 blocked sweeps
    assert hist_block_plan(32, 28, 256) == (32, 28)     # fits: single sweep
    assert hist_block_plan(512, 512, 1024) == (8, 128)  # both axes blocked
    deep = GBDT(GBDTParam(max_depth=10, num_bins=256, hist_method="pallas"),
                num_feature=28)
    assert deep._method() == "pallas"             # blocked
    wide = GBDT(GBDTParam(max_depth=10, num_bins=1024,
                          hist_method="pallas"), num_feature=512)
    assert wide._method() == "pallas"             # 8 nodes x 128 features
    shallow = GBDT(GBDTParam(max_depth=6, num_bins=256,
                             hist_method="pallas"), num_feature=28)
    assert shallow._method() == "pallas"


def test_blocked_hist_matches_scatter():
    """Node counts beyond one VMEM accumulator: the blocked sweep must give
    the same histogram as the exact scatter."""
    # shrink the budget so blocking triggers at test-size shapes (module
    # attribute, NOT a from-import: the mutation must hit the live gate)
    orig = hist_pallas._ACC_BYTES_LIMIT
    hist_pallas._ACC_BYTES_LIMIT = 2 * 8 * 3 * 16 * 4   # 8-node blocks
    try:
        assert hist_pallas.hist_block_plan(32, 3, 16) == (8, 3)
        bins, node, g, h = _rand_case(700, 3, 16, 32, seed=31)
        G, H = _kernel_hist(bins, node, g, h, 32, 16)
        Gr, Hr = grad_histogram(bins, node, g, h, 32, 16, method="scatter")
        assert G.shape == (32, 3, 16)
        np.testing.assert_allclose(np.asarray(G), np.asarray(Gr),
                                   rtol=2e-2, atol=2e-2)
        np.testing.assert_allclose(np.asarray(H), np.asarray(Hr),
                                   rtol=2e-2, atol=2e-2)
        # non-power-of-two node count: last block is short
        bins, node, g, h = _rand_case(500, 3, 16, 20, seed=32)
        G, _ = _kernel_hist(bins, node, g, h, 20, 16)
        Gr, _ = grad_histogram(bins, node, g, h, 20, 16, method="scatter")
        assert G.shape == (20, 3, 16)
        np.testing.assert_allclose(np.asarray(G), np.asarray(Gr),
                                   rtol=2e-2, atol=2e-2)
    finally:
        hist_pallas._ACC_BYTES_LIMIT = orig


def test_non_power_of_two_nodes_padding():
    """The node side's rows stay whole bf16 tiles for any node count."""
    bins, node, g, h = _rand_case(256, 2, 8, 12, seed=4)
    G, H = _kernel_hist(bins, node, g, h, 12, 8)
    Gr, _ = grad_histogram(bins, node, g, h, 12, 8, method="scatter")
    assert G.shape == (12, 2, 8)
    np.testing.assert_allclose(np.asarray(G), np.asarray(Gr),
                               rtol=2e-2, atol=2e-2)


def test_gbdt_fit_pallas_matches_scatter_splits():
    """End-to-end tiny fit: pallas and scatter grow the same trees."""
    from dmlc_core_tpu.models.gbdt import GBDT, GBDTParam

    rng = np.random.RandomState(3)
    x = rng.randn(300, 4).astype(np.float32)   # row count forces fit padding
    y = (x[:, 0] + 0.1 * rng.randn(300) > 0).astype(np.float32)
    param = GBDTParam(num_boost_round=2, max_depth=3, num_bins=16,
                      hist_method="pallas")
    model = GBDT(param, num_feature=4)
    model.make_bins(x)
    bins = np.asarray(model.bin_features(x))
    ens_p, margin_p = model.fit_binned(bins, y)

    model_s = GBDT(GBDTParam(num_boost_round=2, max_depth=3, num_bins=16,
                             hist_method="scatter"), num_feature=4)
    model_s.boundaries = model.boundaries
    ens_s, margin_s = model_s.fit_binned(bins, y)

    assert margin_p.shape == (300,)
    np.testing.assert_array_equal(np.asarray(ens_p.split_feat),
                                  np.asarray(ens_s.split_feat))
    np.testing.assert_allclose(np.asarray(margin_p), np.asarray(margin_s),
                               rtol=5e-2, atol=5e-2)


def _mesh_2d(data=4, model=2):
    import jax
    from dmlc_core_tpu.parallel.mesh import make_mesh

    return make_mesh({"data": data, "model": model},
                     devices=jax.devices()[:data * model])


def test_sharded_pallas_matches_scatter():
    """Model-sharded hist keeps the pallas kernel via shard_map (VERDICT r1
    item 3) and matches the exact scatter result."""
    import jax

    bins, node, g, h = _rand_case(256, 8, 16, 4, seed=11)
    mesh = _mesh_2d()
    calls = []
    orig = hist_pallas.grad_hist_pallas_sharded

    def spy(*args, **kwargs):
        calls.append(args[7])          # model_axis
        return orig(*args, **kwargs)

    hist_pallas.grad_hist_pallas_sharded = spy
    try:
        with mesh:
            G, H = jax.jit(lambda *a: grad_histogram(
                *a, 4, 16, model_axis="model", method="pallas"))(
                    bins, node, g, h)
            G, H = np.asarray(G), np.asarray(H)
    finally:
        hist_pallas.grad_hist_pallas_sharded = orig
    assert calls == ["model"], "sharded pallas path was not taken"
    Gr, Hr = grad_histogram(bins, node, g, h, 4, 16, method="scatter")
    np.testing.assert_allclose(G, np.asarray(Gr), rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(H, np.asarray(Hr), rtol=2e-2, atol=2e-2)


def test_sharded_pallas_uneven_features_raise_at_trace_time():
    """F not divisible by the model axis: the jitted caller hears it while
    tracing, before anything runs."""
    import jax

    bins, node, g, h = _rand_case(256, 7, 8, 4, seed=13)   # 7 % 2 != 0
    with _mesh_2d():
        with pytest.raises(ValueError, match="num_feature=7 does not divide"):
            jax.jit(lambda *a: grad_histogram(
                *a, 4, 8, model_axis="model", method="pallas"))(
                    bins, node, g, h)


def test_gbdt_model_sharded_keeps_pallas():
    """Under an ambient mesh, a model-sharded GBDT resolves to pallas and
    trains on the kernel path end-to-end."""
    import jax
    import jax.numpy as jnp
    from dmlc_core_tpu.models.gbdt import GBDT, GBDTParam
    from dmlc_core_tpu.parallel.mesh import data_sharding

    mesh = _mesh_2d()
    rng = np.random.RandomState(5)
    B, F = 64, 8
    x = rng.randn(B, F).astype(np.float32)
    y = (x[:, 0] > 0).astype(np.float32)
    model = GBDT(GBDTParam(num_boost_round=2, max_depth=3, num_bins=16,
                           hist_method="pallas"), num_feature=F,
                 model_axis="model")
    model.make_bins(x)
    with mesh:
        assert model._method() == "pallas"
        bins = jax.device_put(model.bin_features(x),
                              data_sharding(mesh, ndim=2))
        label = jax.device_put(jnp.asarray(y), data_sharding(mesh, ndim=1))
        weight = jax.device_put(jnp.ones(B, jnp.float32),
                                data_sharding(mesh, ndim=1))
        margin = jax.device_put(jnp.zeros(B, jnp.float32),
                                data_sharding(mesh, ndim=1))
        new_margin, _ = model.boost_round(margin, bins, label, weight)
        new_margin = np.asarray(new_margin)
    assert np.isfinite(new_margin).all()
    # same trees as the unsharded scatter fit
    ref = GBDT(GBDTParam(num_boost_round=2, max_depth=3, num_bins=16,
                         hist_method="scatter"), num_feature=F)
    ref.boundaries = model.boundaries
    rm, _ = ref.boost_round(jnp.zeros(B, jnp.float32),
                            jnp.asarray(model.bin_features(x)),
                            jnp.asarray(y), jnp.ones(B, jnp.float32))
    np.testing.assert_allclose(new_margin, np.asarray(rm), rtol=5e-2,
                               atol=5e-2)


def test_ambient_mesh_probe_on_current_jax():
    """The ambient-mesh accessor reaches into jax internals
    (hist_pallas.ambient_mesh); if a jax upgrade moves it, the model-sharded
    kernel could not find its mesh.  Pin the probe directly."""
    mesh = _mesh_2d()
    assert hist_pallas.ambient_mesh() is None
    with mesh:
        m = hist_pallas.ambient_mesh()
        assert m is not None, (
            "ambient_mesh() lost the enclosing mesh on jax "
            + __import__("jax").__version__)
        assert m.shape["model"] == 2
        # and the plan hands it out for the kernel's shard_map
        assert hist_pallas.hist_kernel_plan("model", 8, 3, 16,
                                            batch=256)["mesh"] is m
    assert hist_pallas.ambient_mesh() is None


def test_subsample_draw_independent_of_row_padding(interpret_mode):
    """The per-tree subsample draw must be made over the UNPADDED row count:
    fit_binned pads rows to the pallas tile, boost_round does not — with
    padding-dependent sampling the two entry points would train different
    trees on identical data (n_rows deliberately not a tile multiple)."""
    import jax.numpy as jnp

    from dmlc_core_tpu.models.gbdt import GBDT, GBDTParam

    rng = np.random.RandomState(21)
    n, F = 1500, 4                       # no whole number of tiles -> fit pads
    x = rng.randn(n, F).astype(np.float32)
    y = (x[:, 0] > 0).astype(np.float32)
    m = GBDT(GBDTParam(num_boost_round=3, max_depth=3, num_bins=16,
                       subsample=0.7, seed=5, hist_method="pallas"),
             num_feature=F)
    m.make_bins(x)
    bins = jnp.asarray(np.asarray(m.bin_features(x), np.int32))
    ens_fit, _ = m.fit_binned(bins, y)

    margin = jnp.zeros(n, jnp.float32)
    w = jnp.ones(n, jnp.float32)
    sfs = []
    for r in range(3):
        margin, tree = m.boost_round(margin, bins, jnp.asarray(y), w,
                                     round_index=r)
        sfs.append(np.asarray(tree[0]))
    np.testing.assert_array_equal(np.stack(sfs),
                                  np.asarray(ens_fit.split_feat))


def test_dp_only_mesh_runs_the_kernel_under_shard_map():
    """A Mosaic kernel has no GSPMD partitioning rule — on a TPU, jit
    refuses one over sharded operands — so rows sharded over a data axis
    (no model axis) route through grad_hist_pallas_sharded: per-shard
    kernel + psum over data.  Interpret mode lowers to ordinary ops and
    could not show the refusal; the routing is what is pinned here."""
    import jax
    from dmlc_core_tpu.parallel.mesh import data_sharding, make_mesh

    mesh = make_mesh({"data": 8})
    bins, node, g, h = _rand_case(512, 8, 16, 4, seed=31)
    # no mesh: one plain kernel call
    plain = hist_pallas.hist_kernel_plan(None, 8, 3, 16, batch=512)
    assert plain["mesh"] is None
    assert plain["row_multiple"] == hist_pallas.hist_row_tile(8, 512)
    calls = []
    orig = hist_pallas.grad_hist_pallas_sharded

    def spy(*args, **kwargs):
        calls.append(args[7])          # model_axis
        return orig(*args, **kwargs)

    hist_pallas.grad_hist_pallas_sharded = spy
    try:
        with mesh:
            sharded = hist_pallas.hist_kernel_plan(None, 8, 3, 16, batch=512)
            assert sharded["mesh"] is mesh
            assert sharded["row_multiple"] == \
                8 * hist_pallas.hist_row_tile(8, 512 // 8)
            # rows that do not divide the data axis cannot be shard_mapped
            with pytest.raises(ValueError, match="510 rows do not divide"):
                hist_pallas.hist_kernel_plan(None, 8, 3, 16, batch=510)
            placed = [jax.device_put(a, data_sharding(mesh, ndim=a.ndim))
                      for a in (bins, node, g, h)]
            G, H = jax.jit(lambda *a: grad_histogram(
                *a, 4, 16, method="pallas"))(*placed)
            G, H = np.asarray(G), np.asarray(H)
    finally:
        hist_pallas.grad_hist_pallas_sharded = orig
    assert calls == [None], "dp-only sharded kernel path was not taken"
    Gr, Hr = grad_histogram(bins, node, g, h, 4, 16, method="scatter")
    np.testing.assert_allclose(G, np.asarray(Gr), rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(H, np.asarray(Hr), rtol=2e-2, atol=2e-2)


def test_gbdt_fit_on_a_dp_mesh_matches_the_one_device_kernel_fit():
    """The whole compiled fit, rows sharded over 8 devices with the kernel
    under shard_map, grows the trees the one-device kernel fit grows."""
    import jax
    from dmlc_core_tpu.models.gbdt import GBDT, GBDTParam
    from dmlc_core_tpu.parallel.mesh import data_sharding, make_mesh

    rng = np.random.RandomState(33)
    n, F = 2000, 6                      # no whole tile a shard -> fit pads
    x = rng.randn(n, F).astype(np.float32)
    y = (x[:, 0] * x[:, 1] > 0).astype(np.float32)
    model = GBDT(GBDTParam(num_boost_round=3, max_depth=3, num_bins=16,
                           hist_method="pallas"), num_feature=F)
    model.make_bins(x)
    bins = np.asarray(model.bin_features(x), np.uint8)
    ens_one, margin_one = model.fit_binned(bins, y)
    mesh = make_mesh({"data": 8})
    with mesh:
        assert model._fit_method(bins) == "pallas"
        ens_dp, margin_dp = model.fit_binned(
            jax.device_put(bins, data_sharding(mesh, ndim=2)),
            jax.device_put(y, data_sharding(mesh)))
        margin_dp = np.asarray(margin_dp)
    np.testing.assert_array_equal(np.asarray(ens_dp.split_feat),
                                  np.asarray(ens_one.split_feat))
    np.testing.assert_array_equal(np.asarray(ens_dp.split_bin),
                                  np.asarray(ens_one.split_bin))
    np.testing.assert_allclose(margin_dp, np.asarray(margin_one),
                               rtol=1e-4, atol=1e-4)


# -- feature blocks: a second, outer grid axis of the same kernel call -------

@pytest.fixture()
def feature_block_budget():
    """Shrink the VMEM budget to 8 node slots x 128 features x ``nbins``
    bins, so test-size tables are blocked (module attribute, NOT a
    from-import: the mutation must hit the live gate).  And unroll the tile
    body over 16 features, not a whole block: a block then runs the loop
    over groups that on the chip only a table wider than 128 unblocked
    features does (100 features: six trips and a static rest of 4), and
    the interpreter traces an eighth of the body."""
    budget, unroll = hist_pallas._ACC_BYTES_LIMIT, hist_pallas._UNROLL
    hist_pallas._UNROLL = 16

    def shrink(nbins):
        hist_pallas._ACC_BYTES_LIMIT = 2 * 8 * 128 * nbins * 4

    yield shrink
    hist_pallas._ACC_BYTES_LIMIT, hist_pallas._UNROLL = budget, unroll


@pytest.mark.parametrize("b,f,nbins,nnodes,plan", [
    (700, 384, 4, 4, (4, 128)),     # three whole feature blocks, two tiles
    (300, 300, 4, 8, (8, 104)),     # F no multiple of the block; row padding
    (300, 130, 8, 3, (3, 72)),      # two blocks, the last 58 of 72 wide
    (500, 260, 4, 20, (8, 88)),     # node blocks x feature blocks, short last
    (256, 100, 4, 32, (8, 100)),    # under 128 features: node blocks alone
])
def test_feature_blocked_hist_matches_scatter(feature_block_budget, b, f,
                                              nbins, nnodes, plan):
    feature_block_budget(nbins)
    assert hist_pallas.hist_block_plan(nnodes, f, nbins) == plan
    bins, node, g, h = _rand_case(b, f, nbins, nnodes, seed=41)
    G, H = _kernel_hist(bins, node, g, h, nnodes, nbins)
    Gr, Hr = grad_histogram(bins, node, g, h, nnodes, nbins,
                            method="scatter")
    assert G.shape == (nnodes, f, nbins)
    # bf16 rounding of g and h is a random walk over a bucket's rows: over
    # thousands of buckets its tail reaches a few 1e-2 (as livetests/ hold)
    np.testing.assert_allclose(np.asarray(G), np.asarray(Gr),
                               rtol=2e-2, atol=6e-2)
    np.testing.assert_allclose(np.asarray(H), np.asarray(Hr),
                               rtol=2e-2, atol=6e-2)


@pytest.mark.parametrize("f,width", [(256, 128), (300, 104)])
def test_feature_blocks_side_by_side_are_the_unblocked_result(
        feature_block_budget, f, width):
    """Blocking changes where a feature's partial sums live, not one bit of
    them: same tiles, same dots, same order over the rows."""
    bins, node, g, h = _rand_case(2100, f, 8, 6, seed=42)
    whole = _kernel_hist(bins, node, g, h, 6, 8)
    feature_block_budget(8)
    assert hist_pallas.hist_block_plan(6, f, 8) == (6, width)
    blocked = _kernel_hist(bins, node, g, h, 6, 8)
    for a, b in zip(whole, blocked):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_one_grid_for_every_width_and_depth():
    """One kernel program for every table and level: a grid of (node
    blocks, feature blocks, row tiles) in one call; a level whose slots and
    features fit one block is the case of one block, whose output block
    never moves and keeps the default buffering."""
    import jax
    import jax.numpy as jnp

    rows = 2 * hist_pallas.BLOCK_ROWS
    row = jnp.zeros((rows,), jnp.float32)
    bins = jnp.zeros((300, rows), jnp.int32)

    def calls(block_features, num_nodes=4, block_nodes=None):
        jaxpr = jax.make_jaxpr(lambda n, g, h, b: hist_pallas.hist_matmul_pallas(
            (n, g, h), b, 8, num_nodes=num_nodes, block_nodes=block_nodes,
            block_features=block_features))(row.astype(jnp.int32), row, row,
                                            bins)
        return [(m.grid, m.block_mappings[-1].pipeline_mode is not None)
                for m in (e.params["grid_mapping"] for e in jaxpr.jaxpr.eqns
                          if e.primitive.name == "pallas_call")]

    assert calls(None) == calls(300) == calls(512) == [((1, 1, 2), False)]
    assert calls(None, block_nodes=4) == calls(None, block_nodes=8) \
        == [((1, 1, 2), False)]
    assert calls(128) == [((1, 3, 2), True)]
    # node blocks are steps of the same call, a short last one included
    assert calls(None, 20, 8) == [((3, 1, 2), True)]
    assert calls(128, 64, 32) == [((2, 3, 2), True)]


@pytest.mark.parametrize("b,f,nbins,nnodes,block_nodes,block_features", [
    (2100, 5, 16, 32, 8, None),     # four whole node blocks, two row tiles
    (700, 3, 256, 20, 8, None),     # a short last node block (4 of 8 slots)
    (900, 40, 8, 20, 8, 16),        # short last node AND feature block
    (300, 140, 4, 64, 32, 128),     # the shape of a depth-8 level, small
])
def test_node_blocks_are_the_one_block_calls_side_by_side(
        b, f, nbins, nnodes, block_nodes, block_features):
    """What the loop over kernel calls did, as grid steps of one call: block
    k is, bit for bit, the one-block call of ``block_nodes`` slots on node
    ids shifted by ``k * block_nodes`` (same split of the bin index, same
    tiles, same dots, same order over the rows).  A short last block's
    spare slots catch the rows whose id is just past ``nnodes`` and are
    dropped with them: such a row adds nothing, as under one block."""
    bins, node, g, h = _rand_case(b, f, nbins, nnodes, seed=61)
    node[::7] = -1
    node[3::11] = nnodes + 2
    bins_fm = np.ascontiguousarray(bins.T)
    blocked = np.asarray(hist_pallas.hist_matmul_pallas(
        (node, g, h), bins_fm, nbins, num_nodes=nnodes,
        block_nodes=block_nodes, block_features=block_features))
    assert blocked.shape == (2 * nnodes, f * nbins)
    parts = [np.asarray(hist_pallas.hist_matmul_pallas(
        (node - b0, g, h), bins_fm, nbins, num_nodes=block_nodes,
        block_features=block_features)).reshape(2, block_nodes, f * nbins)
        for b0 in range(0, nnodes, block_nodes)]
    whole = np.concatenate(parts, axis=1)
    np.testing.assert_array_equal(
        blocked, whole[:, :nnodes].reshape(2 * nnodes, f * nbins))
    Gr, Hr = grad_histogram(bins, node, _bf16(g), _bf16(h), nnodes, nbins,
                            method="scatter")
    np.testing.assert_allclose(blocked[:nnodes].reshape(nnodes, f, nbins),
                               np.asarray(Gr), rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(blocked[nnodes:].reshape(nnodes, f, nbins),
                               np.asarray(Hr), rtol=1e-5, atol=1e-4)


def test_any_node_count_is_one_kernel_call(feature_block_budget):
    """``grad_hist_pallas`` makes exactly one ``hist_level`` call whatever
    the blocking: 500 nodes x 260 features run 63 x 3 blocks inside it,
    named by all the node slots it builds and by no level (a caller that
    has none)."""
    import jax
    import jax.numpy as jnp

    feature_block_budget(4)
    rows = hist_pallas.BLOCK_ROWS
    row = jnp.zeros((rows,), jnp.float32)
    for nodes, f, grid in ((500, 260, (63, 3, 1)), (20, 260, (3, 3, 1)),
                           (32, 100, (4, 1, 1)), (8, 100, (1, 1, 1))):
        jaxpr = jax.make_jaxpr(
            lambda b, n, g, h: hist_pallas.grad_hist_pallas(
                b, n, g, h, nodes, 4))(jnp.zeros((f, rows), jnp.int32),
                                       row.astype(jnp.int32), row, row)
        calls = [e for e in jaxpr.jaxpr.eqns
                 if e.primitive.name == "pallas_call"]
        assert [c.params["name"] for c in calls] == [f"hist_level_n{nodes}"]
        assert calls[0].params["grid_mapping"].grid == grid


@pytest.mark.parametrize("f,nbins,nnodes,asks", [
    (28, 256, 32, False),       # HIGGS' deepest level: Mosaic's default
    (128, 256, 32, False),      # a blocked block of epsilon's: 8 MiB, one buffer
    (2000, 16, 8, True),        # unblocked by the byte rule, 62 MiB lane-padded
])
def test_a_table_of_many_narrow_features_asks_for_its_vmem(f, nbins, nnodes,
                                                           asks):
    """The byte rule counts a dense ``[2n, F * bins]``; the kernel's block
    pads ``2nH`` to the lanes, so 2,000 features x 16 bins, one block by the
    rule, hold 2,000 x 16 x 128 f32 twice: on a described v5e that call runs
    out of VMEM unasked and compiles with the limit (sandbox, PR 28)."""
    import jax
    import jax.numpy as jnp

    rows = hist_pallas.BLOCK_ROWS
    row = jnp.zeros((rows,), jnp.float32)
    _, features = hist_pallas.hist_block_plan(nnodes, f, nbins)
    jaxpr = jax.make_jaxpr(lambda n, g, h, b: hist_pallas.hist_matmul_pallas(
        (n, g, h), b, nbins, num_nodes=nnodes, block_features=features))(
            row.astype(jnp.int32), row, row, jnp.zeros((f, rows), jnp.int32))
    (call,) = [e for e in jaxpr.jaxpr.eqns
               if e.primitive.name == "pallas_call"]
    params = call.params["compiler_params"].get("mosaic_tpu")
    assert (params is not None and params.vmem_limit_bytes > 32 << 20) == asks


def test_the_kernel_entry_returns_the_flat_histogram():
    """``hist_matmul_pallas``: per-row (node, g, h) and feature-major bins
    in, ``[2 * nodes, F * num_bins]`` out, column ``f * num_bins + b``, g's
    rows first — the transpose back from the kernel's ``[F, L, 2nH]`` is
    inside it (the benchmark's dropped-block check wraps it and zeroes
    columns by that index)."""
    bins, node, g, h = _rand_case(700, 5, 256, 4, seed=71)
    out = np.asarray(hist_pallas.hist_matmul_pallas(
        (node, g, h), np.ascontiguousarray(bins.T), 256, num_nodes=4))
    assert out.shape == (8, 5 * 256) and out.dtype == np.float32
    G, H = _kernel_hist(bins, node, g, h, 4, 256)
    np.testing.assert_array_equal(out[:4].reshape(4, 5, 256), np.asarray(G))
    np.testing.assert_array_equal(out[4:].reshape(4, 5, 256), np.asarray(H))
    _assert_hist_of_rounded((G, H), bins, node, g, h, 4, 256)


def test_wide_tables_plan_feature_blocks():
    """2,000 features x 256 bins (epsilon): 25 blocks of 80 features under
    all 32 nodes of the deepest level, one call a level."""
    from dmlc_core_tpu.models.gbdt import GBDT, GBDTParam

    assert hist_pallas.hist_block_plan(32, 2000, 256) == (32, 80)
    assert hist_pallas.hist_block_plan(1, 2000, 256) == (1, 80)
    assert hist_pallas.hist_block_plan(32, 28, 256) == (32, 28)
    assert hist_pallas.hist_block_plan(512, 2000, 256) == (32, 80)
    def blocks(num_feature, max_depth):
        # the deepest level's node blocks are the last of
        # ``level_node_blocks``: the span has no entry of its own for them
        plan = hist_plan("pallas", None, num_feature, max_depth, 256).blocks()
        assert "node_blocks" not in plan
        return (int(plan["level_node_blocks"].split(",")[-1]),
                plan["feature_blocks"])

    assert blocks(2000, 6) == (1, 25)
    # the deepest level builds 256 of its 512 nodes, 32 a grid step: the
    # node blocks are those of a 128-feature block, whatever its width
    assert blocks(2000, 10) == (8, 25)
    assert blocks(28, 6) == (1, 1)
    # epsilon at depth 8: the last level builds 64 nodes in two blocks of
    # 32, each the 2x128 split, inside its one call
    deep = hist_pallas.hist_kernel_plan(None, 2000, 8, 256)
    assert deep["level_node_blocks"] == "1,1,1,1,1,1,1,2"
    assert deep["built_nodes"] == "1,1,2,4,8,16,32,64"
    assert deep["bin_split"] == \
        "16x16,16x16,8x32,8x32,6x48,4x64,2x128,2x128"
    # two node blocks are still one call, named by all 64 slots
    assert deep["level_kernels"].split(",")[-2:] == [
        "hist_level_L6_n32", "hist_level_L7_n64"]
    assert hist_pallas.hist_kernel_plan(None, 2000, 10, 256)[
        "level_node_blocks"] == "1,1,1,1,1,1,1,2,4,8"
    # a narrow table holds 128 slots a block: depth 8 is not blocked
    assert hist_pallas.hist_kernel_plan(None, 28, 8, 256)[
        "level_node_blocks"] == "1,1,1,1,1,1,1,1"
    assert hist_pallas.hist_kernel_plan(None, 2000, 6, 256)["mesh"] is None
    # bins in the tens of thousands: 8 node slots x 128 features overflow
    assert hist_pallas.hist_block_plan(8, 2000, 2 ** 15) is None
    with pytest.raises(ValueError, match="no accumulator block fits"):
        hist_pallas.hist_kernel_plan(None, 2000, 4, 2 ** 15)
    wide = GBDT(GBDTParam(max_depth=6, num_bins=256, hist_method="pallas"),
                num_feature=2000)
    assert wide._method() == "pallas"
    assert wide._hist_blocks("pallas") == {
        "level_node_blocks": "1,1,1,1,1,1",
        "feature_blocks": 25, "block_features": 80,
        "row_tile": hist_pallas.BLOCK_ROWS,
        "bin_split": "16x16,16x16,8x32,8x32,6x48,4x64",
        "built_nodes": "1,1,2,4,8,16",
        "level_kernels": "hist_level_L0_n1,hist_level_L1_n1,"
                         "hist_level_L2_n2,hist_level_L3_n4,"
                         "hist_level_L4_n8,hist_level_L5_n16"}
    assert wide._hist_blocks("scatter") == {"level_node_blocks": "",
                                            "feature_blocks": 0,
                                            "block_features": 0,
                                            "row_tile": 0,
                                            "bin_split": "",
                                            "built_nodes": "1,1,2,4,8,16",
                                            "level_kernels": ""}
    with _mesh_2d():
        sharded = GBDT(GBDTParam(max_depth=6, num_bins=256,
                                 hist_method="pallas"), num_feature=2000,
                       model_axis="model")
        assert sharded._method() == "pallas"
        # each model shard blocks its own 1,000 features: 9 x 112, 1,008
        # slots where 8 x 128 pay 1,024
        blocks = sharded._hist_blocks("pallas")
        assert (blocks["level_node_blocks"], blocks["feature_blocks"],
                blocks["block_features"]) == ("1,1,1,1,1,1", 9, 112)


def _wide_rehearsal(n=900, f=260, seed=51):
    """Rows whose label hangs on one feature of each of the three feature
    blocks, 88 wide by the rule or 128."""
    from dmlc_core_tpu.models.gbdt import GBDT, GBDTParam

    rng = np.random.RandomState(seed)
    x = rng.randn(n, f).astype(np.float32)
    y = (x[:, 5] + x[:, 140] * x[:, 259] + 0.1 * rng.randn(n) > 0
         ).astype(np.float32)

    def model(method, **kw):
        m = GBDT(GBDTParam(num_boost_round=2, max_depth=3, num_bins=8,
                           hist_method=method), num_feature=f, **kw)
        m.make_bins(x)
        return m

    bins = np.asarray(model("scatter").bin_features(x), np.uint8)
    return model, bins, y


def test_gbdt_wide_fit_matches_scatter_and_the_plain_reference(
        feature_block_budget):
    """A fit whose every level runs three feature blocks grows the trees of
    the exact scatter fit, and those are the plain numpy reference's."""
    from benchmarks.chip.reference import gbdt_hist

    feature_block_budget(8)
    model, bins, y = _wide_rehearsal()
    kernel = model("pallas")
    assert kernel._fit_method(bins) == "pallas"
    assert kernel._hist_blocks("pallas") == {
        "level_node_blocks": "1,1,1", "feature_blocks": 3,
        "block_features": 88, "row_tile": 2048,
        "bin_split": "1x16,1x16,1x16", "built_nodes": "1,1,2",
        "level_kernels":
            "hist_level_L0_n1,hist_level_L1_n1,hist_level_L2_n2"}
    ens_p, margin_p = kernel.fit_binned(bins, y)
    ens_s, margin_s = model("scatter").fit_binned(bins, y)
    np.testing.assert_array_equal(np.asarray(ens_p.split_feat),
                                  np.asarray(ens_s.split_feat))
    np.testing.assert_array_equal(np.asarray(ens_p.split_bin),
                                  np.asarray(ens_s.split_bin))
    trees, margin_r = gbdt_hist.boost(
        bins, y, 2, max_depth=3, num_bins=8, learning_rate=0.3,
        reg_lambda=1.0, min_child_weight=1.0)
    np.testing.assert_array_equal(np.asarray(ens_p.split_feat),
                                  np.stack([t[0] for t in trees]))
    np.testing.assert_array_equal(np.asarray(ens_p.split_bin),
                                  np.stack([t[1] for t in trees]))
    # splits in all three feature blocks
    used = set(np.asarray(ens_p.split_feat).ravel()) - {-1}
    assert {f // 88 for f in used} == {0, 1, 2}
    np.testing.assert_allclose(np.asarray(margin_p), margin_r,
                               rtol=5e-2, atol=5e-2)


@pytest.mark.parametrize("axes,model_axis", [
    ({"data": 8}, None),
    ({"data": 2, "model": 2}, "model"),
])
def test_sharded_fits_with_feature_blocks_match_the_one_device_fit(
        feature_block_budget, axes, model_axis):
    """dp: every chip's kernel blocks all F features; model axis: every
    model shard blocks its own F/mp.  Either grows the one-device trees."""
    import jax
    from dmlc_core_tpu.parallel.mesh import data_sharding, make_mesh

    feature_block_budget(8)
    f = 260 if model_axis is None else 520
    model, bins, y = _wide_rehearsal(n=1000, f=f, seed=52)
    ens_one, margin_one = model("pallas").fit_binned(bins, y)
    count = int(np.prod(list(axes.values())))
    mesh = make_mesh(axes, devices=jax.devices()[:count])
    sharded = model("pallas", model_axis=model_axis)
    with mesh:
        assert sharded._fit_method(bins) == "pallas"
        assert sharded._hist_blocks("pallas")["feature_blocks"] == 3
        ens_sh, margin_sh = sharded.fit_binned(
            jax.device_put(bins, data_sharding(mesh, ndim=2)),
            jax.device_put(y, data_sharding(mesh)))
        margin_sh = np.asarray(margin_sh)
    np.testing.assert_array_equal(np.asarray(ens_sh.split_feat),
                                  np.asarray(ens_one.split_feat))
    np.testing.assert_array_equal(np.asarray(ens_sh.split_bin),
                                  np.asarray(ens_one.split_bin))
    np.testing.assert_allclose(margin_sh, np.asarray(margin_one),
                               rtol=1e-4, atol=1e-4)


# -- the width of a feature block: a pure function of the table's features ---

@pytest.mark.parametrize("f", [129, 131, 136, 257, 260, 968, 1000, 1009, 1024,
                               2000, 2003, 2048, 4096])
def test_feature_blocks_divide_the_table(f):
    """A blocked table's blocks are a multiple of the int32 tile's 8
    sublanes wide, 128 at most, cover every column, never pay more slots
    than blocks of 128 do, and none at all where a legal width divides F;
    the plan hands the width out at every node count that is blocked."""
    w = hist_pallas.hist_feature_block(f)
    blocks = -(-f // w)
    # from 72: at 64 features ``hist_row_tile`` doubles a step's rows
    assert w % 8 == 0 and 72 <= w <= 128
    assert hist_pallas.hist_row_tile(w, 10 ** 7) == hist_pallas.BLOCK_ROWS
    assert f <= blocks * w <= -(-f // 128) * 128
    if any(f % d == 0 for d in range(72, 129, 8)):
        assert blocks * w == f
    assert hist_pallas.hist_block_plan(32, f, 256) == (32, w)
    assert hist_pallas.hist_block_plan(64, f, 256) == (32, w)
    # a level that fits one block is not blocked, whatever the rule says
    if hist_pallas.hist_fits_vmem(8, f, 256):        # up to 512 features
        assert hist_pallas.hist_block_plan(8, f, 256) == (8, f)


def test_the_widths_of_the_tables_the_records_name():
    """968 -> 11 x 88 and 2,000 -> 25 x 80, no slot wasted (8 and 16
    blocks of 128 paid 1,024 and 2,048); 260 -> 3 x 88; 136, which only the
    eager 32-node check blocks, -> 2 x 72; whole blocks of 128 stay."""
    width = hist_pallas.hist_feature_block
    assert [width(f) for f in (968, 2000, 260, 136)] == [88, 80, 88, 72]
    assert [width(f) for f in (256, 512, 1024, 2048, 4096)] == [128] * 5
    bosch = hist_pallas.hist_kernel_plan(None, 968, 6, 256)
    assert (bosch["feature_blocks"], bosch["block_features"]) == (11, 88)
    assert bosch["level_node_blocks"] == "1,1,1,1,1,1"
    assert bosch["row_tile"] == hist_pallas.BLOCK_ROWS
    for features in (13, 28, 136):
        narrow = hist_pallas.hist_kernel_plan(None, features, 6, 256)
        assert (narrow["feature_blocks"],
                narrow["block_features"]) == (1, features)


@pytest.mark.parametrize("f", [200, 264])
def test_blocks_of_any_width_sum_the_same_bits(f):
    """A feature's histogram does not depend on which block holds it: at
    the rule's width (104 with a short last block; 88, which divides), at
    128 and unblocked the entry returns the same bits."""
    bins, node, g, h = _rand_case(2 * 256 + 40, f, 16, 4, seed=39)
    bins_t = np.ascontiguousarray(bins.T)

    def entry(block_features):
        return np.asarray(hist_pallas.hist_matmul_pallas(
            (node, g, h), bins_t, 16, num_nodes=4, block_rows=256,
            block_features=block_features))

    rule = hist_pallas.hist_feature_block(f)
    assert rule == {200: 104, 264: 88}[f]
    whole = entry(None)
    assert np.abs(whole).sum() > 0
    np.testing.assert_array_equal(entry(rule), whole)
    np.testing.assert_array_equal(entry(128), whole)


def test_a_fit_grows_the_trees_of_128_wide_blocks(feature_block_budget,
                                                  monkeypatch):
    """260 features in 3 blocks of 88 or 3 of 128 (384 slots): the same
    trees and the same margins, to the bit."""
    feature_block_budget(8)
    model, bins, y = _wide_rehearsal()
    ruled = model("pallas")
    assert ruled._hist_blocks("pallas")["block_features"] == 88
    ens, margin = ruled.fit_binned(bins, y)
    monkeypatch.setattr(hist_pallas, "hist_feature_block", lambda f: 128)
    forced = model("pallas")
    assert forced._hist_blocks("pallas")["block_features"] == 128
    ens_128, margin_128 = forced.fit_binned(bins, y)
    for name in ("split_feat", "split_bin", "leaf_value"):
        np.testing.assert_array_equal(np.asarray(getattr(ens, name)),
                                      np.asarray(getattr(ens_128, name)))
    np.testing.assert_array_equal(np.asarray(margin), np.asarray(margin_128))


# -- the row tile: a pure function of (block features, rows a chip) ----------

K = 1024
# the six configurations of BENCHMARK.json: (features, depth, rows, data
# axis) -> the tile of a chip's calls
CELLS = {
    "higgs11m.fit": ((28, 6, 11_000_000, 1), 8 * K),
    "airline115m.fit.dp4": ((13, 6, 67_108_864, 4), 16 * K),
    "epsilon400k.fit": ((2000, 6, 400_000, 1), 2 * K),
    "bosch1m.fit": ((968, 6, 1_183_747, 1), 2 * K),
    "epsilon400k.d8.fit": ((2000, 8, 400_000, 1), 2 * K),
    "mslr30k.rank.fit": ((136, 6, 2_270_296, 1), 2 * K),
}


def _data_mesh(dp):
    import jax
    from dmlc_core_tpu.parallel.mesh import make_mesh

    return make_mesh({"data": dp}, devices=jax.devices()[:dp])


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_the_row_tile_of_every_cell(cell):
    """A step carries the row-features of a 128-feature block's step: the
    two narrow cells widen, the four others run today's 2,048 rows."""
    (features, depth, rows, dp), tile = CELLS[cell]
    with _data_mesh(dp):
        plan = hist_pallas.hist_kernel_plan(None, features, depth, 256,
                                            batch=rows, pads=True)
    assert plan["row_tile"] == tile
    assert plan["row_multiple"] == tile * dp
    block = hist_pallas.hist_block_plan(2 ** depth // 4, features, 256)[1]
    padded = -(-rows // plan["row_multiple"]) * plan["row_multiple"] // dp
    assert hist_pallas.hist_row_tile(block, padded) == tile


@pytest.mark.parametrize("features, rows, tile", [
    (28, None, 2 * K),                 # rows not known yet
    (28, 8192, 2 * K),                 # the benchmark's check, a sample fit
    (13, 8192, 2 * K),
    (28, 300, 2 * K),
    (28, 64 * 8 * K - 2 * K, 2 * K),   # 63.75 tiles of 8,192
    (28, 64 * 8 * K - 2 * K + 1, 8 * K),   # pads to 64 of them
    (28, 64 * 8 * K, 8 * K),
    (13, 64 * 16 * K - 1, 16 * K),
    (13, 64 * 8 * K, 2 * K),           # the base, not the next tile down
    (1, 10 ** 7, 16 * K),              # the cap
    (8, 10 ** 7, 16 * K),
    (16, 10 ** 7, 16 * K),
    (17, 10 ** 7, 8 * K),
    (32, 10 ** 7, 8 * K),
    (33, 10 ** 7, 4 * K),
    (64, 10 ** 7, 4 * K),
    (65, 10 ** 7, 2 * K),
    (128, 10 ** 7, 2 * K),
    (136, 10 ** 7, 2 * K),
    (2000, 10 ** 7, 2 * K),
])
def test_the_row_tile_rule(features, rows, tile):
    assert hist_pallas.hist_row_tile(features, rows) == tile
    assert tile % hist_pallas.BLOCK_ROWS == 0
    assert features * tile <= 128 * hist_pallas.BLOCK_ROWS or tile == 2 * K


@pytest.mark.parametrize("features", [4, 13, 28, 64, 128])
def test_rows_padded_to_their_tile_keep_it(features):
    """What a fit pads to is what its calls cut the padded rows by, at the
    edge of 64 tiles too."""
    wide = hist_pallas.hist_row_tile(features, 10 ** 9)
    for rows in (1, 2047, 2048, 2049, 64 * wide - 2049, 64 * wide - 2048,
                 64 * wide - 2047, 64 * wide - 1, 64 * wide, 64 * wide + 1,
                 65 * wide - 1, 10 ** 7 + 3):
        tile = hist_pallas.hist_row_tile(features, rows)
        assert tile in (wide, hist_pallas.BLOCK_ROWS)
        padded = -(-rows // tile) * tile
        assert hist_pallas.hist_row_tile(features, padded) == tile, rows
        assert padded - rows < tile and (tile == 2 * K
                                         or padded - rows <= rows / 63)


@pytest.mark.parametrize("dp", [1, 2, 8])
def test_row_multiple_is_the_tile_times_the_data_axis(dp):
    """Plain and under a data mesh: every shard a whole number of tiles.  A
    fit that pads need not bring rows that divide; a caller that does not
    pad must."""
    rows = dp * 64 * 8 * K + 1000 * dp + 1      # divides no data axis > 1
    with _data_mesh(dp):
        fit = hist_pallas.hist_kernel_plan(None, 28, 6, 256, batch=rows,
                                           pads=True)
        assert (fit["row_tile"], fit["row_multiple"]) == (8 * K, 8 * K * dp)
        assert (fit["mesh"] is None) == (dp == 1)
        small = hist_pallas.hist_kernel_plan(None, 28, 6, 256,
                                             batch=dp * 300, pads=True)
        assert small["row_multiple"] == 2 * K * dp
        unknown = hist_pallas.hist_kernel_plan(None, 28, 6, 256)
        assert (unknown["row_tile"], unknown["row_multiple"]) == \
            (2 * K, 2 * K * dp)
        if dp > 1:
            with pytest.raises(ValueError, match="rows do not divide"):
                hist_pallas.hist_kernel_plan(None, 28, 6, 256, batch=rows)
        as_they_are = hist_pallas.hist_kernel_plan(None, 28, 6, 256,
                                                   batch=rows - 1)
        assert as_they_are["row_tile"] == 8 * K


def test_the_model_axis_sees_a_chips_features():
    """56 features over a model axis of 2: a chip's block is 28 wide."""
    with _mesh_2d(data=4, model=2):
        plan = hist_pallas.hist_kernel_plan("model", 56, 6, 256,
                                            batch=4 * 64 * 8 * K)
    assert (plan["row_tile"], plan["row_multiple"]) == (8 * K, 4 * 8 * K)
    assert hist_pallas.hist_kernel_plan(None, 56, 6, 256,
                                        batch=64 * 8 * K)["row_tile"] == 4 * K


@pytest.mark.parametrize("nnodes", [1, 16])
@pytest.mark.parametrize("tile", [4 * K, 8 * K])
def test_a_wide_tile_sums_the_same_histogram(tile, nnodes):
    """A tile above the base, forced: rows that are no whole tile, node ids
    below 0 and past n (a level's sibling rows, a padded row) drop out, and
    the result is the exact histogram of the bf16-rounded g and h, as at
    the base tile."""
    rows, f = 2 * tile + 777, 3
    bins, node, g, h = _rand_case(rows, f, 256, nnodes + 2, seed=tile + nnodes)
    node = node - 1                                  # -1 .. nnodes
    bins_fm = np.ascontiguousarray(bins.T)
    wide = np.asarray(hist_pallas.hist_matmul_pallas(
        (node, g, h), bins_fm, 256, num_nodes=nnodes, block_rows=tile))
    keep = (node >= 0) & (node < nnodes)
    _assert_hist_of_rounded(
        wide.reshape(2, nnodes, f, 256), bins[keep], node[keep], g[keep],
        h[keep], nnodes, 256)
    base = np.asarray(hist_pallas.hist_matmul_pallas(
        (node, g, h), bins_fm, 256, num_nodes=nnodes,
        block_rows=hist_pallas.BLOCK_ROWS))
    np.testing.assert_allclose(wide, base, rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("features, rows, nnodes, tile", [
    (28, 11_001_856, 16, 8 * K), (28, 11_001_856, 32, 8 * K),
    (28, 11_001_856, 64, 4 * K), (28, 11_001_856, 128, 2 * K),
    (13, 16_777_216, 16, 16 * K), (13, 16_777_216, 32, 16 * K),
    (13, 16_777_216, 64, 8 * K), (13, 16_777_216, 128, 4 * K),
    (136, 2_271_232, 16, 2 * K), (136, 2_271_232, 64, 2 * K),
])
def test_a_deep_level_halves_the_tile_that_would_leave_default_vmem(
        features, rows, nnodes, tile):
    """The operands grow with a level's key rows: a widened tile is kept
    while the step stays within Mosaic's default VMEM (every level of a
    depth-6 fit, and 32 built nodes), then halved: the tiles the sweep read
    fastest at 64 and 128 built nodes (PERF.md, PR 37).  A traced shape
    only: nothing runs."""
    import jax
    import jax.numpy as jnp

    row = jax.ShapeDtypeStruct((rows,), jnp.float32)
    jaxpr = jax.make_jaxpr(
        lambda b, n, g, h: hist_pallas.grad_hist_pallas(
            b, n, g, h, nnodes, 256))(
        jax.ShapeDtypeStruct((features, rows), jnp.int32),
        jax.ShapeDtypeStruct((rows,), jnp.int32), row, row)
    (call,) = [e for e in jaxpr.jaxpr.eqns
               if e.primitive.name == "pallas_call"]
    assert rows % tile == 0
    assert call.params["grid_mapping"].grid[2] == rows // tile
    asked = call.params["compiler_params"].get("mosaic_tpu")
    if tile > hist_pallas.BLOCK_ROWS:
        assert asked is None or asked.vmem_limit_bytes is None


@pytest.fixture()
def tiles_fill_early(monkeypatch):
    """Widen the tile of a table small enough for the interpreter: two
    tiles filled, not 64, and a cap of 4,096 rows."""
    monkeypatch.setattr(hist_pallas, "_ROW_TILES_FILLED", 2)
    monkeypatch.setattr(hist_pallas, "_ROW_TILE_CAP", 4 * K)


@pytest.mark.parametrize("rows, steps", [(2 * 4 * K, 2), (9000, 3),
                                         (6000, 3)])
def test_the_call_cuts_its_rows_by_the_rule(tiles_fill_early, rows, steps):
    """``block_rows`` left out: the grid's row steps are the rule's, from
    the call's own shapes (6,000 rows pad to 6,144: under two tiles of
    4,096, so three of 2,048)."""
    import jax
    import jax.numpy as jnp

    row = jnp.zeros((rows,), jnp.float32)
    jaxpr = jax.make_jaxpr(
        lambda b, n, g, h: hist_pallas.grad_hist_pallas(b, n, g, h, 4, 16))(
            jnp.zeros((5, rows), jnp.int32), row.astype(jnp.int32), row, row)
    (call,) = [e for e in jaxpr.jaxpr.eqns
               if e.primitive.name == "pallas_call"]
    assert call.params["grid_mapping"].grid == (1, 1, steps)


@pytest.mark.parametrize("rows, tile", [(9000, 4 * K), (3000, 2 * K)])
def test_a_fit_pads_to_its_tile_and_says_so(tiles_fill_early, rows, tile):
    """``gbdt.fit.dispatch`` carries ``row_tile``; the fit pads once to it
    (no call pads again: the grid's steps are padded rows / tile) and grows
    the exact scatter fit's trees."""
    import jax
    import jax.numpy as jnp
    from dmlc_core_tpu import telemetry
    from dmlc_core_tpu.models.gbdt import GBDT, GBDTParam

    rng = np.random.RandomState(rows)
    x = rng.randn(rows, 5).astype(np.float32)
    y = (x[:, 0] + x[:, 3] * x[:, 1] > 0).astype(np.float32)

    def model(method):
        m = GBDT(GBDTParam(num_boost_round=2, max_depth=3, num_bins=16,
                           hist_method=method), num_feature=5)
        m.make_bins(x)
        return m

    kernel = model("pallas")
    bins = np.asarray(kernel.bin_features(x), np.uint8)
    plan = kernel._fit_plan(bins)
    assert (plan.row_tile, plan.row_multiple) == (tile, tile)
    was_enabled = telemetry.enabled()
    telemetry.reset()
    telemetry.enable()
    try:
        ens_p, _ = kernel.fit_binned(bins, y)
        args = [e["args"] for e in telemetry.get_tracer().events()
                if e["name"] == "gbdt.fit.dispatch"][-1]
    finally:
        telemetry.disable()
        telemetry.reset()
        if was_enabled:
            telemetry.enable()
    assert args["row_tile"] == tile and args["feature_blocks"] == 1
    assert args["block_features"] == 5
    ens_s, _ = model("scatter").fit_binned(bins, y)
    np.testing.assert_array_equal(np.asarray(ens_p.split_feat),
                                  np.asarray(ens_s.split_feat))
    np.testing.assert_array_equal(np.asarray(ens_p.split_bin),
                                  np.asarray(ens_s.split_bin))
    jaxpr = jax.make_jaxpr(kernel._build_fit(2, plan, with_eval=False))(
        jnp.asarray(bins), jnp.asarray(y), jnp.ones(rows, jnp.float32))
    padded = -(-rows // tile) * tile
    grids = _pallas_grids(jaxpr.jaxpr)
    assert grids and set(grids) == {(1, 1, padded // tile)}


def _pallas_grids(jaxpr):
    """The grid of every ``pallas_call`` of a jaxpr, nested ones too."""
    import jax

    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn.params["grid_mapping"].grid)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _pallas_grids(sub)
    return found
