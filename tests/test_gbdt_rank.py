"""``objective="lambdarank"``: LambdaMART's gradient over query groups
(``models/gbdt.py``: ``_rank_layout`` once a fit, ``_lambdarank_grad_hess``
a round) against the benchmark's plain numpy reference
(``benchmarks/chip/objectives/lambdarank.py``), and ``fit_binned(group=)``
against ``reference/gbdt_hist.boost(objective="lambdarank", extras=)``.

The gradient's layout cuts the sorted rows into tiles of ``_RANK_TILE``
and carries a query's first ``k`` rows into the tiles it reaches into, so
the cases put queries on every side of those edges: sizes 1, 2, ``k``,
``k + 1``, one tile, one tile and a row, several tiles; a query of one
grade; tied margins; round 0's all-equal margins.
"""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip.objectives import lambdarank as reference
from benchmarks.chip.reference import gbdt_hist
from dmlc_core_tpu import telemetry
from dmlc_core_tpu.models import gbdt
from dmlc_core_tpu.models.gbdt import GBDT, GBDTParam
from dmlc_core_tpu.ops import hist_pallas
from dmlc_core_tpu.utils.logging import Error

K = 30
TILE = gbdt._RANK_TILE


def _groups(sizes):
    return np.repeat(np.arange(len(sizes)), sizes).astype(np.int32)


def _rows(sizes, seed, grades=5, ties=False, flat=False):
    """``(margin, grade, group)`` of seeded queries of these sizes."""
    rng = np.random.default_rng(seed)
    group = _groups(sizes)
    n = group.shape[0]
    grade = rng.integers(0, grades, n).astype(np.float32)
    margin = rng.standard_normal(n).astype(np.float32)
    if ties:                     # a leaf's rows share a margin: few values
        margin = rng.integers(0, 4, n).astype(np.float32) / 4
    if flat:
        margin = np.zeros(n, np.float32)
    return margin, grade, group


def _fresh_program(k=K):
    """The gradient of ``(margin, grade, group)``, traced anew (a test that
    patches a constant of the layout needs its own trace)."""
    return jax.jit(lambda margin, grade, group: gbdt._lambdarank_grad_hess(
        margin, gbdt._rank_layout(grade, group, k), k))


_program = functools.lru_cache(maxsize=None)(_fresh_program)


CASES = {
    "one_row": dict(sizes=[1]),
    "two_rows": dict(sizes=[2]),
    "k_rows": dict(sizes=[K]),
    "k_plus_1_rows": dict(sizes=[K + 1]),
    "300_rows": dict(sizes=[300]),
    "a_tile": dict(sizes=[TILE]),
    "a_tile_and_a_row": dict(sizes=[TILE + 1, 3, TILE - 4]),
    "first_k_cut_by_a_tile_edge": dict(sizes=[TILE - 7, 500, 2]),
    "skewed": dict(sizes=[1, 2, K, K + 1, 300, 5, 1, 1, 130, 128, 127, 700,
                          3, 1251, 1, 64]),
    "one_grade": dict(sizes=[40, 200, 7], grades=1),
    "two_grades": dict(sizes=[90, 260, 11], grades=2),
    "tied_margins": dict(sizes=[1, 50, 300, 129, 2], ties=True),
    "round_0_all_equal": dict(sizes=[1, 2, 50, 300, 129], flat=True),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_the_gradient_is_the_references(name):
    margin, grade, group = _rows(seed=len(name), **CASES[name])
    want_g, want_h = reference.grad_hess(margin, grade, group)
    g, h = _program()(margin, grade, group)
    np.testing.assert_allclose(np.asarray(g), want_g, rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(np.asarray(h), want_h, rtol=1e-5, atol=1e-7)
    if CASES[name].get("grades") == 1 or name == "one_row":
        assert not np.asarray(g).any() and not np.asarray(h).any()
    elif name != "two_rows":
        assert np.abs(want_g).max() > 1e-3 and want_h.max() > 0


def test_more_tiles_than_a_step_of_the_pair_loop_holds(monkeypatch):
    """Past ``_RANK_CHUNK`` tiles the pair blocks run chunk by chunk, and
    the last chunk is filled with rows that form no pair."""
    monkeypatch.setattr(gbdt, "_RANK_CHUNK", 4)
    rng = np.random.default_rng(11)
    sizes = rng.integers(1, 400, 30).tolist()
    margin, grade, group = _rows(sizes, seed=12)
    assert gbdt._rank_tiles(len(group)) == (-(-len(group) // TILE // 4) * 4,
                                            4)
    g, h = _fresh_program()(margin, grade, group)
    want_g, want_h = reference.grad_hess(margin, grade, group)
    np.testing.assert_allclose(np.asarray(g), want_g, rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(np.asarray(h), want_h, rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize("block", [64, 512])
def test_rows_are_sorted_block_by_block_whatever_a_querys_size(
        block, monkeypatch):
    """``_sort_in_spans`` sorts blocks of ``_RANK_SORT_BLOCK`` rows, then
    the blocks shifted by half, until the rows are in order: queries of 700
    and 1,251 rows take many passes of 64-row blocks, and come out as the
    one sort of the whole array gives them."""
    monkeypatch.setattr(gbdt, "_RANK_SORT_BLOCK", block)
    margin, grade, group = _rows(seed=5, **CASES["skewed"])
    g, h = _fresh_program()(margin, grade, group)
    want_g, want_h = _program()(margin, grade, group)     # 2,719 rows: one
    np.testing.assert_array_equal(np.asarray(g), np.asarray(want_g))
    np.testing.assert_array_equal(np.asarray(h), np.asarray(want_h))
    rng = np.random.default_rng(block)
    keys = (np.sort(rng.integers(0, 9, 1000)).astype(np.int32),
            rng.integers(-5, 5, 1000).astype(np.int32),
            rng.permutation(1000).astype(np.int32))
    payload = rng.standard_normal(1000).astype(np.float32)
    got = jax.jit(lambda k, p: gbdt._sort_in_spans(k, (p,)))(keys, payload)
    order = np.lexsort(keys[::-1])
    for have, want in zip(got, keys + (payload,)):
        np.testing.assert_array_equal(np.asarray(have), want[order])


@pytest.mark.parametrize("k", [1, 5, TILE])
def test_the_truncation_level_is_the_parameters(k, monkeypatch):
    margin, grade, group = _rows([3, 200, 2 * TILE + 9, 40], seed=k)
    monkeypatch.setattr(reference, "TRUNCATION_LEVEL", k)
    want_g, want_h = reference.grad_hess(margin, grade, group)
    g, h = _program(k)(margin, grade, group)
    np.testing.assert_allclose(np.asarray(g), want_g, rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(np.asarray(h), want_h, rtol=1e-5, atol=1e-7)


def test_moving_whole_queries_about_leaves_every_rows_gradient():
    sizes = [5, 300, 1, 131, 64, 2]
    margin, grade, group = _rows(sizes, seed=21, ties=True)
    g, h = (np.asarray(a) for a in _program()(margin, grade, group))
    order = [3, 0, 5, 1, 4, 2]
    rows = np.concatenate([np.flatnonzero(group == q) for q in order])
    moved = _groups([sizes[q] for q in order])
    g2, h2 = _program()(margin[rows], grade[rows], moved)
    np.testing.assert_allclose(np.asarray(g2), g[rows], rtol=1e-6, atol=1e-8)
    np.testing.assert_allclose(np.asarray(h2), h[rows], rtol=1e-6, atol=1e-8)
    # ids need not ascend nor be dense, only a query's rows be adjacent
    g3, _ = _program()(margin, grade, (7 - group) * 1000)
    np.testing.assert_array_equal(np.asarray(g3), g)


def test_rows_beyond_the_group_column_form_no_pair():
    """The fit's row padding: labels longer than the group column."""
    margin, grade, group = _rows([70, 190, 3], seed=31)
    g, h = _program()(margin, grade, group)
    pad = 37
    # padded rows carry grades and margins that would pair if they counted
    g2, h2 = _fresh_program()(np.pad(margin, (0, pad), constant_values=9.0),
                              np.pad(grade, (0, pad), constant_values=4.0),
                              group)
    np.testing.assert_array_equal(np.asarray(g2)[:-pad], np.asarray(g))
    np.testing.assert_array_equal(np.asarray(h2)[:-pad], np.asarray(h))
    assert not np.asarray(g2)[-pad:].any() and not np.asarray(h2)[-pad:].any()


# -- fits ---------------------------------------------------------------------

ROUNDS, DEPTH, BINS, FEATURES = 3, 4, 16, 6


def _table(rows, seed, queries=None):
    """Seeded binned rows whose grades follow a linear teacher, in seeded
    query sizes that sum to ``rows``."""
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((rows, FEATURES)).astype(np.float32)
    latent = x @ rng.standard_normal(FEATURES) + 0.5 * rng.standard_normal(
        rows)
    grade = np.digitize(latent, np.quantile(latent, [0.5, 0.8, 0.93, 0.98]))
    bins = np.stack([np.digitize(x[:, f], np.quantile(
        x[:, f], np.linspace(0, 1, BINS + 1)[1:-1])) for f in range(FEATURES)],
        axis=1).astype(np.uint8)
    cuts = np.sort(rng.choice(np.arange(1, rows), (queries or rows // 40) - 1,
                              replace=False))
    sizes = np.diff(np.r_[0, cuts, rows])
    return bins, grade.astype(np.float32), _groups(sizes)


def _model(method="scatter", **more):
    return GBDT(GBDTParam(num_boost_round=ROUNDS, max_depth=DEPTH,
                          num_bins=BINS, objective="lambdarank",
                          hist_method=method, **more), num_feature=FEATURES)


def _reference_fit(bins, grade, group):
    return gbdt_hist.boost(
        bins, grade, ROUNDS, max_depth=DEPTH, num_bins=BINS,
        learning_rate=0.3, reg_lambda=1.0, min_child_weight=1.0,
        objective="lambdarank", extras={"group": group})


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_scatter_fit_grows_the_references_trees(seed):
    """Same splits, node for node, and the compared loss to the float32
    rounding of the leaf values: the exact histogram leaves the reference
    nothing to differ by."""
    bins, grade, group = _table(3000, seed)
    trees, want = _reference_fit(bins, grade, group)
    ensemble, margin = _model().fit_binned(bins, grade, group=group)
    for i, what in enumerate(("feature", "threshold")):
        np.testing.assert_array_equal(
            np.asarray(ensemble[i]), np.stack([t[i] for t in trees]), what)
    np.testing.assert_allclose(np.asarray(margin), want, atol=1e-5)
    got = reference.loss(np.asarray(margin), grade, group)
    assert got == pytest.approx(reference.loss(want, grade, group), abs=1e-6)
    assert got < reference.learned_nothing(grade, {}, group) - 0.05


def test_kernel_fit_is_within_the_tolerance_of_the_reference(monkeypatch):
    """The Pallas kernel (interpret mode) rounds g and h to bfloat16 and may
    flip a near-tie split; a flip moves whole queries' NDCG.  The limit is
    the cell's (``check.logloss_tolerance`` of
    mslr-web30k-2.27m-x136-lambdarank), at a fortieth of its sample."""
    monkeypatch.setattr(hist_pallas, "_INTERPRET", True)
    bins, grade, group = _table(3000, 5)
    _, want = _reference_fit(bins, grade, group)
    _, margin = _model("pallas").fit_binned(bins, grade, group=group)
    assert reference.loss(np.asarray(margin), grade, group) == pytest.approx(
        reference.loss(want, grade, group), abs=0.02)


def test_padded_rows_are_inert(monkeypatch):
    """A row count off the plan's multiple grows the trees of the same rows
    at a multiple, filled by hand with weightless one-row queries."""
    monkeypatch.setattr(hist_pallas, "_INTERPRET", True)
    bins, grade, group = _table(2500, 7)
    model = _model("pallas")
    multiple = model._fit_plan(jnp.asarray(bins)).row_multiple
    fill = -len(group) % multiple
    assert multiple > 1 and fill
    ensemble, margin = model.fit_binned(bins, grade, group=group)
    rng = np.random.default_rng(8)
    by_hand = model.fit_binned(
        np.concatenate([bins, rng.integers(0, BINS, (fill, FEATURES))
                        .astype(np.uint8)]),
        np.concatenate([grade, np.full(fill, 4.0, np.float32)]),
        weight=np.r_[np.ones(len(group)), np.zeros(fill)].astype(np.float32),
        group=np.concatenate([group, group[-1] + 1 + np.arange(fill)])
        .astype(np.int32))
    for a, b in zip(ensemble[:4], by_hand[0][:4]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(margin),
                                  np.asarray(by_hand[1])[:len(group)])


def test_the_row_weight_scales_the_pairs_sums():
    """``g`` and ``h`` take the row weight after the query's pairs are
    summed, as every objective's do: doubling every weight doubles the
    sums a split is scored by and changes no split at lambda 0."""
    bins, grade, group = _table(2000, 9)
    model = _model(reg_lambda=0.0, min_child_weight=0.0)
    one, _ = model.fit_binned(bins, grade, group=group)
    two, _ = model.fit_binned(bins, grade, group=group,
                              weight=np.full(len(group), 2.0, np.float32))
    np.testing.assert_array_equal(np.asarray(one[0]), np.asarray(two[0]))
    np.testing.assert_allclose(np.asarray(one[2]), np.asarray(two[2]),
                               rtol=1e-5)


def test_a_fit_over_a_mesh_grows_the_trees_of_one_device():
    """Rows sharded over ``data``, every shard holding whole queries (and
    where it does not: the gradient is one global program, not a shard's):
    the trees of the one-device fit."""
    from dmlc_core_tpu.parallel.mesh import data_sharding, make_mesh

    rows = 4096
    bins, grade, group = _table(rows, 13)
    # whole queries a shard: move the ids' edges onto the shard edges
    for edge in range(rows // 4, rows, rows // 4):
        group[edge:] += group[edge] == group[edge - 1]
    model = _model()
    alone, margin = model.fit_binned(bins, grade, group=group)
    mesh = make_mesh({"data": 4}, devices=jax.devices()[:4])
    rows1d = data_sharding(mesh)
    rows2d = jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec(*rows1d.spec, None))
    with mesh:
        sharded, margin4 = model.fit_binned(
            jax.device_put(bins, rows2d), jax.device_put(grade, rows1d),
            group=jax.device_put(group, rows1d))
    for a, b in zip(alone[:2], sharded[:2]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_allclose(np.asarray(margin4), np.asarray(margin),
                               atol=1e-5)


# -- the contract around the gradient ------------------------------------------

def test_the_parameter_gains_one_field():
    assert GBDTParam().lambdarank_truncation_level == K == \
        reference.TRUNCATION_LEVEL
    assert [f for f in GBDTParam.__fields__ if "rank" in f] == [
        "lambdarank_truncation_level"]
    with pytest.raises(Exception, match="lambdarank_truncation_level"):
        GBDTParam(lambdarank_truncation_level=TILE + 1)


def test_lambdarank_without_a_group_fails_by_name():
    bins, grade, _ = _table(500, 1)
    with pytest.raises(Error, match=r"fit_binned\(group=\).*lambdarank.*"
                                    r"group missing"):
        _model().fit_binned(bins, grade)


@pytest.mark.parametrize("objective", ["logistic", "squared"])
def test_a_group_under_another_objective_fails_by_name(objective):
    bins, grade, group = _table(500, 1)
    model = GBDT(GBDTParam(objective=objective, hist_method="scatter"),
                 num_feature=FEATURES)
    with pytest.raises(Error, match=rf"no other objective takes one "
                                    rf"\(objective='{objective}', group "
                                    rf"given\)"):
        model.fit_binned(bins, (grade > 0).astype(np.float32), group=group)


def test_a_group_of_another_length_fails_by_name():
    bins, grade, group = _table(500, 1)
    with pytest.raises(Error, match="group has shape"):
        _model().fit_binned(bins, grade, group=group[:-1])


@pytest.mark.parametrize("entry", ["boost_round", "append_rounds",
                                   "fit_with_eval"])
def test_the_streaming_entries_refuse_lambdarank_by_name(entry):
    bins, grade, _ = _table(500, 1)
    model = _model()
    calls = {
        "boost_round": lambda: model.boost_round(
            np.zeros(500, np.float32), bins, grade, np.ones(500, np.float32)),
        "append_rounds": lambda: model.append_rounds(None, bins, grade),
        "fit_with_eval": lambda: model.fit_with_eval(bins, grade, bins,
                                                     grade)}
    with pytest.raises(Error, match=rf"{entry} takes no group column: "
                                    rf"objective='lambdarank'"):
        calls[entry]()


def test_predict_returns_the_margin():
    bins, grade, group = _table(1000, 4)
    model = _model()
    ensemble, margin = model.fit_binned(bins, grade, group=group)
    np.testing.assert_allclose(np.asarray(model.predict(ensemble, bins)),
                               np.asarray(margin), atol=1e-6)
    with pytest.raises(Error, match="classification objective"):
        model.predict_class(ensemble, bins)


def test_serving_state_round_trips_the_objectives_code():
    bins, grade, group = _table(1000, 4)
    model = _model()
    model.set_boundaries(np.zeros((FEATURES, BINS - 1), np.float32))
    ensemble, _ = model.fit_binned(bins, grade, group=group)
    state = model.serving_state(ensemble)
    assert int(state["serve_meta"][4]) == 3
    flat = {f"['{k}']": v for k, v in state.items()}
    again, trees = GBDT.from_serving_state(flat)
    assert again.param.objective == "lambdarank"
    np.testing.assert_array_equal(
        np.asarray(again.predict(trees, bins)),
        np.asarray(model.predict(ensemble, bins)))


@functools.lru_cache(maxsize=None)
def _op_names():
    bins, grade, group = _table(300, 2)
    compiled = _model()._fit_fn(ROUNDS, "scatter").lower(
        bins, grade, np.ones(300, np.float32), group=group).compile()
    return re.findall(r'op_name="([^"]*)"', compiled.as_text())


@pytest.mark.parametrize("scope", ["gbdt.rank", "gbdt.layout", "gbdt.hist",
                                   "gbdt.split", "gbdt.route", "gbdt.leaf",
                                   "gbdt.grad_hess"])
def test_the_compiled_fit_names_the_gradients_phase(scope):
    """``gbdt.rank`` a round and ``gbdt.layout`` once a fit, beside the
    six scopes of every fit; the gradient is not nested in
    ``gbdt.grad_hess``, whose reader keeps its meaning."""
    names = _op_names()
    assert any(scope in name.split("/") for name in names)
    ranked = [n.split("/") for n in names if "gbdt.rank" in n.split("/")]
    assert ranked and not any("gbdt.grad_hess" in parts for parts in ranked)
    assert any("sort" in parts[-1] for parts in ranked)


def test_the_dispatch_span_carries_the_objective():
    was_enabled = telemetry.enabled()
    telemetry.reset()
    telemetry.enable()
    try:
        bins, grade, group = _table(300, 2)
        _model().fit_binned(bins, grade, group=group)
        span = [e for e in telemetry.get_tracer().events()
                if e["name"] == "gbdt.fit.dispatch"][-1]
        assert span["args"]["objective"] == "lambdarank"
        assert span["args"]["truncation_level"] == K
    finally:
        telemetry.disable()
        telemetry.reset()
        if was_enabled:
            telemetry.enable()
