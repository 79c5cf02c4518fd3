"""The names the compiled fit carries for its own profiles: the ``gbdt.*``
``jax.named_scope``s of a boosting round, the ``gbdt.level<d>`` scope round
every tree level, the kernels' ``name=`` and the ``gbdt.fit.dispatch`` host
span (docs/observability.md, "Device scopes").

A scope is metadata: it reaches the compiled program as a component of an
instruction's ``op_name`` and a ``jax.profiler`` trace as the stat
``tf_op``; ``benchmarks/chip/scopes.py`` reads it back per phase and
``benchmarks/chip/levels.py`` per level.
"""

import functools
import re

import numpy as np
import pytest

from dmlc_core_tpu import telemetry
from dmlc_core_tpu.models.gbdt import GBDT, GBDTParam

SCOPES = ("gbdt.layout", "gbdt.hist", "gbdt.split", "gbdt.route",
          "gbdt.leaf", "gbdt.grad_hess")
LEVEL_PHASES = ("gbdt.hist", "gbdt.split", "gbdt.route")
OBJECTIVES = {"logistic": {}, "softmax": {"num_class": 3}}
ROWS, FEATURES, ROUNDS, DEPTH = 64, 3, 2, 2
LEVEL = re.compile(r"^gbdt\.level(\d+)$")


def _model(objective):
    return GBDT(GBDTParam(num_boost_round=ROUNDS, max_depth=DEPTH, num_bins=8,
                          objective=objective, hist_method="scatter",
                          **OBJECTIVES[objective]), num_feature=FEATURES)


def _data(objective, seed=0):
    rng = np.random.default_rng(seed)
    classes = OBJECTIVES[objective].get("num_class", 2)
    return (rng.integers(0, 8, (ROWS, FEATURES)).astype(np.uint8),
            rng.integers(0, classes, ROWS).astype(np.float32),
            np.ones(ROWS, np.float32))


def _op_names(compiled):
    """Every ``op_name`` of a compiled program, as its path components."""
    names = re.findall(r'op_name="([^"]*)"', compiled.as_text())
    assert names, "the compiled text carries no op_name metadata"
    return [name.split("/") for name in names]


@functools.lru_cache(maxsize=None)
def _fit_op_names(objective):
    return _op_names(_model(objective)._fit_fn(ROUNDS, "scatter").lower(
        *_data(objective)).compile())


@functools.lru_cache(maxsize=None)
def _streamed_op_names():
    model = _model("logistic")
    bins, label, weight = _data("logistic")
    return _op_names(model._round_fn(model._plan("scatter")).lower(
        np.zeros(ROWS, np.float32), bins, label, weight,
        np.uint32(0)).compile())


def _components(paths):
    return {part for path in paths for part in path}


@pytest.mark.parametrize("scope", SCOPES)
@pytest.mark.parametrize("objective", sorted(OBJECTIVES))
def test_compiled_fit_names_every_phase(objective, scope):
    assert scope in _components(_fit_op_names(objective))


def test_streaming_round_carries_the_same_scopes():
    found = _components(_streamed_op_names())
    for scope in SCOPES:
        assert scope in found, scope


@functools.lru_cache(maxsize=None)
def _streamed_softmax_op_names():
    model = _model("softmax")
    bins, label, weight = _data("softmax")
    return _op_names(model._round_fn(model._plan("scatter")).lower(
        np.zeros((ROWS, 3), np.float32), bins, label, weight,
        np.uint32(0)).compile())


@pytest.mark.parametrize("program", ["fit", "streamed"])
def test_a_softmax_program_names_its_class_axis(program):
    """``gbdt.softmax``: what a boosting round does once over the class
    axis (the ``[K, rows]`` gradient, the margin's update, the
    transpositions to and from ``[rows, K]``), through the whole fit and
    the streamed round.  The name is a phase to the benchmark's readers
    (``scopes.SCOPE`` matches it) and is nested in no other phase."""
    from benchmarks.chip import scopes

    paths = (_fit_op_names("softmax") if program == "fit"
             else _streamed_softmax_op_names())
    under = [path for path in paths if "gbdt.softmax" in path]
    assert under
    for path in under:
        assert scopes.scope_of("/".join(path)) == "gbdt.softmax", path
        assert not any(LEVEL.match(part) for part in path), path
    # the gradient, the margin's update and the transposition, by the
    # primitive that ends each path
    said = {path[-1].rstrip(":") for path in under}
    assert said & {"exp", "div", "reduce_sum", "reduce_max"}, said
    assert said & {"dynamic_update_slice", "add"}, said
    assert "transpose" in said, said


@pytest.mark.parametrize("program", ["fit", "streamed"])
def test_no_per_row_program_holds_the_softmax_scope(program):
    paths = (_fit_op_names("logistic") if program == "fit"
             else _streamed_op_names())
    assert "gbdt.softmax" not in _components(paths)


def test_a_softmax_round_is_one_traced_tree(monkeypatch):
    """The K trees of a round are one body scanned over the class axis:
    the fit's jaxpr holds one tree's kernels whatever K is."""
    import jax
    import jax.numpy as jnp

    from dmlc_core_tpu.ops import hist_pallas

    monkeypatch.setattr(hist_pallas, "_INTERPRET", True)
    rows, depth = hist_pallas.BLOCK_ROWS, 3
    counts = []
    for classes in (3, 23):
        model = GBDT(GBDTParam(num_boost_round=2, max_depth=depth,
                               num_bins=16, objective="softmax",
                               num_class=classes, hist_method="pallas"),
                     num_feature=FEATURES)
        plan = model._plan("pallas", rows=rows, pads=True)
        jaxpr = jax.make_jaxpr(model._build_fit(2, plan, with_eval=False))(
            jnp.zeros((rows, FEATURES), jnp.uint8), jnp.zeros(rows),
            jnp.ones(rows))
        counts.append(len(re.findall(r"\bname=hist_level\w*", str(jaxpr))))
    assert counts == [depth, depth]


@pytest.mark.parametrize("depth", range(DEPTH))
@pytest.mark.parametrize("program", sorted(OBJECTIVES) + ["streamed"])
def test_compiled_fit_names_every_level(program, depth):
    """``gbdt.level<d>`` for every level of ``_build_tree``, through the
    whole fit of both objectives and through the streamed round."""
    paths = (_streamed_op_names() if program == "streamed"
             else _fit_op_names(program))
    assert f"gbdt.level{depth}" in _components(paths)
    assert f"gbdt.level{DEPTH}" not in _components(paths)


@pytest.mark.parametrize("program", sorted(OBJECTIVES) + ["streamed"])
def test_a_phase_sits_inside_its_level_and_the_leaf_in_none(program):
    """``.../gbdt.level1/gbdt.route/...``: the level scope is outside the
    phase's, every op of a level's three phases is in exactly one level,
    and what a round does once (leaf values, the gradient, the layout) in
    none.  A level is no phase: the benchmark's ``scopes.SCOPE`` does not
    match it, so its per-phase readers read what they read."""
    from benchmarks.chip import scopes

    paths = (_streamed_op_names() if program == "streamed"
             else _fit_op_names(program))
    seen = set()
    for path in paths:
        levels = [i for i, part in enumerate(path) if LEVEL.match(part)]
        phases = [i for i, part in enumerate(path) if part in SCOPES]
        if not phases:
            assert not levels, path
            continue
        phase = path[phases[0]]
        if phase in LEVEL_PHASES:
            assert len(levels) == 1 and levels[0] == phases[0] - 1, path
            seen.add((path[levels[0]], phase))
        else:
            assert not levels, path
        assert scopes.scope_of("/".join(path)) == phase
    assert seen == {(f"gbdt.level{d}", phase) for d in range(DEPTH)
                    for phase in LEVEL_PHASES}


def _kernel_calls(monkeypatch, nodes, **label):
    """The ``pallas_call`` equations of one ``grad_hist_pallas`` call."""
    import jax
    import jax.numpy as jnp

    from dmlc_core_tpu.ops import hist_pallas

    monkeypatch.setattr(hist_pallas, "_INTERPRET", True)
    bins = jnp.zeros((FEATURES, hist_pallas.BLOCK_ROWS), jnp.int32)
    row = jnp.zeros((hist_pallas.BLOCK_ROWS,), jnp.float32)
    jaxpr = jax.make_jaxpr(
        lambda b, n, g, h: hist_pallas.grad_hist_pallas(
            b, n, g, h, nodes, 256, **label))(
                bins, row.astype(jnp.int32), row, row)
    return [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "pallas_call"]


@pytest.mark.parametrize("nodes", [1, 32])
def test_the_hist_kernel_is_named(monkeypatch, nodes):
    """One Mosaic call whatever split of the bin index the level runs,
    named ``hist_level`` + the level where the caller has one + the node
    slots it builds: static, and with no ``.<digits>`` ending, the one
    suffix the benchmark's ``tracereduce`` strips to group an op's copies.
    The benchmark counts rounds and levels on these names."""
    from benchmarks.chip import tracereduce

    for label, name in (({}, f"hist_level_n{nodes}"),
                        ({"level": 5}, f"hist_level_L5_n{nodes}")):
        calls = _kernel_calls(monkeypatch, nodes, **label)
        assert [c.params["name"] for c in calls] == [name]
        assert tracereduce._SUFFIX.sub("", name) == name
        assert tracereduce._SUFFIX.sub("", name + ".52") == name


def test_the_level_label_changes_the_name_and_nothing_else(monkeypatch):
    """Two calls that differ only in the level label are one kernel: every
    parameter of the ``pallas_call`` but ``name`` is equal."""
    def shown(value):
        # the kernel's own jaxpr and the grid mapping compare by identity
        return value if isinstance(value, (str, int, bool, tuple,
                                           type(None))) else str(value)

    first, second = (_kernel_calls(monkeypatch, 8, level=level)[0].params
                     for level in (3, 4))
    assert set(first) == set(second)
    assert (first["name"], second["name"]) == ("hist_level_L3_n8",
                                               "hist_level_L4_n8")
    differ = {key for key in first
              if shown(first[key]) != shown(second[key])}
    assert differ == {"name"} or differ == {"name", "name_and_src_info"}


def test_the_jaxpr_names_its_kernels_as_the_span_says(monkeypatch, spans):
    """The ``pallas_call`` names of a fit's jaxpr, root first, are the
    ``level_kernels`` its ``gbdt.fit.dispatch`` span carries: a trace's
    reader joins the two and never rebuilds the rule."""
    import jax
    import jax.numpy as jnp

    from dmlc_core_tpu.ops import hist_pallas

    monkeypatch.setattr(hist_pallas, "_INTERPRET", True)
    rows, depth = hist_pallas.BLOCK_ROWS, 4
    model = GBDT(GBDTParam(num_boost_round=1, max_depth=depth, num_bins=16,
                           hist_method="pallas"), num_feature=FEATURES)
    bins = np.zeros((rows, FEATURES), np.uint8)
    model.fit_binned(bins, np.zeros(rows, np.float32))
    said = spans()[-1]["args"]["level_kernels"].split(",")
    plan = model._fit_plan(jnp.asarray(bins))
    jaxpr = jax.make_jaxpr(model._build_fit(1, plan, with_eval=False))(
        jnp.asarray(bins), jnp.zeros(rows), jnp.ones(rows))
    # the printed jaxpr shows every pallas_call's name, the scan's too
    assert re.findall(r"\bname=(hist_level\w*)", str(jaxpr)) == said == [
        "hist_level_L0_n1", "hist_level_L1_n1", "hist_level_L2_n2",
        "hist_level_L3_n4"]


@pytest.fixture
def spans():
    was_enabled = telemetry.enabled()
    telemetry.reset()
    telemetry.enable()
    yield lambda: [e for e in telemetry.get_tracer().events()
                   if e["name"] == "gbdt.fit.dispatch"]
    telemetry.disable()
    telemetry.reset()
    if was_enabled:
        telemetry.enable()


@pytest.mark.parametrize("objective", sorted(OBJECTIVES))
def test_fit_binned_records_one_dispatch_span_per_call(spans, objective):
    """``num_class`` and ``trees_per_round`` beside ``rounds``: what turns
    the trees a trace holds into boosting rounds (1 for every objective
    but softmax, whose round grows a tree a class)."""
    model = _model(objective)
    data = _data(objective)
    classes = OBJECTIVES[objective].get("num_class", 1)
    for calls in (1, 2):
        model.fit_binned(*data)
        found = spans()
        assert len(found) == calls
        # scatter is no kernel: no blocks of one, but its levels build one
        # child of every pair like the kernel's
        assert found[-1]["args"] == {
            "rounds": ROUNDS, "objective": objective, "method": "scatter",
            "num_class": classes, "trees_per_round": classes,
            "level_node_blocks": "", "feature_blocks": 0,
            "block_features": 0, "row_tile": 0,
            "bin_split": "", "built_nodes": "1,1", "level_kernels": ""}
        assert found[-1]["ph"] == "X" and found[-1]["dur"] > 0


def test_fit_binned_records_nothing_when_telemetry_is_off():
    # off by this test's own hand: another file on the same xdist worker
    # may have left it on
    was_enabled = telemetry.enabled()
    telemetry.disable()
    try:
        before = len(telemetry.get_tracer().events())
        _model("logistic").fit_binned(*_data("logistic"))
        assert len(telemetry.get_tracer().events()) == before
    finally:
        if was_enabled:
            telemetry.enable()

