"""The names the compiled fit carries for its own profiles: the ``gbdt.*``
``jax.named_scope``s of a boosting round, the kernels' ``name=`` and the
``gbdt.fit.dispatch`` host span (docs/observability.md, "Device scopes").

A scope is metadata: it reaches the compiled program as a component of an
instruction's ``op_name`` and a ``jax.profiler`` trace as the stat
``tf_op``; ``benchmarks/chip/scopes.py`` reads it back per phase.
"""

import functools
import re

import numpy as np
import pytest

from dmlc_core_tpu import telemetry
from dmlc_core_tpu.models.gbdt import GBDT, GBDTParam

SCOPES = ("gbdt.layout", "gbdt.hist", "gbdt.split", "gbdt.route",
          "gbdt.leaf", "gbdt.grad_hess")
OBJECTIVES = {"logistic": {}, "softmax": {"num_class": 3}}
ROWS, FEATURES, ROUNDS = 64, 3, 2


def _model(objective):
    return GBDT(GBDTParam(num_boost_round=ROUNDS, max_depth=2, num_bins=8,
                          objective=objective, hist_method="scatter",
                          **OBJECTIVES[objective]), num_feature=FEATURES)


def _data(objective, seed=0):
    rng = np.random.default_rng(seed)
    classes = OBJECTIVES[objective].get("num_class", 2)
    return (rng.integers(0, 8, (ROWS, FEATURES)).astype(np.uint8),
            rng.integers(0, classes, ROWS).astype(np.float32),
            np.ones(ROWS, np.float32))


@functools.lru_cache(maxsize=None)
def _op_name_components(objective):
    """Every path component of every ``op_name`` of the compiled fit."""
    compiled = _model(objective)._fit_fn(ROUNDS, "scatter").lower(
        *_data(objective)).compile()
    names = re.findall(r'op_name="([^"]*)"', compiled.as_text())
    assert names, "the compiled text carries no op_name metadata"
    return {part for name in names for part in name.split("/")}


@pytest.mark.parametrize("scope", SCOPES)
@pytest.mark.parametrize("objective", sorted(OBJECTIVES))
def test_compiled_fit_names_every_phase(objective, scope):
    assert scope in _op_name_components(objective)


def test_streaming_round_carries_the_same_scopes():
    model = _model("logistic")
    bins, label, weight = _data("logistic")
    compiled = model._round_fn(model._plan("scatter")).lower(
        np.zeros(ROWS, np.float32), bins, label, weight,
        np.uint32(0)).compile()
    text = compiled.as_text()
    for scope in SCOPES:
        assert f"/{scope}/" in text, scope


@pytest.mark.parametrize("nodes", [1, 32])
def test_the_hist_kernel_is_named(monkeypatch, nodes):
    """One Mosaic call, ``hist_level``, whatever split of the bin index the
    level runs: the benchmark counts rounds and levels on that name."""
    import jax
    import jax.numpy as jnp

    from dmlc_core_tpu.ops import hist_pallas

    monkeypatch.setattr(hist_pallas, "_INTERPRET", True)
    bins = jnp.zeros((FEATURES, hist_pallas.BLOCK_ROWS), jnp.int32)
    row = jnp.zeros((hist_pallas.BLOCK_ROWS,), jnp.float32)
    jaxpr = jax.make_jaxpr(
        lambda b, n, g, h: hist_pallas.grad_hist_pallas(
            b, n, g, h, nodes, 256))(bins, row.astype(jnp.int32), row, row)
    calls = [e for e in jaxpr.jaxpr.eqns
             if e.primitive.name == "pallas_call"]
    assert [c.params["name"] for c in calls] == ["hist_level"]


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


def test_fit_binned_records_one_dispatch_span_per_call(spans):
    model = _model("logistic")
    data = _data("logistic")
    for calls in (1, 2):
        model.fit_binned(*data)
        found = spans()
        assert len(found) == calls
        # scatter is no kernel: no blocks of one, but its levels build one
        # child of every pair like the kernel's
        assert found[-1]["args"] == {
            "rounds": ROUNDS, "objective": "logistic", "method": "scatter",
            "node_blocks": 0,
            "level_node_blocks": "", "feature_blocks": 0, "row_tile": 0,
            "bin_split": "", "built_nodes": "1,1"}
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

