"""The ``fit`` traffic kind end to end at a rehearsal size, one device and
four virtual ones, with the Pallas kernel in interpret mode."""

import jax
import pytest

from benchmarks.chip.tests import rehearsal
from dmlc_core_tpu.ops import hist_pallas


@pytest.fixture()
def interpret(monkeypatch):
    monkeypatch.setattr(hist_pallas, "_INTERPRET", True)


def test_fit_cell_one_device(interpret, tmp_path):
    cell = {"name": "r.fit", "kind": "fit", "chips": 1, "rounds_per_fit": 2}
    result, lines = rehearsal.run(cell, rehearsal.config(), tmp_path,
                                  jax.devices()[:1])
    assert result["correct"], lines
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert set(result["metrics"]) == {"train_rows_per_s", "setup_s"}
    assert result["metrics"]["train_rows_per_s"]["value"] > 0
    assert result["device"]["count"] == 1


def test_fit_cell_four_virtual_devices(interpret, tmp_path):
    cell = {"name": "r.fit.dp4", "kind": "fit", "chips": 4,
            "rounds_per_fit": 2}
    cfg = rehearsal.config(mesh={"data": 4}, rows=8192)
    result, lines = rehearsal.run(cell, cfg, tmp_path, jax.devices()[:4])
    assert result["correct"], lines
    assert result["device"]["count"] == 4
    assert any("all-reduce" in line for line in lines)


def test_fit_check_catches_a_dropped_level(interpret, tmp_path):
    """The reference comparison is tight enough that a program boosting
    shallower trees than the configuration states is not ``correct``."""
    from benchmarks.chip.traffic import fit

    cell = {"name": "r.fit", "kind": "fit", "chips": 1, "rounds_per_fit": 2}
    cfg = rehearsal.config()
    ctx, _ = rehearsal.context(cell, cfg, tmp_path, jax.devices()[:1])
    shallow = fit.make_model(rehearsal.config(max_depth=2), 2)
    state = fit.setup(ctx)
    shallow.set_boundaries(state["model"].boundaries)
    state["model"] = shallow
    state["warm"] = fit._fit(state)
    window = fit.window(ctx, state, 0.0)
    failed = [what for ok, what in fit.check(ctx, state, window) if not ok]
    assert any("train logloss" in what for what in failed), failed


# -- a configuration's own model parameters ---------------------------------

@pytest.mark.parametrize("cell", ["higgs11m.fit", "airline115m.fit.dp4",
                                  "epsilon400k.fit"])
def test_accepted_configurations_get_the_param_they_always_got(cell):
    """Without a ``model`` block ``make_model`` gives the ``GBDTParam`` of
    the eight fields it always passed, field for field."""
    from benchmarks.chip import harness
    from benchmarks.chip.traffic import fit
    from dmlc_core_tpu.models.gbdt import GBDTParam

    cell, config = harness.load_cell(harness.load_manifest(), cell)
    assert "model" not in config
    rounds = cell["rounds_per_fit"]
    model = fit.make_model(config, rounds)
    before = GBDTParam(
        num_boost_round=rounds, max_depth=config["max_depth"],
        num_bins=config["num_bins"], learning_rate=config["learning_rate"],
        reg_lambda=config["reg_lambda"],
        min_child_weight=config["min_child_weight"],
        objective=config["objective"], hist_method=config["hist_method"])
    assert model.param.to_dict() == before.to_dict()
    assert model.num_feature == config["num_feature"]


def test_model_block_reaches_the_param():
    from benchmarks.chip.traffic import fit

    plain = fit.make_model(rehearsal.config(), 2).param.to_dict()
    cfg = rehearsal.config(model={"handle_missing": True, "subsample": 0.5,
                                  "seed": 3})
    got = fit.make_model(cfg, 2).param.to_dict()
    changed = {k for k in got if got[k] != plain[k]}
    assert changed == {"handle_missing", "subsample", "seed"}
    assert fit.make_model(cfg, 2).param.handle_missing is True
    # the Bosch configuration states exactly one further field
    from benchmarks.chip import harness
    _, bosch = harness.load_cell(harness.load_manifest(), "bosch1m.fit")
    assert bosch["model"] == {"handle_missing": True}


@pytest.mark.parametrize("name, says", [
    ("no_such_field", "GBDTParam has no field 'no_such_field'"),
    ("max_depth", "model.max_depth: 'max_depth' has a key of its own"),
    ("hist_method", "model.hist_method"),
    ("num_boost_round", "model.num_boost_round"),
])
def test_model_block_refuses_unknown_and_doubled_names(name, says):
    from benchmarks.chip.traffic import fit

    cfg = rehearsal.config(model={name: 3})
    with pytest.raises(ValueError, match=says):
        fit.make_model(cfg, 2)
