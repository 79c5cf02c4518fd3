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
