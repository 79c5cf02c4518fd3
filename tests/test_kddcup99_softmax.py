"""``kddcup99-4.9m-x41-softmax23`` and its cell ``kddcup99.softmax.fit``:
the configuration and the cell load through the harness as ISSUE 40 names
them, and at a small size on the CPU the program's softmax fit over a
table drawn with the configuration's own columns grows the trees of the
benchmark's plain reference (``reference/gbdt_hist.py:boost(num_class=23)``).
"""

import numpy as np
import pytest

import jax.numpy as jnp

from benchmarks.chip import datagen, harness, objectives
from benchmarks.chip.reference import gbdt_hist, tree_walk
from benchmarks.chip.traffic import fit

CELL, CONFIG = "kddcup99.softmax.fit", "kddcup99-4.9m-x41-softmax23"
ROWS, SEED = 4096, 40


@pytest.fixture(scope="module")
def manifest():
    return harness.load_manifest()


@pytest.fixture(scope="module")
def loaded(manifest):
    return harness.load_cell(manifest, CELL)


def test_the_cell_loads_as_the_issue_names_it(manifest, loaded):
    cell, config = loaded
    (entry,) = [c for c in manifest["configs"] if c["name"] == CONFIG]
    assert entry["file"] == f"benchmarks/chip/configs/{CONFIG}.json"
    assert entry["reduced"] == [] and config["reduced_reason"] == {}
    assert entry["source"] == config["source"]
    assert 0 < len(config["source"]) <= 200
    assert (cell["kind"], cell["config"], cell["chips"],
            cell["rounds_per_fit"]) == ("fit", CONFIG, 1, 2)
    assert cell["rounds_per_fit_reason"]


def test_the_configuration_is_at_the_sources_sizes(loaded):
    _, config = loaded
    assert (config["rows"], config["num_feature"]) == (4_898_431, 41)
    assert config["model"] == {"num_class": 23}
    assert (config["max_depth"], config["num_bins"], config["learning_rate"],
            config["reg_lambda"], config["min_child_weight"]) == (
        6, 256, 0.3, 1.0, 1.0)
    assert config["objective"] == "softmax" and config["mesh"] is None
    assert (config["hist_method"], config["expect_hist_method"]) == (
        "auto", "pallas")
    data = config["data"]
    assert len(data["cardinality"]) == len(data["columns"]) == 41
    named = dict(zip(data["columns"], data["cardinality"]))
    assert (named["protocol_type"], named["service"], named["flag"]) == (
        3, 70, 11)
    assert named["duration"] == named["src_bytes"] == named["dst_bytes"] == 0
    assert named["count"] == named["srv_count"] == 512
    assert sum(k == 101 for k in data["cardinality"]) == 15   # the rates
    assert datagen.columns(config)[0].shape == (41,)
    for key in ("symbolic_columns", "heavy_tailed_columns",
                "integer_columns", "num_outbound_cmds", "class_shares",
                "parameters"):
        assert config["assumed"][key], key
    model = fit.make_model(config, 2)
    assert (model.param.objective, model.param.num_class) == ("softmax", 23)
    assert fit.reference_params(config)["num_class"] == 23
    assert objectives.load(config["objective"]).latents(config) == 23


def test_every_limit_of_the_check_carries_its_chip_readings(loaded):
    _, config = loaded
    for key, reason in (("hist_atol", "hist_tolerance_reason"),
                        ("logloss_tolerance", "logloss_tolerance_reason"),
                        ("full_vs_sample_band", "full_vs_sample_band_reason"),
                        ("margin_atol", "margin_atol_reason")):
        assert config["check"][key] > 0
        assert "my chip run, PR 40" in config["check"][reason], reason
    assert config["check"]["hist_rows"] == 16384


def test_the_cell_reports_the_one_chip_readers_and_its_own(manifest):
    e2e = {m["name"] for m in harness.cell_metrics(manifest, CELL,
                                                   "end_to_end")}
    assert e2e == {"train_rows_per_s", "setup_s"}
    mine = [m["name"] for m in harness.cell_metrics(manifest, CELL,
                                                    "per_layer")]
    higgs = [m["name"] for m in harness.cell_metrics(manifest, "higgs11m.fit",
                                                     "per_layer")]
    # every reader a one-chip depth-6 cell reports, and the cell's own
    assert set(mine) - set(higgs) == {"softmax_grad_ms_per_round"}
    assert set(higgs) <= set(mine)
    (own,) = [m for m in manifest["per_layer"]
              if m["name"] == "softmax_grad_ms_per_round"]
    assert own["workloads"] == [CELL] and own["moves"] == "train_rows_per_s"


@pytest.fixture(scope="module")
def small(loaded):
    """The configuration at 4,096 rows and 2 rounds, exact histograms: the
    model, its bins and labels, the program's fit, the reference's."""
    _, config = loaded
    config = {**config, "hist_method": "scatter", "bin_sample_rows": 4000}
    model = fit.make_model(config, 2)
    fit.fit_bins(config, SEED, model)
    bins, label, _, extras = datagen.device_binned(
        config, SEED, ROWS, model.boundaries, jnp.uint8)
    assert extras == {}
    bins, label = np.asarray(bins), np.asarray(label)
    trees, margin = gbdt_hist.boost(bins, label, 2,
                                    **fit.reference_params(config))
    return config, bins, label, model.fit_binned(bins, label), trees, margin


def test_the_table_has_the_sources_columns_and_every_class(small):
    config, bins, label, *_ = small
    assert bins.shape == (ROWS, 41) and bins.dtype == np.uint8
    card = np.asarray(config["data"]["cardinality"])
    distinct = np.array([len(np.unique(bins[:, f])) for f in range(41)])
    # a column of k values fills at most k bins, a normal one most of 256
    assert (distinct[card > 0] <= card[card > 0]).all()
    assert (distinct[card == 0] > 200).all()
    assert set(np.unique(label).astype(int)) <= set(range(23))
    assert len(np.unique(label)) >= 20


def test_the_program_grows_the_references_trees(small):
    """All 23 roots of round 0 are the reference's; below them the two
    sides agree in most nodes and not in all, and that is the data's doing,
    not the round's: in round 0 a class's gradient takes two values and its
    hessian one, and a third of the columns hold 2 to 11 values, so many
    candidates of a node tie EXACTLY and the last bit decides, which the
    program's float32 sibling subtraction and the reference's float64
    bincount round differently (the parent's unrolled round differs from
    the reference in the same nodes: tests/test_gbdt_softmax_round.py holds
    the new round to it bit for bit in the splits).  So the line the cell
    itself compares is held here: the loss of the two fits, inside the
    configuration's ``logloss_tolerance``; and the program's margins are
    its own trees' walk."""
    config, bins, label, (ensemble, fitted), trees, margin = small
    stack = [np.stack([t[i] for t in trees]) for i in range(4)]
    feat, thresh = (np.asarray(ensemble.split_feat),
                    np.asarray(ensemble.split_bin))
    assert feat.shape == stack[0].shape == (2, 23, 63)
    assert (stack[0] >= 0).sum() > 23 * 2 * 30        # trees that split
    assert np.array_equal(feat[0, :, 0], stack[0][0, :, 0])
    assert np.array_equal(thresh[0, :, 0], stack[1][0, :, 0])
    assert len(set(feat[0, :, 0].tolist())) > 5       # a root a class
    splits = stack[0] >= 0
    same = (feat == stack[0]) & splits
    assert same.sum() >= 0.85 * splits.sum()
    assert ((thresh == stack[1]) & same).sum() >= 0.85 * same.sum()
    assert fitted.shape == (ROWS, 23)
    walked = tree_walk.margins(bins.astype(np.int64),
                               *(np.asarray(a) for a in ensemble[:3]))
    np.testing.assert_allclose(walked, np.asarray(fitted), atol=1e-5)
    objective = objectives.load("softmax")
    program, reference = (objective.loss(np.asarray(fitted), label),
                          objective.loss(margin, label))
    assert abs(program - reference) <= config["check"]["logloss_tolerance"]
    nothing = objective.learned_nothing(label, config)
    assert nothing == pytest.approx(np.log(23))
    assert max(program, reference) < nothing - 0.5
