"""The multi-class cell arrives as files: ``kddcup99.softmax.fit`` on
``kddcup99-4.9m-x41-softmax23`` through the unchanged ``fit`` kind and the
``softmax`` objective PR 35 shipped, and one reader,
``softmax_grad_ms_per_round``, on made-up evidence.  (The configuration,
the manifest's entries and the program against the reference at a small
size are held in ``tests/test_kddcup99_softmax.py``, which the tier-1 run
counts.)"""

import jax
import pytest

from benchmarks.chip import harness, tracereduce
from benchmarks.chip.layer_metrics import (leaf_grad_ms_per_round,
                                           softmax_grad_ms_per_round)
from benchmarks.chip.tests import rehearsal
from benchmarks.chip.tests import test_scopes as made
from dmlc_core_tpu.ops import hist_pallas

CELL = "kddcup99.softmax.fit"
SOFTMAX = "%multiply_maximum_fusion.9 = (f32[23,8], f32[23,8]) fusion(%p)"
CLASSES = {"max_depth": 2, "model": {"num_class": 23}}


def _evidence(tmp_path, monkeypatch, ops, config):
    monkeypatch.setitem(made.TF_OPS, SOFTMAX,
                        "jit(fit)/while/body/closed_call/gbdt.softmax/max:")
    said = []
    evidence = dict(made._evidence(tmp_path, [made._chip(0, ops)]),
                    say=said.append)
    evidence["config"] = config
    return evidence, said


def test_the_reader_reads_a_boosting_round(tmp_path, monkeypatch):
    """``phase_ms`` divides by trees (Mosaic calls / max_depth); a boosting
    round of 23 classes grows 23 of them: 3 ms of ``gbdt.softmax`` in every
    made-up tree are 69 ms a boosting round."""
    tree = [(SOFTMAX, 3.0)] + made.ROUND
    evidence, said = _evidence(tmp_path, monkeypatch, tree + tree, CLASSES)
    assert softmax_grad_ms_per_round.reduce(evidence) == pytest.approx(
        3.0 * 23)
    assert not said
    # the scope is no part of the per-tree gradient's reader
    assert leaf_grad_ms_per_round.reduce(evidence) == pytest.approx(2.0)


def test_the_reader_returns_nothing_without_the_scope_and_says_why(
        tmp_path, monkeypatch):
    evidence, said = _evidence(tmp_path, monkeypatch, made.ROUND, CLASSES)
    assert softmax_grad_ms_per_round.reduce(evidence) is None
    assert said == ["softmax_grad_ms_per_round: no op of the trace ran "
                    "under gbdt.softmax"]
    # a per-row objective's program, recorded on the chip (PR 24)
    trace = tracereduce.from_profile(tracereduce.read_profile(made.SCOPED))
    assert softmax_grad_ms_per_round.reduce(
        {"trace": trace, "xplane": made.SCOPED, "config": {"max_depth": 6},
         "say": said.append}) is None
    assert len(said) == 2
    # and no trace at all
    assert softmax_grad_ms_per_round.reduce(
        {"trace": trace, "xplane": None, "config": CLASSES,
         "say": said.append}) is None


def test_the_reader_is_the_manifests_entry():
    (entry,) = [m for m in harness.load_manifest()["per_layer"]
                if m["name"] == softmax_grad_ms_per_round.NAME]
    assert (entry["unit"], entry["layer"], entry["moves"]) == (
        softmax_grad_ms_per_round.UNIT, softmax_grad_ms_per_round.LAYER,
        softmax_grad_ms_per_round.MOVES)
    assert entry["workloads"] == [CELL] and entry["source"] == "device_trace"
    assert "fit" in softmax_grad_ms_per_round.KINDS


def test_the_cell_reads_correct_at_a_small_size(tmp_path, monkeypatch):
    """The configuration's own columns and 23 classes at 8,192 rows and
    depth 3 through ``run_cell`` on the CPU (kernel in interpret mode)."""
    monkeypatch.setattr(hist_pallas, "_INTERPRET", True)
    cell, config = harness.load_cell(harness.load_manifest(), CELL)
    config = {**config, "rows": 8192, "max_depth": 3, "num_bins": 32,
              "hist_method": "pallas", "bin_sample_rows": 4000,
              "check": {**config["check"], "hist_rows": 2048,
                        "sample_rows": 4096, "logloss_tolerance": 0.02,
                        "full_vs_sample_band": 0.3}}
    result, lines = rehearsal.run({**cell, "name": "r.fit"}, config,
                                  tmp_path, jax.devices()[:1])
    assert result["correct"], lines
    assert any("train mlogloss" in line for line in result["compared"])
