"""``hist_blocked_ms_per_round``: the time a round spends in the levels
whose one ``hist_level`` call runs several node blocks, read from the
``gbdt.fit.dispatch`` span's ``level_node_blocks`` and the trace's Mosaic
calls taken program by program, in level order."""

import os

import pytest

from benchmarks.chip import harness, tracereduce
from benchmarks.chip.layer_metrics import (hist_blocked_ms_per_round,
                                           hist_ms_per_level)

SCOPED = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "testdata", "smallfit_scoped.xplane.pb.gz")
MOSAIC = ('%hist_level.{n} = f32[1,8,16]{{2,1,0}} custom-call(), '
          'custom_call_target="tpu_custom_call"')


def dispatch(**args):
    return {"name": "gbdt.fit.dispatch", "ph": "X", "ts": 0, "dur": 900,
            "args": {"rounds": 2, "method": "pallas", **args}}


def evidence(trace, spans, depth, said=None):
    return {"trace": trace, "spans": spans, "config": {"max_depth": depth},
            "say": (said.append if said is not None else None)}


def test_the_recorded_depth_6_fit_has_no_blocked_level():
    """Two fits of 2 rounds, depth 6, 32,768 x 28 rows, traced on a TPU v5
    lite (my chip run, PR 24): 24 kernel calls in two programs, no level
    of which takes a second node block.  Its program was older than the
    field: a span without it gives nothing, and says so."""
    trace = tracereduce.from_profile(tracereduce.read_profile(SCOPED))
    (chip,) = trace.chips
    groups = hist_blocked_ms_per_round.calls_by_program(chip)
    assert [len(g) for g in groups] == [12, 12]
    spans = [dispatch(level_node_blocks="1,1,1,1,1,1")] * 2
    assert hist_blocked_ms_per_round.reduce(evidence(trace, spans, 6)) == 0.0
    # were the last level of that fit blocked, the metric would be the
    # last call of each of the four rounds
    spans = [dispatch(level_node_blocks="1,1,1,1,1,2")] * 2
    last = [g[i].dur_s for g in groups for i in (5, 11)]
    assert hist_blocked_ms_per_round.reduce(evidence(trace, spans, 6)) \
        == pytest.approx(1e3 * sum(last) / 4)
    for spans, why in (([dispatch()], "carries no one level_node_blocks"),
                       ([], "no gbdt.fit.dispatch span"),
                       (None, "no span buffer"),
                       ([dispatch(level_node_blocks="1,1,2")],
                        "has 3 levels, max_depth is 6"),
                       ([dispatch(level_node_blocks="1,1,1,1,1,1"),
                         dispatch(level_node_blocks="1,1,1,1,1,2")],
                        "carries no one level_node_blocks")):
        said = []
        assert hist_blocked_ms_per_round.reduce(
            evidence(trace, spans, 6, said)) is None
        assert len(said) == 1 and why in said[0], said


def hand_made(programs, depth=4, level_ms=(1.0, 1.0, 2.0, 4.0)):
    """A chip whose trace holds ``programs``: for each, the number of
    kernel calls recorded and whether the trace's START cut it (the calls
    kept are then its last ones).  A fit is 2 rounds of ``depth`` levels;
    level ``i`` takes ``level_ms[i]``."""
    ops, modules, t = [], [], 0.0
    for count, cut_at_start in programs:
        levels = [i % depth for i in range(2 * depth)]
        levels = levels[-count:] if cut_at_start else levels[:count]
        start = t
        for level in levels:
            dur = level_ms[level] * 1e-3
            ops.append(tracereduce.parse_op(MOSAIC.format(n=len(ops)),
                                            t * 1e9, dur * 1e9))
            ops.append(tracereduce.parse_op(
                f"%fusion.{len(ops)} = f32[8]{{0}} fusion()",
                (t + dur) * 1e9, 1e5))
            t += dur + 2e-4
        modules.append(("jit_fit", start, t))
        t += 1e-3
    return tracereduce.Trace([tracereduce.ChipTrace(0, ops, [], modules)])


def test_two_blocked_levels_on_a_hand_made_trace():
    """Levels 2 and 3 of a depth-4 fit are blocked: 2 + 4 ms of a round's
    8 ms.  A program the trace's edge cut mid-round is left out, wherever
    it was cut; one cut between two rounds counts its whole round."""
    spans = [dispatch(level_node_blocks="1,1,2,4")]
    whole = hand_made([(8, False), (8, False)])
    assert hist_blocked_ms_per_round.reduce(evidence(whole, spans, 4)) \
        == pytest.approx(6.0)
    assert hist_ms_per_level.reduce({"trace": whole}) == pytest.approx(2.0)
    # the first program lost its first 3 calls to the trace's start, the
    # last its last 5 to the end: their calls would be read a level off
    cut = hand_made([(5, True), (8, False), (3, False)])
    (chip,) = cut.chips
    assert [len(g) for g in
            hist_blocked_ms_per_round.calls_by_program(chip)] == [5, 8, 3]
    assert hist_blocked_ms_per_round.reduce(evidence(cut, spans, 4)) \
        == pytest.approx(6.0)
    between = hand_made([(4, True), (8, False)])
    assert hist_blocked_ms_per_round.reduce(evidence(between, spans, 4)) \
        == pytest.approx(6.0)
    # nothing but stubs: no whole round, nothing to read
    said = []
    stubs = hand_made([(5, True), (3, False)])
    assert hist_blocked_ms_per_round.reduce(
        evidence(stubs, spans, 4, said)) is None
    assert "traced no whole round" in said[0]
    # calls under no program event at all still count, as one run
    (chip,) = whole.chips
    bare = tracereduce.Trace([tracereduce.ChipTrace(0, chip.ops, [], [])])
    assert hist_blocked_ms_per_round.reduce(evidence(bare, spans, 4)) \
        == pytest.approx(6.0)


def test_the_manifest_lists_the_reader_for_the_depth_8_cell_alone():
    manifest = harness.load_manifest()
    (entry,) = [m for m in manifest["per_layer"]
                if m["name"] == hist_blocked_ms_per_round.NAME]
    assert entry["workloads"] == ["epsilon400k.d8.fit"]
    cell, config = harness.load_cell(manifest, "epsilon400k.d8.fit")
    assert cell["kind"] in hist_blocked_ms_per_round.KINDS
    assert config["max_depth"] == 8 and config["num_feature"] == 2000
    names = {m["name"] for m in
             harness.cell_metrics(manifest, cell["name"], "per_layer")}
    # a one-chip depth-6 cell's readers, and this one
    higgs = {m["name"] for m in
             harness.cell_metrics(manifest, "higgs11m.fit", "per_layer")}
    assert names == higgs | {hist_blocked_ms_per_round.NAME}
    assert "allreduce_exposed_ms_per_round" not in names
