"""``levels.py`` and the three whole-round readers built on it
(``hist_ms_per_round``, ``hist_shallow_ms_per_round``,
``last_level_nonhist_ms_per_round``): on hand-made traces with stubs at
both edges and a program cut mid-round, and on a trace recorded on the
v5e from the program that names its levels."""

import os

import pytest

from benchmarks.chip import harness, levels, scopes, tracereduce
from benchmarks.chip.layer_metrics import (hist_blocked_ms_per_round,
                                           hist_ms_per_level,
                                           hist_ms_per_round,
                                           hist_shallow_ms_per_round,
                                           last_level_nonhist_ms_per_round)
from benchmarks.chip.tests.test_scopes import _field, _plane

READERS = (hist_ms_per_round, hist_shallow_ms_per_round,
           last_level_nonhist_ms_per_round)
RECORDED = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "testdata", "smallfit_levels.xplane.pb.gz")
FIT_CELLS = ["higgs11m.fit", "airline115m.fit.dp4", "epsilon400k.fit",
             "bosch1m.fit", "epsilon400k.d8.fit", "mslr30k.rank.fit"]

# a depth-3 fit: the kernels' names as the span gives them, the node slots
# they build, and each level's ms in the kernel and outside it by op
NAMES = ["hist_level_L0_n1", "hist_level_L1_n1", "hist_level_L2_n16"]
BUILT = "1,1,16"
KERNEL_MS = (1.0, 1.5, 4.0)
PREP_MS, MADE_MS, SPLIT_MS, ROUTE_MS = (0.1, 0.2, 0.3), 0.25, (0.5, 1.0,
                                                               2.0), 0.6
GRAD_MS, LEAF_MS, AFTER_LEAF_MS = 0.5, 1.5, 0.125
SCAN = "jit(fit)/while/body/closed_call"


def _kernel(level):
    return (f"%{NAMES[level]}.{50 + level} = f32[1,28,16,32]{{3,2,1,0}} "
            f"custom-call(%k, %g, %h, %bins), "
            f'custom_call_target="tpu_custom_call"')


def _op(kind, level):
    return f"%{kind}_fusion.{10 * level + len(kind)} = f32[8]{{0}} fusion(%p)"


def _tf_ops():
    """``{op text: tf_op}`` of the depth-3 program: every level's phases
    under its ``gbdt.level<d>``, the compiler's own op with no stat, the
    leaf and the gradient under no level."""
    out = {_op("grad", 0): f"jit(fit)/while/body/gbdt.grad_hess/mul:",
           _op("leaf", 0): f"{SCAN}/gbdt.leaf/div:",
           _op("after_leaf", 0): None}
    for d in range(3):
        at = f"{SCAN}/gbdt.level{d}"
        out[_kernel(d)] = f"{at}/gbdt.hist/{NAMES[d]}/pallas_call:"
        out[_op("prep", d)] = f"{at}/gbdt.hist/transpose:"
        out[_op("made", d)] = None
        out[_op("split", d)] = f"{at}/gbdt.split/argmax:"
        out[_op("route", d)] = f"{at}/gbdt.route/reduce_sum:"
    return out


def _round(levels_kept=(0, 1, 2), scale=1.0, stub=1.0):
    """``(text, ms)`` of one round's ops, or of the part of it whose
    levels the trace kept; a stub's kernel calls run ``stub`` times as long
    (an edge cuts what it cuts)."""
    ops = [(_op("grad", 0), GRAD_MS)] if 0 in levels_kept else []
    for d in levels_kept:
        ops += [(_op("prep", d), PREP_MS[d]),
                (_kernel(d), KERNEL_MS[d] * stub),
                (_op("made", d), MADE_MS), (_op("split", d), SPLIT_MS[d]),
                (_op("route", d), ROUTE_MS)]
    if 2 in levels_kept:
        ops += [(_op("leaf", 0), LEAF_MS),
                (_op("after_leaf", 0), AFTER_LEAF_MS)]
    return [(text, ms * scale) for text, ms in ops]


def _chip(number, programs, scale=1.0):
    """A chip whose trace holds ``programs``, each a list of rounds
    (``_round``), laid end to end with 1 ms between programs."""
    ops, modules, t = [], [], 1.0
    for rounds in programs:
        start = t
        for text, ms in (op for r in rounds for op in r):
            ops.append(tracereduce.parse_op(text, t * 1e9, ms * scale * 1e6))
            t += ms * scale * 1e-3
        modules.append(("jit_fit", start, t))
        t += 1e-3
    return tracereduce.ChipTrace(number, ops, [], modules)


def dispatch(**args):
    return {"name": "gbdt.fit.dispatch", "ph": "X", "ts": 0, "dur": 900,
            "args": {"rounds": 2, "method": "pallas", **args}}


SPANS = [dispatch(level_kernels=",".join(NAMES), built_nodes=BUILT)] * 3


def _evidence(tmp_path, chips, spans=SPANS, depth=3, said=None):
    stats = {1: "tf_op"}
    events = {n: (text, [] if tf_op is None
                  else [_field(1, 1) + _field(5, tf_op)])
              for n, (text, tf_op) in enumerate(_tf_ops().items(), 1)}
    path = tmp_path / "made.xplane.pb"
    path.write_bytes(b"".join(
        _field(1, _plane(f"/device:TPU:{c.chip}", events, stats))
        for c in chips))
    return {"trace": tracereduce.Trace(chips), "xplane": str(path),
            "spans": spans, "config": {"max_depth": depth},
            "say": said.append if said is not None else None}


# a fit is 2 rounds.  The trace's start cut the first program inside its
# first round (levels 1 and 2 left, half of the level-1 call), its end the
# last program inside its second (level 0 alone, a third of its call)
CUT = [[_round((1, 2), stub=0.5), _round()],
       [_round(), _round()],
       [_round(), _round((0,), stub=1 / 3)]]
NONHIST = [PREP_MS[d] + MADE_MS + SPLIT_MS[d] + ROUTE_MS for d in range(3)]


@pytest.mark.parametrize("tf_op, scope, level", [
    (f"{SCAN}/gbdt.level3/gbdt.route/x", "gbdt.route", 3),
    (f"{SCAN}/gbdt.level0/gbdt.hist/hist_level_L0_n1/pallas_call:",
     "gbdt.hist", 0),
    (f"{SCAN}/gbdt.level12/gbdt.split/argmax:", "gbdt.split", 12),
    (f"{SCAN}/gbdt.leaf/div:", "gbdt.leaf", None),
    (f"{SCAN}/gbdt.level/gbdt.route/x", "gbdt.level", None),
    (f"{SCAN}/my_gbdt.level3/x", None, None),
    ("bins:", None, None), ("", None, None), (None, None, None),
])
def test_a_level_is_no_phase(tf_op, scope, level):
    """``gbdt.level<d>`` does not match ``scopes.SCOPE`` (the digit sees to
    it): the per-phase readers find the phase inside it, this file the
    level round it."""
    assert scopes.scope_of(tf_op) == scope
    assert levels.level_of(tf_op) == level


def test_whole_rounds_are_found_by_name_on_a_cut_trace(tmp_path):
    """Four whole rounds among 12 + 2 + 1 calls: the cut programs' whole
    rounds are kept, the stubs left out, and the three readers read what a
    round takes; the readers that count calls read the stubs in."""
    said = []
    evidence = _evidence(tmp_path, [_chip(0, CUT)], said=said)
    (chip,) = evidence["trace"].chips
    calls = levels.mosaic_calls(chip)
    assert len(calls) == 15
    assert levels.whole_rounds(calls, NAMES) == [2, 5, 8, 11]
    assert hist_ms_per_round.reduce(evidence) == pytest.approx(6.5)
    assert hist_shallow_ms_per_round.reduce(evidence) == pytest.approx(2.5)
    assert last_level_nonhist_ms_per_round.reduce(evidence) \
        == pytest.approx(NONHIST[2])
    # the whole level table is in the run's log
    assert len(said) == 3
    assert ("chip 0 4 whole rounds in 15 Mosaic calls; kernel ms by level: "
            "hist_level_L0_n1 1.0000, hist_level_L1_n1 1.5000, "
            "hist_level_L2_n16 4.0000") in said[0]
    assert said[1].endswith("levels of 8 built nodes and fewer: "
                            "hist_level_L0_n1 1.0000, hist_level_L1_n1 "
                            "1.5000")
    assert said[2].endswith(
        "gbdt.level0 1.4500, gbdt.level1 2.0500, gbdt.level2 3.1500")
    # the mean over every traced call has the stubs in it, and the reader
    # that drops a cut program keeps the middle one alone
    assert 3 * hist_ms_per_level.reduce(evidence) != pytest.approx(6.5)
    assert [len(g) for g in hist_blocked_ms_per_round.calls_by_program(
        chip)] == [5, 6, 4]


def test_the_compilers_own_ops_follow_the_scoped_op_before_them(tmp_path):
    evidence = _evidence(tmp_path, [_chip(0, [[_round()]])])
    (chip,) = evidence["trace"].chips
    booked = {o.text: level for o, level in levels.levelled_ops(
        chip, scopes.tf_ops(evidence["xplane"])[0])}
    assert [booked[_op("made", d)] for d in range(3)] == [0, 1, 2]
    assert [booked[_kernel(d)] for d in range(3)] == [0, 1, 2]
    # after the leaf values an unscoped op is in no level, nor is the
    # gradient before the first
    assert booked[_op("after_leaf", 0)] is None
    assert booked[_op("leaf", 0)] is None and booked[_op("grad", 0)] is None


def test_an_op_the_scheduler_moved_stays_in_its_level_and_its_round(tmp_path):
    """On the chip an op of level 0 may run after level 1's kernel call,
    and one of level 1 before the round's first (a hoisted constant): its
    ``tf_op`` says where it belongs, and its round is the one it lies in."""
    moved = _round()
    late = moved.pop(moved.index((_op("split", 0), SPLIT_MS[0])))
    moved.insert(moved.index((_op("split", 1), SPLIT_MS[1])), late)
    route = moved.index((_op("route", 1), ROUTE_MS))
    moved.insert(0, moved.pop(route))
    said = []
    evidence = _evidence(tmp_path, [_chip(0, [[_round(), moved], [_round()]])],
                         said=said)
    assert last_level_nonhist_ms_per_round.reduce(evidence) \
        == pytest.approx(NONHIST[2])
    assert said[0].endswith(
        "gbdt.level0 1.4500, gbdt.level1 2.0500, gbdt.level2 3.1500")


def test_a_last_round_that_no_call_follows_is_left_out_of_the_last_level(
        tmp_path):
    """The trace may have ended inside the last level of its last whole
    round: the kernel readers keep the round, the reader of the ops
    outside the kernel does not."""
    ended = [[_round(), _round()], [_round()]]
    chip = _chip(0, ended)
    # the trace ends inside the last round's last level: its split and
    # route are gone
    gone = {_op("split", 2), _op("route", 2), _op("leaf", 0),
            _op("after_leaf", 0)}
    last = max(i for i, o in enumerate(chip.ops) if o.is_mosaic)
    chip.ops[:] = [o for i, o in enumerate(chip.ops)
                   if i <= last or o.text not in gone]
    said = []
    evidence = _evidence(tmp_path, [chip], said=said)
    assert hist_ms_per_round.reduce(evidence) == pytest.approx(6.5)
    assert last_level_nonhist_ms_per_round.reduce(evidence) \
        == pytest.approx(NONHIST[2])
    # one round alone, and nothing after it: nothing to read
    alone = _evidence(tmp_path, [_chip(0, [[_round()]])], said=said)
    assert hist_ms_per_round.reduce(alone) == pytest.approx(6.5)
    assert last_level_nonhist_ms_per_round.reduce(alone) is None
    assert "no whole round that a Mosaic call follows" in said[-1]


def test_the_mean_is_over_chips(tmp_path):
    chips = [_chip(0, CUT), _chip(1, CUT, scale=3.0)]
    evidence = _evidence(tmp_path, chips)
    assert hist_ms_per_round.reduce(evidence) == pytest.approx(2 * 6.5)
    assert hist_shallow_ms_per_round.reduce(evidence) \
        == pytest.approx(2 * 2.5)
    assert last_level_nonhist_ms_per_round.reduce(evidence) \
        == pytest.approx(2 * NONHIST[2])


def test_a_program_without_the_names_gives_nothing_and_says_why(tmp_path):
    """The parent's span has no ``level_kernels`` and its kernel is
    ``hist_level``: every reader returns ``None`` and raises nothing."""
    trace = [_chip(0, CUT)]
    for spans, why in (
            ([dispatch(built_nodes=BUILT)], "carries no one level_kernels"),
            ([], "no gbdt.fit.dispatch span"),
            (None, "no span buffer"),
            ([dispatch(level_kernels="a,b", built_nodes="1,1")],
             "has 2 levels, max_depth is 3"),
            ([dispatch(level_kernels=",".join(NAMES), built_nodes=BUILT),
              dispatch(level_kernels="a,b,c", built_nodes=BUILT)],
             "carries no one level_kernels"),
            ([dispatch(level_kernels="hist_level,hist_level,hist_level",
                       built_nodes=BUILT)], "traced no whole round")):
        for reader in READERS:
            said = []
            assert reader.reduce(_evidence(tmp_path, trace, spans,
                                           said=said)) is None
            assert len(said) == 1 and why in said[0], said
            assert said[0].startswith(reader.NAME + ": ")
    # the names without the node counts: the shallow reader alone stops
    said = []
    evidence = _evidence(tmp_path, trace,
                         [dispatch(level_kernels=",".join(NAMES))],
                         said=said)
    assert hist_shallow_ms_per_round.reduce(evidence) is None
    assert "carries no one built_nodes" in said[0]
    assert hist_ms_per_round.reduce(evidence) == pytest.approx(6.5)
    # no trace file: the kernel's readers need none, the tf_op reader does
    evidence = _evidence(tmp_path, trace, said=said)
    evidence["xplane"] = str(tmp_path / "gone.xplane.pb")
    assert hist_ms_per_round.reduce(evidence) == pytest.approx(6.5)
    assert last_level_nonhist_ms_per_round.reduce(evidence) is None
    assert "no .xplane.pb" in said[-1]


def test_the_manifest_lists_the_three_readers_for_every_fit_cell():
    manifest = harness.load_manifest()
    listed = {m["name"]: m for m in manifest["per_layer"]}
    fit_cells = [w["name"] for w in manifest["workloads"]
                 if w["traffic"] == "fit"]
    assert set(FIT_CELLS) <= set(fit_cells)
    for reader in READERS:
        entry = listed[reader.NAME]
        assert entry["workloads"] == fit_cells
        assert entry["source"] == "device_trace"
        assert entry["better"] == "lower"
        assert (entry["unit"], entry["layer"], entry["moves"]) == (
            reader.UNIT, reader.LAYER, reader.MOVES)
    # no new layer: the kernel's, and _build_tree's outside it
    assert hist_ms_per_round.LAYER == hist_ms_per_level.LAYER


def test_the_readers_on_a_recorded_trace_of_the_program_that_names_its_levels():
    """Two fits of 2 rounds, depth 6, 32,768 x 28 rows, traced on a TPU v5
    lite with telemetry on (my chip run, PR 38): the kernel's six calls a
    round carry the names the span gives, every level's ops its scope, and
    ``breakdown.device_ops`` one line a level."""
    trace = tracereduce.from_profile(tracereduce.read_profile(RECORDED))
    (chip,) = trace.chips
    maps = scopes.tf_ops(RECORDED)
    assert all(o.text in maps[0] for o in chip.ops)
    names = [f"hist_level_L{d}_n{n}"
             for d, n in enumerate((1, 1, 2, 4, 8, 16))]
    calls = levels.mosaic_calls(chip)
    # the name is the instruction's, numbered like any other, and a
    # component of its tf_op inside the level's and the phase's scopes
    assert [o.name for o in calls] == [f"{n}.8" for n in names] * 4
    assert [maps[0][o.text] for o in calls[:6]] == [
        f"{SCAN}/gbdt.level{d}/gbdt.hist/{n}/pallas_call:"
        for d, n in enumerate(names)]
    assert levels.whole_rounds(calls, names) == [0, 6, 12, 18]
    groups = [g for g, _ in trace.breakdown()["device_ops"]]
    assert {f"tpu_custom_call:{n}" for n in names} <= set(groups)
    assert "tpu_custom_call:hist_level" not in groups
    # every level has ops outside the kernel under each of its phases, and
    # what a round does once is in no level
    booked = levels.levelled_ops(chip, maps[0])
    phases = {(level, scopes.scope_of(maps[0][o.text])) for o, level in booked
              if levels.level_of(maps[0][o.text]) is not None}
    assert phases == {(d, phase) for d in range(6)
                      for phase in ("gbdt.hist", "gbdt.split", "gbdt.route")}
    assert {level for o, level in booked
            if scopes.scope_of(maps[0][o.text]) in (
                "gbdt.leaf", "gbdt.grad_hess", "gbdt.layout")} == {None}
    # the compiler's own ops (the cumsums' reduce-window lowerings carry no
    # tf_op) are booked to a level, all six of them
    windows = {level for o, level in booked if o.opcode == "reduce-window"}
    assert windows == set(range(6))
    spans = [dispatch(level_kernels=",".join(names),
                      built_nodes="1,1,2,4,8,16")] * 2
    said = []
    evidence = {"trace": trace, "xplane": RECORDED, "spans": spans,
                "config": {"max_depth": 6}, "say": said.append}
    whole = hist_ms_per_round.reduce(evidence)
    assert whole == pytest.approx(0.26782675, rel=1e-6)
    # no stub in this trace: the mean over calls agrees
    assert whole == pytest.approx(6 * hist_ms_per_level.reduce(evidence))
    assert hist_shallow_ms_per_round.reduce(evidence) \
        == pytest.approx(0.18750075, rel=1e-6)
    # of the four rounds the last is followed by no call: three are read
    assert last_level_nonhist_ms_per_round.reduce(evidence) \
        == pytest.approx(0.2259457, rel=1e-6)
    assert "hist_level_L4_n8 0.0608, hist_level_L5_n16 0.0803" in said[0]
    assert said[2].endswith(
        "gbdt.level0 0.0123, gbdt.level1 0.0221, gbdt.level2 0.0358, "
        "gbdt.level3 0.0622, gbdt.level4 0.1143, gbdt.level5 0.2259")
    # the older program's span names no kernels: nothing to read
    for reader in READERS:
        assert reader.reduce({**evidence, "spans": [dispatch(
            built_nodes="1,1,2,4,8,16")] * 2}) is None
