"""``scopes.py`` and the seven readers built on it, against the trace
recorded on the v5e (PR 22: a program WITHOUT scopes, so everything in it
is unscoped), hand-made traces, and a hand-encoded xplane."""

import gzip
import os
import time

import pytest

from benchmarks.chip import scopes, tracereduce
from benchmarks.chip.layer_metrics import (fit_dispatch_ms,
                                           fit_nonhist_ms_per_round,
                                           fit_unscoped_share,
                                           hist_prep_ms_per_level,
                                           idle_attributed_share,
                                           leaf_grad_ms_per_round,
                                           route_ms_per_level,
                                           split_ms_per_level)

RECORDED = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "testdata", "smallfit.xplane.pb.gz")
PHASES = (route_ms_per_level, split_ms_per_level, hist_prep_ms_per_level,
          leaf_grad_ms_per_round)
KERNEL = ('%hist_level.{n} = f32[16,7168] custom-call(%w, %bins), '
          'custom_call_target="tpu_custom_call"')


@pytest.fixture(scope="module")
def recorded():
    return tracereduce.from_profile(tracereduce.read_profile(RECORDED))


# -- the wire walker, on the recorded trace -----------------------------------

def test_tf_ops_of_the_recorded_trace(recorded):
    maps = scopes.tf_ops(RECORDED)
    assert list(maps) == [0]                       # one device plane
    chip = recorded.chips[0]
    assert len(chip.ops) == 783                    # 784 events less `while`
    assert all(o.text in maps[0] for o in chip.ops)
    mosaic = [o for o in chip.ops if o.is_mosaic]
    assert sorted({o.name for o in mosaic}) == [
        f"closed_call.{n}" for n in range(56, 62)]
    assert [maps[0][o.text] for o in mosaic] == [
        "jit(fit)/while/body/closed_call/pallas_call:"] * 12
    (copy,) = {o.text for o in chip.ops if o.name == "copy.404"}
    assert maps[0][copy] == "bins:"
    windows = [o for o in chip.ops if o.opcode == "reduce-window"]
    assert windows and all(maps[0][o.text] is None for o in windows)
    tf_op = maps[0][next(o.text for o in chip.ops
                         if o.name == "select_reduce_fusion.13")]
    assert tf_op == "jit(fit)/while/body/closed_call/reduce_sum:"


@pytest.mark.parametrize("tf_op, scope", [
    ("jit(fit)/while/body/closed_call/gbdt.route/reduce_sum:", "gbdt.route"),
    ("jit(fit)/while/body/closed_call/gbdt.hist/shard_map/hist_level/"
     "pallas_call:", "gbdt.hist"),
    ("jit(fit)/while/body/gbdt.grad_hess/add:", "gbdt.grad_hess"),
    ("jit(fit)/while/body/closed_call/gbdt_leaf/gather", "gbdt_leaf"),
    ("jit(fit)/while/body/closed_call/gbdt.hist/gbdt.route/x:", "gbdt.hist"),
    ("jit(fit)/while/body/closed_call/reduce_sum:", None),
    ("jit(fit)/gbdt.Route/x:", None),              # not one of the program's
    ("jit(fit)/my_gbdt.route/x:", None),
    ("bins:", None),
    ("", None),
    (None, None),
])
def test_scope_of(tf_op, scope):
    assert scopes.scope_of(tf_op) == scope


def test_host_annotations_of_the_recorded_trace(recorded):
    found = scopes.host_annotations(RECORDED, {"PjitFunction(fit)", "nope"})
    assert [name for name, _, _ in found] == ["PjitFunction(fit)"] * 2
    start, end = found[0][1:]
    assert end - start == pytest.approx(726.881e-6)
    # on the device ops' time base: the call that launched the program lies
    # within a millisecond of the program's first op
    assert abs(start - recorded.chips[0].span[0]) < 1e-3
    assert scopes.host_annotations(RECORDED, set()) == []


# -- a hand-encoded xplane: str_value and ref_value ---------------------------

def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(number, value):
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint((number << 3) | 2) + _varint(len(value)) + value


def _entry(key, message):
    return _field(1, key) + _field(2, message)


def _plane(name, events, stats):
    """``events``: ``{id: (name, [XStat bytes])}``; ``stats``: ``{id: name}``."""
    body = _field(1, 7) + _field(2, name)
    for sid, sname in stats.items():
        body += _field(5, _entry(sid, _field(1, sid) + _field(2, sname)))
    for eid, (ename, estats) in events.items():
        meta = _field(1, eid) + _field(2, ename)
        for s in estats:
            meta += _field(5, s)
        body += _field(4, _entry(eid, meta))
    return body


def test_tf_ops_reads_str_and_ref_values(tmp_path):
    stats = {1: "tf_op", 2: "flops", 3: "jit(fit)/gbdt.split/argmax:"}
    events = {
        10: ("%a = fusion()", [_field(1, 2) + _field(3, 99),
                               _field(1, 1) + _field(5, "jit(fit)/x:")]),
        11: ("%b = fusion()", [_field(1, 1) + _field(7, 3)]),
        12: ("%c = reduce-window()", [_field(1, 2) + _field(3, 5)]),
        13: ("%d = copy()", []),
    }
    space = (_field(1, _plane("/host:CPU", {1: ("%a = fusion()", [])}, {}))
             + _field(1, _plane("/device:TPU:2", events, stats))
             + _field(1, _plane("/device:TPU:10", {}, stats))
             + _field(2, "an error string"))
    want = {2: {"%a = fusion()": "jit(fit)/x:",
                "%b = fusion()": "jit(fit)/gbdt.split/argmax:",
                "%c = reduce-window()": None, "%d = copy()": None},
            10: {}}
    plain = tmp_path / "t.xplane.pb"
    plain.write_bytes(space)
    assert scopes.tf_ops(str(plain)) == want
    zipped = tmp_path / "t.xplane.pb.gz"
    with gzip.open(zipped, "wb") as f:
        f.write(space)
    assert scopes.tf_ops(str(zipped)) == want


# -- where the trace is found -------------------------------------------------

def _leave_trace(tmp, run, stamp):
    folder = tmp / f"chipbench_{run}" / "trace" / "plugins" / "profile" / "t"
    folder.mkdir(parents=True)
    path = folder / "host.xplane.pb"
    path.write_bytes(b"")
    os.utime(path, (stamp, stamp))
    return str(path)


def test_find_xplane(tmp_path, monkeypatch):
    monkeypatch.setattr(scopes.tempfile, "tempdir", str(tmp_path))
    assert scopes.find_xplane({}) is None
    now = time.time()
    old = _leave_trace(tmp_path, "old", now - 60)
    new = _leave_trace(tmp_path, "new", now)
    assert scopes.find_xplane({}) == new           # the newest run's
    assert scopes.find_xplane({"xplane": old}) == old
    assert scopes.find_xplane({"xplane": str(tmp_path / "gone.pb")}) is None
    # a reader without a trace file has nothing to read and says so
    monkeypatch.setattr(scopes.tempfile, "tempdir", str(tmp_path / "empty"))
    evidence = {"trace": None, "spans": [{"name": "x", "ph": "X", "dur": 1}]}
    assert all(m.reduce(evidence) is None
               for m in PHASES + (fit_unscoped_share, idle_attributed_share))


# -- the readers, on the recorded (unscoped) trace ----------------------------

def test_readers_on_a_program_without_scopes(recorded):
    evidence = {"trace": recorded, "xplane": RECORDED, "spans": [],
                "config": {"max_depth": 6}}
    assert fit_unscoped_share.reduce(evidence) == 100.0
    assert [m.reduce(evidence) for m in PHASES] == [0.0] * 4
    # no span of the program's in the buffer: nothing to read
    assert fit_dispatch_ms.reduce(evidence) is None
    assert idle_attributed_share.reduce(evidence) is None
    assert fit_dispatch_ms.reduce({**evidence, "spans": None}) is None
    assert idle_attributed_share.reduce({**evidence, "spans": None}) is None
    # a host event that ends before the device starts covers no idle time,
    # one the trace does not hold covers none either
    spans = [{"name": "PjitFunction(fit)", "ph": "X", "dur": 1.0}]
    assert idle_attributed_share.reduce({**evidence, "spans": spans}) == 0.0
    spans = [{"name": "not.in.the.trace", "ph": "X", "dur": 1.0}]
    assert idle_attributed_share.reduce({**evidence, "spans": spans}) == 0.0


# -- the readers, on a trace of the scoped program ----------------------------

SCOPED = os.path.join(os.path.dirname(RECORDED),
                      "smallfit_scoped.xplane.pb.gz")


def test_readers_on_a_recorded_trace_of_the_scoped_program():
    """Two fits of 2 rounds, depth 6, 32,768 x 28 rows, traced on a TPU v5
    lite with telemetry on (my chip run, PR 24).  At this size the cumsums'
    ``reduce-window`` ops, which carry no scope, outweigh the per-row work:
    the unscoped share is 57% here and 11% at 11M rows."""
    trace = tracereduce.from_profile(tracereduce.read_profile(SCOPED))
    (chip,) = trace.chips
    maps = scopes.tf_ops(SCOPED)
    assert all(o.text in maps[0] for o in chip.ops)
    mosaic = [o for o in chip.ops if o.is_mosaic]
    # name="hist_level" renames the instruction and lands in its tf_op
    assert sorted({o.name for o in mosaic}) == [
        f"hist_level.{n}" for n in range(48, 54)]
    assert {maps[0][o.text] for o in mosaic} == {
        "jit(fit)/while/body/closed_call/gbdt.hist/hist_level/pallas_call:"}
    assert {s for _, s in scopes.scoped_ops(
        {"trace": trace, "xplane": SCOPED})[0]} == {
        None, "gbdt.hist", "gbdt.split", "gbdt.route", "gbdt.leaf",
        "gbdt.grad_hess"}
    spans = [{"name": "gbdt.fit.dispatch", "ph": "X", "dur": d}
             for d in (1911.22, 1799.309)]
    evidence = {"trace": trace, "xplane": SCOPED, "spans": spans,
                "config": {"max_depth": 6}}
    route = route_ms_per_level.reduce(evidence)
    split = split_ms_per_level.reduce(evidence)
    prep = hist_prep_ms_per_level.reduce(evidence)
    leaf = leaf_grad_ms_per_round.reduce(evidence)
    unscoped = fit_unscoped_share.reduce(evidence)
    assert route == pytest.approx(0.035918, rel=1e-3)
    assert split == pytest.approx(0.004601, rel=1e-3)
    assert prep == pytest.approx(0.001033, rel=1e-3)
    assert leaf == pytest.approx(0.064726, rel=1e-3)
    assert unscoped == pytest.approx(56.647, rel=1e-3)
    # the identity that ties the split to the accepted metric
    assert 6 * (route + split + prep) + leaf == pytest.approx(
        (1 - unscoped / 100) * fit_nonhist_ms_per_round.reduce(evidence),
        rel=1e-6)
    assert fit_dispatch_ms.reduce(evidence) == pytest.approx(1.8552645)
    # the span is on the host plane of the same trace, once per fit, and
    # explains idle time only once the planes' clocks are aligned
    found = scopes.host_annotations(SCOPED, {"gbdt.fit.dispatch"})
    assert len(found) == 2
    events = scopes.host_annotations(
        SCOPED, {"gbdt.fit.dispatch", scopes.LAUNCH, scopes.DONE})
    # three programs a fit here (two tiny ones 0.06 and 0.9 ms before
    # jit_fit): the first's launch lies nearest to jit_fit's start and
    # bounds the lead from there (0.342 ms, where its own start would give
    # 1.261: looser, never wrong), a tiny program's own done gives 1.559
    assert scopes.host_clock_lead(chip, events) == (
        pytest.approx(0.3420e-3, rel=1e-3), pytest.approx(1.5591e-3, rel=1e-3))
    assert scopes.idle_attributed(chip, found) < 0.5
    assert idle_attributed_share.reduce(evidence) == pytest.approx(
        55.741, rel=1e-3)


def test_report_names_the_unscoped_groups():
    text = scopes.report(RECORDED)
    assert "chip 0: busy 0.004120 s, Mosaic 0.002672 s" in text
    assert "(none)" in text and "reduce-window" in text


# -- the readers, on hand-made traces -----------------------------------------

def _chip(number, ops, scale=1.0):
    """Ops laid end to end from t=1 s, ``(text, ms)`` each; a ``None`` text
    is an idle gap of that length."""
    out, t = [], 1.0
    for text, ms in ops:
        if text is not None:
            out.append(tracereduce.parse_op(text, t * 1e9, ms * scale * 1e6))
        t += ms * scale * 1e-3
    return tracereduce.ChipTrace(number, out, [], [])


ROUTE, SPLIT, PREP, LEAF, GRAD, MADE, ONCE = (
    "%select_reduce_fusion.1 = s32[8] fusion(%p)",
    "%fusion.2 = f32[2,3,8] fusion(%p)",
    "%pad_maximum_fusion.3 = bf16[16,8] fusion(%p)",
    "%compare_reduce_fusion.4 = f32[8] fusion(%p)",
    "%select_add_fusion.5 = f32[8] fusion(%p)",
    "%reduce-window.6 = f32[2,3,8] reduce-window(%p)",
    "%convert_element_type.7 = s32[8,3] fusion(%p)")
TF_OPS = {
    ROUTE: "jit(fit)/while/body/closed_call/gbdt.route/reduce_sum:",
    SPLIT: "jit(fit)/while/body/closed_call/gbdt.split/argmax:",
    PREP: "jit(fit)/while/body/closed_call/gbdt.hist/concatenate:",
    LEAF: "jit(fit)/while/body/closed_call/gbdt.leaf/gather:",
    GRAD: "jit(fit)/while/body/gbdt.grad_hess/add:",
    MADE: None, ONCE: "jit(fit)/convert_element_type:",
    KERNEL.format(n=1): "jit(fit)/while/body/closed_call/gbdt.hist/"
                        "hist_level/pallas_call:",
    KERNEL.format(n=2): "jit(fit)/while/body/closed_call/gbdt.hist/"
                        "hist_level/pallas_call:",
}
# one round of a depth-2 tree: 2 levels (2 kernel calls)
ROUND = [(ONCE, 1.0), (GRAD, 0.5),
         (PREP, 2.0), (KERNEL.format(n=1), 10.0), (MADE, 0.25), (SPLIT, 1.0),
         (ROUTE, 4.0),
         (PREP, 3.0), (KERNEL.format(n=2), 10.0), (MADE, 0.25), (SPLIT, 2.0),
         (ROUTE, 6.0), (LEAF, 1.5)]


def _evidence(tmp_path, chips, known=None):
    """Evidence whose trace file names ``TF_OPS`` on the planes of the
    chips ``known`` (all of them unless given)."""
    stats = {1: "tf_op"}
    events = {n: (text, [] if tf_op is None
                  else [_field(1, 1) + _field(5, tf_op)])
              for n, (text, tf_op) in enumerate(TF_OPS.items(), 1)}
    path = tmp_path / "made.xplane.pb"
    path.write_bytes(b"".join(
        _field(1, _plane(f"/device:TPU:{c.chip}", events, stats))
        for c in chips if known is None or c.chip in known))
    return {"trace": tracereduce.Trace(chips), "config": {"max_depth": 2},
            "xplane": str(path), "spans": []}


def test_phase_split_of_a_hand_made_round(tmp_path):
    evidence = _evidence(tmp_path, [_chip(0, ROUND)])
    assert route_ms_per_level.reduce(evidence) == pytest.approx(5.0)
    assert split_ms_per_level.reduce(evidence) == pytest.approx(1.5)
    assert hist_prep_ms_per_level.reduce(evidence) == pytest.approx(2.5)
    assert leaf_grad_ms_per_round.reduce(evidence) == pytest.approx(2.0)
    # 1.5 ms of 21.5 ms outside the kernel carry no scope
    unscoped = fit_unscoped_share.reduce(evidence)
    assert unscoped == pytest.approx(100 * 1.5 / 21.5)
    # the identity the split must satisfy against the accepted metric
    levels = 2
    named = (levels * (5.0 + 1.5 + 2.5) + 2.0)
    assert named == pytest.approx(
        (1 - unscoped / 100) * fit_nonhist_ms_per_round.reduce(evidence))


def test_phase_split_is_the_mean_over_chips(tmp_path):
    # the second chip takes twice as long for everything, over two rounds
    chips = [_chip(0, ROUND), _chip(3, ROUND + ROUND, 2.0)]
    evidence = _evidence(tmp_path, chips)
    assert route_ms_per_level.reduce(evidence) == pytest.approx(7.5)
    assert leaf_grad_ms_per_round.reduce(evidence) == pytest.approx(3.0)
    assert fit_unscoped_share.reduce(evidence) == pytest.approx(
        100 * 1.5 / 21.5)
    # a chip whose plane the file does not hold is all unscoped
    evidence = _evidence(tmp_path, chips, known={0})
    assert fit_unscoped_share.reduce(evidence) == pytest.approx(
        50 * 1.5 / 21.5 + 50)


def test_no_kernel_call_means_nothing_to_read(tmp_path):
    evidence = _evidence(tmp_path, [_chip(0, [(ROUTE, 1.0), (SPLIT, 1.0)])])
    assert route_ms_per_level.reduce(evidence) is None
    assert leaf_grad_ms_per_round.reduce(evidence) is None
    assert fit_unscoped_share.reduce(evidence) == 0.0


def test_idle_attributed_to_a_host_annotation():
    # three ops, one 2 ms gap (t = 1.003 .. 1.005) between the 2nd and 3rd
    chip = _chip(0, [(ROUTE, 1.0), (SPLIT, 2.0), (None, 2.0), (LEAF, 1.0)])
    assert scopes.idle_intervals(chip) == [
        (pytest.approx(1.003), pytest.approx(1.005))]

    def share(*annotations):
        return scopes.idle_attributed(
            chip, [("gbdt.fit.dispatch", s, e) for s, e in annotations])

    assert share() == pytest.approx(0.0)
    assert share((1.0035, 1.0045)) == pytest.approx(0.5)   # inside the gap
    assert share((1.000, 1.004)) == pytest.approx(0.5)     # over its start
    assert share((1.0045, 1.010)) == pytest.approx(0.25)   # over its end
    assert share((0.5, 2.0)) == pytest.approx(1.0)
    assert share((1.0035, 1.004), (1.00375, 1.0045)) == pytest.approx(0.5)
    assert share((1.000, 1.003), (1.005, 1.006)) == pytest.approx(0.0)
    busy = _chip(1, [(ROUTE, 1.0), (SPLIT, 2.0)])
    assert scopes.idle_attributed(busy, [("x", 0.0, 9.0)]) is None


def test_host_clock_lead_from_launch_and_done():
    # two programs on the chip's clock: 1.000-3.000 s and 3.002-5.000 s
    chip = tracereduce.ChipTrace(0, [], [], [("jit_fit", 1.0, 3.0),
                                             ("jit_fit", 3.002, 5.0)])

    def lead(*events):
        return scopes.host_clock_lead(
            chip, [(name, t, t + 1e-4) for name, t in events])

    # launched at host 1.0010 and 3.0035, seen done at 3.0030 and 5.0024:
    # the lead is at least 1.5 ms (second launch) and at most 2.4 (second
    # done)
    both = [(scopes.LAUNCH, 1.0010), (scopes.DONE, 3.0030),
            (scopes.LAUNCH, 3.0035), (scopes.DONE, 5.0024)]
    assert lead(*both) == (pytest.approx(0.0015), pytest.approx(0.0024))
    # four chips, four launches and dones a program, whose is whose unknown:
    # the earliest launch and the latest done, looser and never wrong
    more = both + [(scopes.LAUNCH, 3.0031), (scopes.DONE, 5.0029)]
    assert lead(*more) == (pytest.approx(0.0011), pytest.approx(0.0029))
    assert lead(*[e for e in both if e[0] == scopes.LAUNCH]) is None
    assert lead(*[e for e in both if e[0] == scopes.DONE]) is None
    assert lead((scopes.LAUNCH, 2.0), (scopes.DONE, 4.0)) is None  # not near
    # bounds that cross say the events are not this chip's programs'
    assert lead((scopes.LAUNCH, 1.004), (scopes.DONE, 3.001)) is None


# modules and host events of an 8 s trace of ``epsilon400k.fit`` (my chip
# run, PR 30, seed 3000000041): fits of 1.1357 s, and the trace began 5.3 ms
# before one ended, so the next fit's launch is stamped 9 ms after the cut
# program's recorded start
EPSILON_PROGRAMS = [(0.129912, 0.135240), (0.137570, 1.273260),
                    (1.275199, 2.410891), (2.412899, 3.548565),
                    (3.550507, 4.686197), (4.688076, 5.823737),
                    (5.825994, 6.961683), (6.963790, 7.956313)]
EPSILON_HOST = [(0.137329, 0.137503, 0.138677, 0.138865),
                (1.275247, 1.275448, 1.276264, 1.276491),
                (2.412872, 2.413055, 2.413920, 2.414155),
                (3.550613, 3.550777, 3.551604, 3.551806),
                (4.688252, 4.688422, 4.689191, 4.689371),
                (5.825669, 5.825891, 5.826963, 5.827276),
                (6.963701, 6.963912, 6.964866, 6.965081)]


@pytest.mark.parametrize("cut_end_s", [None, 6.969],
                         ids=["cut_at_the_start", "cut_at_both_edges"])
def test_programs_cut_by_the_trace_edges_bound_nothing(monkeypatch,
                                                       cut_end_s):
    """Before PR 30 every launch within 10 ms of a program's start counted
    for it: the cut first program read a lead of 8.95 ms, the bounds
    crossed, and the metric was left out.  A trace that ends 5 ms into a
    fit mirrors it: the previous fit's done lies within 10 ms of the cut
    program's recorded end."""
    programs = EPSILON_PROGRAMS if cut_end_s is None else (
        EPSILON_PROGRAMS[:-1] + [(EPSILON_PROGRAMS[-1][0], cut_end_s)])
    ops = [tracereduce.parse_op(ROUTE, s * 1e9, (e - s) * 1e9)
           for s, e in programs]
    chip = tracereduce.ChipTrace(
        0, ops, [], [("jit_fit", s, e) for s, e in programs])
    host = []
    for done, opened, closed, launch in EPSILON_HOST:
        host += [(scopes.DONE, done, done + 1e-4),
                 ("gbdt.fit.dispatch", opened, closed),
                 (scopes.LAUNCH, launch, launch + 5e-5)]
    assert scopes.host_clock_lead(chip, host) == (
        pytest.approx(1.299e-3, abs=1e-6), pytest.approx(1.932e-3, abs=1e-6))
    monkeypatch.setattr(scopes, "find_xplane", lambda evidence: "a.pb")
    monkeypatch.setattr(
        scopes, "host_annotations",
        lambda path, names: [a for a in host if a[0] in names])
    said = []
    evidence = {"trace": tracereduce.Trace([chip]), "say": said.append,
                "spans": [{"name": "gbdt.fit.dispatch"}]}
    # the seven gaps between fits, 14.5 ms; the dispatch spans cover 6.5 of
    # them under either bound of the lead (the traced run itself, with the
    # microsecond gaps inside a fit in the idle time too: 44.45%)
    assert idle_attributed_share.reduce(evidence) == pytest.approx(
        44.786, abs=0.01)
    assert not said
    # a reader with nothing to read says which of its exits it took
    host[:] = [a for a in host if a[0] != scopes.DONE]
    assert idle_attributed_share.reduce(evidence) is None
    assert "do not bound" in said[-1]
    assert idle_attributed_share.reduce({**evidence, "spans": []}) is None
    assert "no span" in said[-1]


def test_a_cut_stub_shorter_than_the_lead_takes_no_done():
    """The traced run of ``bosch1m.fit`` that reported no
    ``idle_attributed_share`` (my chip run, PR 32, seed 3200000111; ms from
    the first op): the trace ended 0.03 ms into the ninth fit, and the
    eighth fit's done, 2.40 ms after its own end, lies 0.56 ms after the
    stub's.  The bounds cross with it and hold without it."""
    programs = [(0.0, 4.028), (6.277, 1135.535), (1137.801, 2267.033),
                (2269.001, 3398.236), (3400.32, 4529.548),
                (4531.517, 5660.747), (5662.862, 6792.109),
                (6794.135, 7923.38), (7925.186, 7925.215)]
    launches = [8.118, 1139.552, 2270.81, 3402.154, 4533.356, 5664.592,
                6795.955]
    dones = [6.53, 1138.149, 2269.494, 3400.764, 4532.016, 5663.274,
             6794.664, 7925.779]
    chip = tracereduce.ChipTrace(
        0, [], [], [("jit_fit", s * 1e-3, e * 1e-3) for s, e in programs])
    host = [(scopes.LAUNCH, t * 1e-3, t * 1e-3 + 5e-5) for t in launches] \
        + [(scopes.DONE, t * 1e-3, t * 1e-3 + 1e-4) for t in dones]
    assert scopes.host_clock_lead(chip, host) == (
        pytest.approx(1.841e-3, abs=1e-6), pytest.approx(2.462e-3, abs=1e-6))
    # a done that contradicts the launches in the MIDDLE of the trace is no
    # edge's doing: the events are not this chip's programs'
    dones[3] = 3398.9
    host = [(scopes.LAUNCH, t * 1e-3, t * 1e-3 + 5e-5) for t in launches] \
        + [(scopes.DONE, t * 1e-3, t * 1e-3 + 1e-4) for t in dones]
    assert scopes.host_clock_lead(chip, host) is None


def test_host_clock_lead_of_the_recorded_trace(recorded):
    events = scopes.host_annotations(RECORDED, {scopes.LAUNCH, scopes.DONE})
    least, most = scopes.host_clock_lead(recorded.chips[0], events)
    # the program's first op is stamped 1.29 ms BEFORE the runtime enqueued
    # it: the planes' clocks differ by more than a dispatch takes
    assert least == pytest.approx(1.288e-3, rel=0.01)
    assert most == pytest.approx(1.942e-3, rel=0.01)


def test_idle_attributed_share_under_a_clock_lead(tmp_path, monkeypatch):
    """The reader end to end on hand-made host events: a 1 ms dispatch span
    inside a 2 ms gap between two programs reads 50% only once the host's
    lead (2.0-2.4 ms here) is taken off."""
    gap = (3.000, 3.002)
    ops = [tracereduce.parse_op(ROUTE, 1.0e9, (gap[0] - 1.0) * 1e9),
           tracereduce.parse_op(LEAF, gap[1] * 1e9, 1e9)]
    chip = tracereduce.ChipTrace(0, ops, [], [("jit_fit", 1.0, gap[0]),
                                              ("jit_fit", gap[1], 4.002)])
    host = [(scopes.DONE, 3.0024, 3.0025),
            ("gbdt.fit.dispatch", 3.0026, 3.0036),
            (scopes.LAUNCH, 3.0040, 3.0041)]
    monkeypatch.setattr(scopes, "find_xplane", lambda evidence: "a.pb")
    monkeypatch.setattr(
        scopes, "host_annotations",
        lambda path, names: [a for a in host if a[0] in names])
    evidence = {"trace": tracereduce.Trace([chip]),
                "spans": [{"name": "gbdt.fit.dispatch"}]}
    assert scopes.host_clock_lead(chip, host) == (
        pytest.approx(0.0020), pytest.approx(0.0024))
    assert scopes.idle_attributed(chip, host[1:2]) == pytest.approx(0.0)
    assert idle_attributed_share.reduce(evidence) == pytest.approx(50.0)
    # a span the lead's bounds disagree about counts at its smaller share:
    # 3.0022-3.0032 lies wholly in the gap at 2.0 ms, 0.8 ms of it at 2.4
    host[1] = ("gbdt.fit.dispatch", 3.0022, 3.0032)
    assert idle_attributed_share.reduce(evidence) == pytest.approx(40.0)
    # without the runtime's events there is no telling: nothing to read
    host[:] = host[1:2]
    assert idle_attributed_share.reduce(evidence) is None


def test_fit_dispatch_ms_is_the_median_span():
    spans = [{"name": "gbdt.fit.dispatch", "ph": "X", "dur": d}
             for d in (400.0, 900.0, 500.0)]
    spans.append({"name": "serve.predict", "ph": "X", "dur": 9000.0})
    assert fit_dispatch_ms.reduce({"spans": spans}) == pytest.approx(0.5)
