"""What the program names in a ``jax.profiler`` trace, read back.

The program opens a ``jax.named_scope`` round each phase of a boosting
round (``gbdt.hist`` / ``gbdt.split`` / ``gbdt.route`` / ``gbdt.leaf`` /
``gbdt.grad_hess``) and a ``jax.profiler.TraceAnnotation`` for every
enabled telemetry span.  Neither changes an op's name: on a v5e trace an
``XLA Ops`` event is named by its HLO text without metadata, so
``tracereduce.Op`` never sees a scope.  The scope reaches the trace as the
stat ``tf_op`` on the event's *metadata* (``XEventMetadata.stats``), e.g.
``jit(fit)/while/body/closed_call/gbdt.route/reduce_sum:``; a fusion
carries the ``op_name`` of its root, and ops the TPU compiler makes itself
(``reduce-window`` from a cumsum, ``AllocateBuffer``, async copies) carry
none.  ``jax.profiler.ProfileData`` does not expose metadata stats and no
``xplane_pb2`` is importable without TensorFlow, so :func:`tf_ops` walks
the protobuf wire format itself.  Field numbers, from
``tsl/profiler/protobuf/xplane.proto``::

    XSpace.planes=1
    XPlane.name=2 .lines=3 .event_metadata=4 .stat_metadata=5   (maps:
        key=1, value=2)
    XEventMetadata.id=1 .name=2 .stats=5
    XStatMetadata.id=1 .name=2
    XStat.metadata_id=1 .str_value=5 .ref_value=7   (a ref_value points at
        a stat_metadata entry whose name is the string)

The map is joined to ``tracereduce``'s own ops by ``Op.text``, so the
durations, the chips kept and the containers left out are exactly
``tracereduce``'s and the per-phase numbers reconcile with
``fit_nonhist_ms_per_round``.  Standard library and ``tracereduce`` only.

``python -m benchmarks.chip.scopes <file.xplane.pb[.gz]>`` prints the
device time by scope and the unscoped ops by group.
"""

from __future__ import annotations

import glob
import gzip
import os
import re
import sys
import tempfile
from typing import Dict, Iterable, Iterator, List, Optional, Tuple

from benchmarks.chip import stats, tracereduce
from benchmarks.chip.layer_metrics import kernel_seconds, rounds_traced

SCOPE = re.compile(r"^gbdt[._][a-z_]+$")
TF_OP = "tf_op"
# the TPU runtime's own host events round one program: its enqueue, and the
# host seeing it done
LAUNCH, DONE = "DoEnqueueProgram", "tpu::System::Execute=>Done"
NEAR_S = 0.010
_VARINT, _FIXED64, _BYTES, _FIXED32 = 0, 1, 2, 5


def find_xplane(evidence) -> Optional[str]:
    """The traced run's ``.xplane.pb``: ``evidence["xplane"]`` where a
    harness (or a test, with the recorded trace) provides it, else the
    newest trace under a ``chipbench_*`` scratch directory of ``run.py``,
    which still holds it while the readers run.  ``None`` if there is
    none: every reader then has nothing to read."""
    given = evidence.get("xplane")
    if given:
        return given if os.path.isfile(given) else None
    paths = glob.glob(os.path.join(
        tempfile.gettempdir(), "chipbench_*", "trace", "plugins", "profile",
        "*", "*.xplane.pb"))
    return max(paths, key=os.path.getmtime) if paths else None


def _fields(buf: bytes) -> Iterator[Tuple[int, int, object]]:
    """``(field number, wire type, value)`` of one serialized message; a
    length-delimited value is its bytes, a varint an int."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == _VARINT:
            value, i = _varint(buf, i)
        elif kind == _BYTES:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif kind == _FIXED64:
            value, i = buf[i:i + 8], i + 8
        elif kind == _FIXED32:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {kind} at byte {i}: not an xplane")
        yield key >> 3, kind, value


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _map_values(plane: bytes, field: int) -> Iterator[bytes]:
    """The values of a ``map<int64, Message>`` field of a plane."""
    for number, kind, entry in _fields(plane):
        if number == field and kind == _BYTES:
            for key, _, value in _fields(entry):
                if key == 2:
                    yield value


def _plane_tf_ops(plane: bytes) -> Dict[str, Optional[str]]:
    stat_names: Dict[int, str] = {}
    for meta in _map_values(plane, 5):
        got = {n: v for n, _, v in _fields(meta) if n in (1, 2)}
        stat_names[got.get(1, 0)] = got.get(2, b"").decode("utf-8", "replace")
    wanted = {i for i, name in stat_names.items() if name == TF_OP}
    out: Dict[str, Optional[str]] = {}
    for meta in _map_values(plane, 4):
        name, tf_op = None, None
        for number, _, value in _fields(meta):
            if number == 2:
                name = value.decode("utf-8", "replace")
            elif number == 5:
                stat = {n: v for n, _, v in _fields(value) if n in (1, 5, 7)}
                if stat.get(1) not in wanted:
                    continue
                if 5 in stat:
                    tf_op = stat[5].decode("utf-8", "replace")
                elif 7 in stat:
                    tf_op = stat_names.get(stat[7])
        if name is not None:
            out[name] = tf_op
    return out


def _read_bytes(path: str) -> bytes:
    with (gzip.open if path.endswith(".gz") else open)(path, "rb") as f:
        return f.read()


def tf_ops(path: str) -> Dict[int, Dict[str, Optional[str]]]:
    """``{chip: {event-metadata name (the op's HLO text): tf_op}}`` of every
    ``/device:TPU:<chip>`` plane of an ``.xplane.pb`` (``.gz`` accepted).
    Every op of the plane is a key; one without the stat maps to ``None``."""
    out: Dict[int, Dict[str, Optional[str]]] = {}
    for number, kind, plane in _fields(_read_bytes(path)):
        if number != 1 or kind != _BYTES:
            continue
        name = next((v for n, _, v in _fields(plane) if n == 2), b"")
        m = tracereduce.DEVICE_PLANE.match(name.decode("utf-8", "replace"))
        if m:
            out[int(m.group(1))] = _plane_tf_ops(plane)
    return out


def scope_of(tf_op: Optional[str]) -> Optional[str]:
    """The program's scope an op ran under: the first ``op_name`` path
    component that looks like ``gbdt.<phase>``, else ``None``."""
    if not tf_op:
        return None
    for part in tf_op.rstrip(":").split("/"):
        if SCOPE.match(part):
            return part
    return None


def host_annotations(path: str, names: Iterable[str]
                     ) -> List[Tuple[str, float, float]]:
    """``(name, start_s, end_s)`` of every event called one of ``names`` on
    a plane that is not a device's (``/host:CPU``: one line per thread),
    in seconds as ``tracereduce`` gives the device ops — of the host's
    clock, which is not the devices' (:func:`host_clock_lead`)."""
    names = set(names)
    out = []
    for plane in tracereduce.read_profile(path).planes:
        if tracereduce.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in names:
                    out.append((e.name, e.start_ns * 1e-9,
                                (e.start_ns + e.duration_ns) * 1e-9))
    return sorted(out, key=lambda a: a[1])


def scoped_ops(evidence) -> Optional[List[List[Tuple[tracereduce.Op,
                                                      Optional[str]]]]]:
    """For each chip of ``evidence["trace"]``, its ops outside the Mosaic
    kernel, each with the scope it ran under; ``None`` without a trace
    file."""
    path = find_xplane(evidence)
    if path is None:
        return None
    maps = tf_ops(path)
    return [[(o, scope_of(maps.get(chip.chip, {}).get(o.text)))
             for o in chip.ops if not o.is_mosaic]
            for chip in evidence["trace"].chips]


def phase_ms(evidence, scopes, per: str) -> Optional[float]:
    """Milliseconds the ops scoped one of ``scopes`` took outside the
    Mosaic kernel, per tree ``"level"`` or per ``"round"`` (counted from
    the kernel's calls, as ``layer_metrics.rounds_traced`` does), mean over
    chips; ``None`` where there is nothing to read."""
    chips = scoped_ops(evidence)
    if chips is None:
        return None
    values = []
    for chip, ops in zip(evidence["trace"].chips, chips):
        count = (len(kernel_seconds(chip)) if per == "level"
                 else rounds_traced(evidence, chip))
        if not count:
            return None
        values.append(sum(o.dur_s for o, s in ops if s in scopes) / count)
    return 1e3 * sum(values) / len(values)


def idle_intervals(chip: tracereduce.ChipTrace) -> List[Tuple[float, float]]:
    """The gaps between a chip's ops, from its first op to its last."""
    out, end = [], None
    for s, e in sorted(chip.intervals(chip.ops)):
        if end is not None and s > end:
            out.append((end, s))
        end = e if end is None else max(end, e)
    return out


def _nearest(times, marks) -> Dict[int, List[float]]:
    """``{index of a mark: the times that lie nearest to it}``, of those
    within ``NEAR_S`` of it."""
    out: Dict[int, List[float]] = {}
    for t in times:
        i = min(range(len(marks)), key=lambda j: abs(marks[j] - t))
        if abs(marks[i] - t) < NEAR_S:
            out.setdefault(i, []).append(t)
    return out


def host_clock_lead(chip: tracereduce.ChipTrace, host_events
                    ) -> Optional[Tuple[float, float]]:
    """``(least, most)`` seconds by which the host plane's clock runs ahead
    of a chip's plane, from the trace's own causality.

    The two are NOT one time base to the precision an idle gap needs: on
    the v5e the device plane's stamps lie 1.9-2.6 ms before the host's (my
    chip run, PR 24: a fit's first op is stamped 0.5 ms BEFORE the host
    saw the previous fit end), more than the 2 ms gap between two fits.
    But a program cannot start before the runtime enqueued it
    (``DoEnqueueProgram``) nor end after the runtime saw it done
    (``tpu::System::Execute=>Done``): each program of the chip's ``XLA
    Modules`` line bounds the lead from one side.  With several chips the
    host's events do not say whose they are, so of the launches within 10
    ms of a program's start the earliest is taken, and of the dones round
    its end the latest: looser, never wrong.  An event counts only for the
    program whose start (end) it lies NEAREST to: the trace's edges cut
    the first and the last program, whose recorded start (end) is then the
    trace's and whose own launch (done) the trace does not hold, and the
    next program's launch, 9 ms after the trace began 5 ms before a fit's
    end, is not the cut one's (my chip run, PR 30: what kept the metric
    out of every traced run of ``epsilon400k.fit``).  Nearest is not
    enough where the cut stub is shorter than the lead: a trace that ended
    0.03 ms into a fit gave that stub the previous fit's done, 0.56 ms
    after its recorded end where every other program read 2.46-2.61, and
    the bounds crossed (my chip run, PR 32: one traced run of
    ``bosch1m.fit`` in four).  So bounds that cross are taken again
    without the first program's launch and the last program's done, the
    two an edge can have cut.  ``None`` where either side has no event or
    the bounds still cross."""
    if not chip.modules:
        return None
    starts = [start for _, start, _ in chip.modules]
    ends = [end for _, _, end in chip.modules]
    launches = [s for n, s, _ in host_events if n == LAUNCH]
    dones = [s for n, s, _ in host_events if n == DONE]
    least = {i: min(q) - starts[i]
             for i, q in _nearest(launches, starts).items()}
    most = {i: max(d) - ends[i] for i, d in _nearest(dones, ends).items()}

    def bounds():
        if not least or not most or \
                max(least.values()) > min(most.values()):
            return None
        return max(least.values()), min(most.values())

    found = bounds()
    if found is None:
        least.pop(0, None)
        most.pop(len(ends) - 1, None)
        found = bounds()
    return found


def idle_attributed(chip: tracereduce.ChipTrace, annotations,
                    lead: float = 0.0) -> Optional[float]:
    """Share (0..1) of a chip's idle seconds between its first and last op
    during which one of ``annotations`` (``(name, start_s, end_s)`` on the
    host's clock, ``lead`` seconds ahead of the chip's) was open on the
    host; ``None`` for a chip that never idled."""
    idle = idle_intervals(chip)
    total = stats.union_seconds(idle)
    if not total:
        return None
    open_s = [(s - lead, e - lead) for _, s, e in annotations]
    return 1.0 - stats.uncovered(idle, open_s) / total


def report(path: str, top: int = 12) -> str:
    """Device seconds by scope and the unscoped ops by group, per chip."""
    trace = tracereduce.from_profile(tracereduce.read_profile(path))
    lines = []
    for chip, ops in zip(trace.chips,
                         scoped_ops({"trace": trace, "xplane": path})):
        by_scope: Dict[str, float] = {}
        unscoped: Dict[str, float] = {}
        for o, s in ops:
            by_scope[s or "(none)"] = by_scope.get(s or "(none)", 0) + o.dur_s
            if s is None:
                unscoped[o.group] = unscoped.get(o.group, 0) + o.dur_s
        mosaic = sum(o.dur_s for o in chip.ops if o.is_mosaic)
        lines.append(f"chip {chip.chip}: busy {chip.busy_s:.6f} s, Mosaic "
                     f"{mosaic:.6f} s, outside it by scope:")
        lines += [f"  {s:<16} {t:.6f} s" for s, t in
                  sorted(by_scope.items(), key=lambda kv: -kv[1])]
        lines.append("  unscoped, by group:")
        lines += [f"    {g:<32} {t:.6f} s" for g, t in
                  sorted(unscoped.items(), key=lambda kv: -kv[1])[:top]]
    return "\n".join(lines)


if __name__ == "__main__":
    print(report(sys.argv[1]))
