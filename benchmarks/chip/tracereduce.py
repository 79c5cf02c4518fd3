"""From a ``jax.profiler`` trace (``*.xplane.pb``) to device metrics.

What a v5e trace holds (looked at by hand on the chip, PR 22): one plane
per chip named ``/device:TPU:<n>``; its line ``XLA Modules`` has one event
per executed program (``jit_fit(<hash>)``), ``XLA Ops`` one event per HLO
op — named by the op's whole HLO text, with control-flow containers
(``while``, ``conditional``, ``call``) enclosing the events of their bodies
— and ``Async XLA Ops`` one event per asynchronous pair (``copy-start`` ..
``copy-done``, ``all-reduce-start`` ..).  A Pallas/Mosaic kernel is a
``custom-call`` whose text says ``custom_call_target="tpu_custom_call"``;
the program gives its kernels no name, so that text is the only handle.

Everything here is computed the same way for every PR: busy time is the
union of the op intervals; the traced window runs from the first event to
the end of the last on ANY plane, the host's threads included (they share
the device planes' time base), so a device that idles while the host works
is seen to idle; per-op sums leave the containers out.  Times are seconds
as measured, unrounded.
"""

from __future__ import annotations

import glob
import gzip
import os
import re
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

from benchmarks.chip import stats

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, ASYNC_LINE, MODULES_LINE = "XLA Ops", "Async XLA Ops", "XLA Modules"
CONTAINERS = frozenset({"while", "conditional", "call"})
MOSAIC_MARK = 'custom_call_target="tpu_custom_call"'
_OPCODE = re.compile(r"\s([a-z][a-z0-9_\-]*)\(")
_NAME = re.compile(r"^%?([^\s=]+)")
_SUFFIX = re.compile(r"(\.\d+)+$")


@dataclass(frozen=True)
class Op:
    name: str        # "closed_call.61"
    opcode: str      # "custom-call", "fusion", "all-reduce-start", ...
    text: str        # the event's whole name (the op's HLO text)
    start_s: float
    dur_s: float

    @property
    def end_s(self) -> float:
        return self.start_s + self.dur_s

    @property
    def is_mosaic(self) -> bool:
        return MOSAIC_MARK in self.text

    @property
    def is_all_reduce(self) -> bool:
        return self.opcode.startswith("all-reduce")

    @property
    def group(self) -> str:
        """Ops that differ only in their numeric suffix, as one name."""
        base = _SUFFIX.sub("", self.name)
        return f"tpu_custom_call:{base}" if self.is_mosaic else base


def parse_op(text: str, start_ns: float, duration_ns: float) -> Op:
    name = _NAME.match(text).group(1)
    found = _OPCODE.search(text)
    opcode = found.group(1) if found else _SUFFIX.sub("", name)
    return Op(name, opcode, text, start_ns * 1e-9, duration_ns * 1e-9)


@dataclass
class ChipTrace:
    chip: int
    ops: List[Op]                          # XLA Ops, containers left out
    async_ops: List[Op]                    # Async XLA Ops
    modules: List[Tuple[str, float, float]]  # (name, start_s, end_s)

    def intervals(self, ops=None) -> List[Tuple[float, float]]:
        return [(o.start_s, o.end_s)
                for o in (self.ops + self.async_ops if ops is None else ops)]

    @property
    def busy_s(self) -> float:
        """Seconds in which an op ran (synchronous ops; an async copy in
        flight with nothing computing is a transfer, not compute, and is
        counted through ``async_ops`` by the readers that want it)."""
        return stats.union_seconds(self.intervals(self.ops))

    @property
    def span(self) -> Optional[Tuple[float, float]]:
        every = self.intervals()
        if not every:
            return None
        return min(s for s, _ in every), max(e for _, e in every)

    def seconds_by_group(self) -> Dict[str, float]:
        out: Dict[str, float] = {}
        for o in self.ops:
            out[o.group] = out.get(o.group, 0.0) + o.dur_s
        return out

    def gaps(self) -> List[Tuple[str, float]]:
        """Idle gaps between ops, longest first, each named by the program
        that runs next.  The host's spans are on another clock, so what the
        host did meanwhile is unattributed."""
        ordered = sorted(self.intervals(self.ops))
        out, end = [], None
        for s, e in ordered:
            if end is not None and s > end:
                inside = next((m for m, ms, me in self.modules
                               if ms <= end and s <= me), None)
                nxt = next((m for m, ms, _ in self.modules if ms >= end),
                           None)
                what = "unattributed"
                if inside is not None:
                    what += f", inside {inside}"
                elif nxt is not None:
                    what += f", before {nxt}"
                out.append((what, s - end))
            end = e if end is None else max(end, e)
        return sorted(out, key=lambda g: -g[1])


class Trace:
    """Every chip's ops of one traced window."""

    def __init__(self, chips: List[ChipTrace],
                 extent: Optional[Tuple[float, float]] = None):
        if not chips:
            raise ValueError("the trace holds no device plane with an op: "
                             "nothing ran on the device while it was "
                             "recorded")
        self.chips = chips
        spans = [c.span for c in chips] + ([extent] if extent else [])
        self.window_s = (max(e for _, e in spans) - min(s for s, _ in spans))
        self.busy_s = sum(c.busy_s for c in chips) / len(chips)

    @property
    def worst(self) -> ChipTrace:
        """The chip that was busy least (idle most)."""
        return min(self.chips, key=lambda c: c.busy_s)

    def breakdown(self, top: int = 10) -> dict:
        groups: Dict[str, float] = {}
        for c in self.chips:
            for g, s in c.seconds_by_group().items():
                groups[g] = groups.get(g, 0.0) + s / len(self.chips)
        ops = sorted(groups.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[g, s] for g, s in ops],
                "idle_gaps": [[w, s] for w, s in self.worst.gaps()[:top]]}


def from_profile(profile, chips: Optional[int] = None) -> Trace:
    """Reduce a ``jax.profiler.ProfileData`` to a :class:`Trace`.  With
    ``chips``, only the first that many device planes that ran something
    are kept (a one-chip cell on a four-chip host uses one)."""
    found, first, last = [], None, None
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            for line in plane.lines:
                for e in line.events:
                    end = e.start_ns + e.duration_ns
                    first = e.start_ns if first is None else min(first,
                                                                 e.start_ns)
                    last = end if last is None else max(last, end)
            continue
        ops, async_ops, modules = [], [], []
        for line in plane.lines:
            if line.name == MODULES_LINE:
                modules = [(e.name.split("(")[0], e.start_ns * 1e-9,
                            (e.start_ns + e.duration_ns) * 1e-9)
                           for e in line.events]
            elif line.name in (OPS_LINE, ASYNC_LINE):
                parsed = [parse_op(e.name, e.start_ns, e.duration_ns)
                          for e in line.events]
                if line.name == OPS_LINE:
                    ops = [o for o in parsed if o.opcode not in CONTAINERS]
                else:
                    async_ops = parsed
        if ops:
            found.append(ChipTrace(int(m.group(1)), ops, async_ops,
                                   sorted(modules, key=lambda t: t[1])))
    found.sort(key=lambda c: c.chip)
    extent = None if first is None else (first * 1e-9, last * 1e-9)
    return Trace(found[:chips] if chips else found, extent)


def read_profile(path: str):
    """``ProfileData`` of an ``.xplane.pb`` (or ``.xplane.pb.gz``) file."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def load(trace_dir: str, chips: Optional[int] = None) -> Trace:
    """The trace ``jax.profiler.start_trace(trace_dir)`` left behind."""
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return from_profile(read_profile(paths[-1]), chips)
