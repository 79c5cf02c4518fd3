"""Which tree level a traced op belongs to, as the program says it.

The compiled fit names its levels twice (docs/observability.md, "Device
scopes"): every level of ``_build_tree`` runs under a ``jax.named_scope``
``gbdt.level<d>``, outside the phase scopes, so an op's ``tf_op`` reads
``.../gbdt.level3/gbdt.route/...``; and the histogram kernel's call of
level ``d`` is named for it, ``%hist_level_L3_n4.52 = ... custom-call``,
so that ``tracereduce`` groups it as ``tpu_custom_call:hist_level_L3_n4``.
The rule behind the kernel's names is the program's: its
``gbdt.fit.dispatch`` span carries ``level_kernels``, the names root
first, beside ``built_nodes``, and nothing here rebuilds them.

A **whole round** is ``max_depth`` consecutive Mosaic calls of one chip, in
time order, whose names are ``level_kernels`` in order.  Calls before the
first such run and after the last (the stubs of the programs the trace's
edges cut) are left out, so a program cut mid-round still gives its whole
rounds, and nothing is counted: a round is found, not divided out.

A program without the names (any before PR 38) has no ``level_kernels`` on
its span and no ``gbdt.level<d>`` in any ``tf_op``: every reader built on
this file then returns ``None`` and says why through ``evidence["say"]``.
Standard library, ``tracereduce`` and ``scopes`` only.
"""

from __future__ import annotations

import bisect
import re
from typing import Dict, List, Optional, Sequence, Tuple

from benchmarks.chip import scopes, tracereduce

SPAN = "gbdt.fit.dispatch"
LEVEL = re.compile(r"^gbdt\.level(\d+)$")
Op = tracereduce.Op


def sayer(evidence):
    """The run's log, or nothing where the evidence has none (a test's)."""
    return evidence.get("say") or (lambda msg: None)


def said(evidence, field: str) -> Tuple[Optional[List[str]], Optional[str]]:
    """``(entries, None)``: the one comma-joined ``field`` every
    ``gbdt.fit.dispatch`` span of the run carries, one entry a tree level;
    ``(None, why)`` where there is none to read."""
    if evidence.get("spans") is None:
        return None, "no span buffer"
    values = {e.get("args", {}).get(field) for e in evidence["spans"]
              if e["name"] == SPAN}
    if not values:
        return None, f"no {SPAN} span"
    if len(values) > 1 or not next(iter(values)):
        return None, (f"{SPAN} carries no one {field} "
                      f"(has: {sorted(map(str, values))})")
    entries = next(iter(values)).split(",")
    depth = evidence["config"]["max_depth"]
    if len(entries) != depth:
        return None, f"{field} has {len(entries)} levels, max_depth is {depth}"
    return entries, None


def level_of(tf_op: Optional[str]) -> Optional[int]:
    """The tree level an op ran in: the ``<d>`` of the first ``op_name``
    component ``gbdt.level<d>``, else ``None``."""
    for part in (tf_op or "").rstrip(":").split("/"):
        found = LEVEL.match(part)
        if found:
            return int(found.group(1))
    return None


def mosaic_calls(chip: tracereduce.ChipTrace) -> List[Op]:
    """The chip's histogram kernel calls in time order."""
    return sorted((o for o in chip.ops if o.is_mosaic),
                  key=lambda o: o.start_s)


def whole_rounds(calls: Sequence[Op], names: Sequence[str]) -> List[int]:
    """Where each whole round starts in ``calls`` (one chip's, in time
    order): the indices ``i`` at which ``calls[i:i + len(names)]`` are the
    kernels ``names`` in order, rounds never overlapping."""
    want = [f"tpu_custom_call:{name}" for name in names]
    groups = [o.group for o in calls]
    starts, i = [], 0
    while i + len(want) <= len(groups):
        if groups[i:i + len(want)] == want:
            starts.append(i)
            i += len(want)
        else:
            i += 1
    return starts


def traced_rounds(evidence, reader: str):
    """``(names, [(chip, calls, starts)])``: the span's ``level_kernels``
    and, for every chip of the trace, its Mosaic calls in time order and
    where its whole rounds start in them (:func:`whole_rounds`).  ``None``,
    with the reason said under ``reader``'s name, where the span has no
    names (the program is older than they are) or a chip traced no whole
    round."""
    say = sayer(evidence)
    names, why = said(evidence, "level_kernels")
    if names is None:
        say(f"{reader}: {why}")
        return None
    found = []
    for chip in evidence["trace"].chips:
        calls = mosaic_calls(chip)
        starts = whole_rounds(calls, names)
        if not starts:
            say(f"{reader}: chip {chip.chip} traced no whole round (no "
                f"{len(names)} consecutive Mosaic calls named "
                f"{','.join(names)} among its {len(calls)}: "
                f"{sorted({o.group for o in calls})})")
            return None
        found.append((chip, calls, starts))
    return names, found


def kernel_ms_by_level(found, depth: int) -> List[float]:
    """Milliseconds of each level's kernel call, mean over every chip's
    whole rounds, mean over chips, of what :func:`traced_rounds` found."""
    per_chip = [[1e3 * sum(calls[s + d].dur_s for s in starts) / len(starts)
                 for d in range(depth)] for _, calls, starts in found]
    return [sum(level) / len(per_chip) for level in zip(*per_chip)]


def levelled_ops(chip: tracereduce.ChipTrace,
                 tf_op_of: Dict[str, Optional[str]]
                 ) -> List[Tuple[Op, Optional[int]]]:
    """Every op of the chip in time order with the tree level it is booked
    to.  An op the program scoped (a ``gbdt.*`` component in its ``tf_op``)
    is in the level its ``gbdt.level<d>`` says, or in none (the leaf
    values, the gradient, the layout: once a round or a fit).  An op with
    no scope at all (the compiler's own: a cumsum's ``reduce-window``
    lowering, relayout copies, a multi-output fusion's tuple root) is
    booked to the level of the scoped op before it on that chip."""
    # an instruction runs once a round: its text's reading is kept
    read: Dict[str, Tuple[Optional[int], bool]] = {}
    out, before = [], None
    for op in sorted(chip.ops, key=lambda o: o.start_s):
        if op.text not in read:
            tf_op = tf_op_of.get(op.text)
            level = level_of(tf_op)
            read[op.text] = level, (level is not None
                                    or scopes.scope_of(tf_op) is not None)
        level, scoped = read[op.text]
        if scoped:
            before = level
        out.append((op, level if scoped else before))
    return out


def nonkernel_ms_by_level(evidence, found, depth: int, reader: str
                          ) -> Optional[List[float]]:
    """Milliseconds a round spends outside the kernel in each level, mean
    over every chip's whole rounds, mean over chips.  The scheduler moves
    an op across its neighbours' kernel calls but never out of its round
    (a ``while`` iteration), so an op belongs to the round it lies in, from
    the round's first kernel call to its last; between two rounds an op of
    the deepest level belongs to the round before it (its split and route
    follow its kernel call) and an op of any other level to the round
    after.  A chip's last whole round counts only if a Mosaic call follows
    it: the trace may have ended inside its last level.  ``None``, said
    under ``reader``'s name, without a trace file or where no op of the
    trace carries a level."""
    say = sayer(evidence)
    path = scopes.find_xplane(evidence)
    if path is None:
        say(f"{reader}: no .xplane.pb to read tf_op from")
        return None
    maps = scopes.tf_ops(path)
    per_chip = []
    for chip, calls, starts in found:
        ops = [(o, level) for o, level in
               levelled_ops(chip, maps.get(chip.chip, {}))
               if not o.is_mosaic and level is not None]
        if not ops:
            say(f"{reader}: no op of chip {chip.chip} ran under a "
                f"gbdt.level<d> scope")
            return None
        kept = [s for s in starts if s + depth < len(calls)]
        if not kept:
            say(f"{reader}: chip {chip.chip} traced no whole round that a "
                f"Mosaic call follows")
            return None
        at = [o.start_s for o, _ in ops]
        sums = [0.0] * depth
        for s in kept:
            first, last, after = calls[s], calls[s + depth - 1], \
                calls[s + depth]
            before = calls[s - 1].end_s if s else float("-inf")
            for o, level in ops[bisect.bisect_left(at, before):
                                bisect.bisect_left(at, after.start_s)]:
                if (first.start_s <= o.start_s if level == depth - 1
                        else o.start_s < last.end_s and level < depth):
                    sums[level] += o.dur_s
        per_chip.append([1e3 * total / len(kept) for total in sums])
    return [sum(level) / len(per_chip) for level in zip(*per_chip)]


def table(names: Sequence[str], ms: Sequence[float]) -> str:
    """``name ms, name ms, ...`` of a level table, for a run's log."""
    return ", ".join(f"{n} {v:.4f}" for n, v in zip(names, ms))
