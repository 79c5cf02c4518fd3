"""Time a boosting round spends in the tree levels whose histogram runs in
more than one node block.

A level is one ``hist_level`` call whatever its blocking (the program's
promise since PR 33): where the level's node slots times 128 features
overflow the kernel's 8 MiB accumulator, the node blocks are steps on the
outermost axis of that call's grid, and each of them re-reads the bins and
re-builds every one-hot.  The program says which levels those are: the
``gbdt.fit.dispatch`` span carries ``level_node_blocks``, one count a tree
level, root first (``"1,1,1,1,1,1,1,2"`` at 2,000 features, 256 bins and
depth 8: the last level builds 64 nodes in two blocks of 32;
``"1,1,1,1,1,1"`` in every depth-6 cell), beside ``built_nodes`` (the node
slots each level's call builds) and ``bin_split`` (the ``HxL`` split of
the bin index a block's slots run under).

The trace's Mosaic calls are taken program by program (``XLA Modules``),
in time order: call ``i`` of a fit is level ``i % max_depth``.  A program
the trace's edge cut, whose calls are no whole number of rounds, is left
out.  The value is the mean over chips of (seconds in calls of levels
whose count is over 1) / (rounds kept), in ms: 0 where the fit has no
blocked level.  ``None``, with the reason through ``evidence["say"]``,
where the span buffer is missing, the span carries no such field (a
program before PR 33), the field disagrees with the configuration's
``max_depth``, or no whole round was traced."""

NAME = "hist_blocked_ms_per_round"
UNIT = "ms"
LAYER = "ops: hist_pallas kernel, levels that take more than one node block"
MOVES = "train_rows_per_s"
KINDS = ("fit",)

SPAN, FIELD = "gbdt.fit.dispatch", "level_node_blocks"


def level_node_blocks(evidence):
    """``(counts, None)``: the span's ``level_node_blocks`` as ints, one a
    level; ``(None, why)`` where there is none to read."""
    if evidence.get("spans") is None:
        return None, "no span buffer"
    said = {e.get("args", {}).get(FIELD) for e in evidence["spans"]
            if e["name"] == SPAN}
    if not said:
        return None, f"no {SPAN} span"
    if len(said) > 1 or not next(iter(said)):
        return None, (f"{SPAN} carries no one {FIELD} "
                      f"(has: {sorted(map(str, said))})")
    steps = [int(n) for n in next(iter(said)).split(",")]
    depth = evidence["config"]["max_depth"]
    if len(steps) != depth:
        return None, f"{FIELD} has {len(steps)} levels, max_depth is {depth}"
    return steps, None


def calls_by_program(chip):
    """The chip's Mosaic calls in time order, one list a traced program
    (calls under no ``XLA Modules`` event form runs of their own)."""
    groups, last = [], object()
    for op in sorted((o for o in chip.ops if o.is_mosaic),
                     key=lambda o: o.start_s):
        inside = next((i for i, (_, start, end) in enumerate(chip.modules)
                       if start <= op.start_s <= end), None)
        if inside != last:
            groups.append([])
            last = inside
        groups[-1].append(op)
    return groups


def reduce(evidence):
    say = evidence.get("say") or (lambda msg: None)
    steps, why = level_node_blocks(evidence)
    if steps is None:
        say(f"{NAME}: {why}")
        return None
    depth = len(steps)
    values = []
    for chip in evidence["trace"].chips:
        whole = [calls for calls in calls_by_program(chip)
                 if len(calls) % depth == 0]
        rounds = sum(len(calls) for calls in whole) // depth
        if not rounds:
            say(f"{NAME}: chip {chip.chip} traced no whole round (its "
                f"Mosaic calls are no whole multiple of max_depth {depth})")
            return None
        blocked = sum(op.dur_s for calls in whole
                      for i, op in enumerate(calls) if steps[i % depth] > 1)
        values.append(blocked / rounds)
    return 1e3 * sum(values) / len(values)
