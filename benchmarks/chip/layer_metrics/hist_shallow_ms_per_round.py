"""Time a boosting round spends in the histogram kernel's calls of 8 built
nodes and fewer: the calls below the MXU's peak.

From 16 built nodes up a call's product fills 128 x 128 tiles and runs at
92-99% of the bf16 peak; the calls of 1, 1, 2, 4 and 8 built nodes give a
128-wide MXU a product 32 to 96 wide and run at 22-66% (PERF.md section
5).  They are the same five calls at any depth, so this is the part of the
kernel a change to the shallow levels moves, apart from the deep levels'.

Whole rounds only, found by name as ``hist_ms_per_round`` finds them
(``benchmarks/chip/levels.py``: the ``gbdt.fit.dispatch`` span's
``level_kernels``); which levels are shallow is the span's
``built_nodes``, one count a level.  The value is the sum of those levels'
ms, mean over a chip's whole rounds, mean over chips.  ``None``, with the
reason through ``evidence["say"]``, where the span carries neither field
(a program before PR 38) or no whole round was traced."""

from benchmarks.chip import levels

NAME = "hist_shallow_ms_per_round"
UNIT = "ms"
LAYER = "ops: hist_pallas kernel"
MOVES = "train_rows_per_s"
KINDS = ("fit",)

# node slots a level's call builds, at and under which it counts as shallow
SHALLOW = 8


def reduce(evidence):
    say = levels.sayer(evidence)
    traced = levels.traced_rounds(evidence, NAME)
    if traced is None:
        return None
    names, found = traced
    built, why = levels.said(evidence, "built_nodes")
    if built is None:
        say(f"{NAME}: {why}")
        return None
    ms = levels.kernel_ms_by_level(found, len(names))
    shallow = [d for d, nodes in enumerate(built) if int(nodes) <= SHALLOW]
    say(f"{NAME}: levels of {SHALLOW} built nodes and fewer: "
        + levels.table([names[d] for d in shallow], [ms[d] for d in shallow]))
    return sum(ms[d] for d in shallow)
