"""Time a boosting round spends outside the histogram kernel in its deepest
tree level.

What follows the nodes sits in the last levels: split scoring over ``n x F
x bins`` candidates, the relayout copies and the sibling subtraction of
the level histograms (131 / 262 MB at 2,000 features and depth 8), the
cumsums the compiler lowers itself.  The per-phase readers divide a
phase's seconds by the count of levels, a mean over work that doubles with
every level; this one reads the one level that holds half of it.

An op is in the level its ``tf_op`` names (``gbdt.level<d>``, the scope
``_build_tree`` opens round every level, outside the phase scopes).  An op
with no scope at all, which the compiler made (a cumsum's
``reduce-window`` lowering, a relayout copy, a multi-output fusion's tuple
root), is booked to the level of the scoped op before it on that chip.
Ops the program scoped outside any level (``gbdt.leaf``,
``gbdt.grad_hess``, ``gbdt.rank``, ``gbdt.layout``) are in none.

Over the whole rounds ``hist_ms_per_round`` finds by name
(``benchmarks/chip/levels.py``); an op belongs to the round it lies in
(the scheduler moves it across its neighbours' kernel calls, never out of
its round), and a chip's last whole round counts only if a Mosaic call
follows it (the trace may have ended inside its last level).  The value is the ms of the ops outside the kernel
in level ``max_depth - 1``, mean over those rounds, mean over chips; every
level's ms goes to the run's log.  ``None``, with the reason through
``evidence["say"]``, where the span carries no ``level_kernels`` or no op
a level (a program before PR 38), no whole round was traced, or there is
no trace file to read ``tf_op`` from."""

from benchmarks.chip import levels

NAME = "last_level_nonhist_ms_per_round"
UNIT = "ms"
LAYER = "models: gbdt._build_tree outside the kernel"
MOVES = "train_rows_per_s"
KINDS = ("fit",)


def reduce(evidence):
    traced = levels.traced_rounds(evidence, NAME)
    if traced is None:
        return None
    names, found = traced
    ms = levels.nonkernel_ms_by_level(evidence, found, len(names), NAME)
    if ms is None:
        return None
    say = levels.sayer(evidence)
    say(f"{NAME}: ms outside the kernel by level: "
        + levels.table([f"gbdt.level{d}" for d in range(len(ms))], ms))
    return ms[-1]
