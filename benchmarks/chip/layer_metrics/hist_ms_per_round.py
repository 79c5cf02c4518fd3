"""Time a boosting round spends in the histogram kernel's calls, over the
whole rounds of the trace alone.

The whole-round twin of ``max_depth x hist_ms_per_level``: that reader
takes the mean over every traced call, the stubs of the programs the
trace's edges cut included, and so moves with where the edges fall (3% at
depth 8, where the last call is 43% of a round).  This one finds a round
by name (``benchmarks/chip/levels.py``): the ``gbdt.fit.dispatch`` span's
``level_kernels`` says what the kernel's call of each level is called
(``hist_level_L0_n1`` ... ``hist_level_L5_n16``), a round is ``max_depth``
consecutive Mosaic calls of a chip that carry those names in order, and
calls before the first such run and after the last are left out.

The value is the mean over chips of the mean over a chip's whole rounds of
the seconds in the round's calls, in ms.  Every level's own ms goes to the
run's log.  ``None``, with the reason through ``evidence["say"]``, where
the span carries no ``level_kernels`` (a program before PR 38) or no whole
round was traced."""

from benchmarks.chip import levels

NAME = "hist_ms_per_round"
UNIT = "ms"
LAYER = "ops: hist_pallas kernel"
MOVES = "train_rows_per_s"
KINDS = ("fit",)


def reduce(evidence):
    traced = levels.traced_rounds(evidence, NAME)
    if traced is None:
        return None
    names, found = traced
    ms = levels.kernel_ms_by_level(found, len(names))
    say = levels.sayer(evidence)
    say(f"{NAME}: " + "; ".join(
        f"chip {chip.chip} {len(starts)} whole rounds in {len(calls)} "
        f"Mosaic calls" for chip, calls, starts in found)
        + f"; kernel ms by level: {levels.table(names, ms)}")
    return sum(ms)
