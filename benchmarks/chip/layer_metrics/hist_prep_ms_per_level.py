"""Device time of one level's ``HistPlan.level`` outside the Mosaic kernel
(ops scoped ``gbdt.hist`` that are not the custom call): the relayout of
the kernel's result, the sibling's subtraction and the interleave, and on
four chips the all-reduce's slivers on the ``XLA Ops`` line."""

from benchmarks.chip import scopes

NAME = "hist_prep_ms_per_level"
UNIT = "ms"
LAYER = "ops: HistPlan.level outside the kernel"
MOVES = "train_rows_per_s"
KINDS = ("fit",)


def reduce(evidence):
    return scopes.phase_ms(evidence, ("gbdt.hist",), "level")
