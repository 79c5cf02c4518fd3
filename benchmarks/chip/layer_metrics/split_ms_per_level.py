"""Device time of one tree level's split scoring (``gbdt.split`` in
``_build_tree``): gain, masks, argmax and the split-table writes.  The
cumsums' ``reduce-window`` ops are the compiler's and carry no scope: they
are in ``fit_unscoped_share``."""

from benchmarks.chip import scopes

NAME = "split_ms_per_level"
UNIT = "ms"
LAYER = "models: _build_tree split scoring"
MOVES = "train_rows_per_s"
KINDS = ("fit",)


def reduce(evidence):
    return scopes.phase_ms(evidence, ("gbdt.split",), "level")
