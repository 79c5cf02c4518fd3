"""Device time of one tree level's routing (``gbdt.route`` in
``_build_tree``): the per-row select-sum over the feature axis, the node
table gathers and ``node = node * 2 + go_right``."""

from benchmarks.chip import scopes

NAME = "route_ms_per_level"
UNIT = "ms"
LAYER = "models: _build_tree routing"
MOVES = "train_rows_per_s"
KINDS = ("fit",)


def reduce(evidence):
    return scopes.phase_ms(evidence, ("gbdt.route",), "level")
