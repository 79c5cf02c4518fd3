"""Device time of one round's leaf values and gradients (``gbdt.leaf`` in
``_build_tree``, ``gbdt.grad_hess`` in the round step): leaf sums, leaf
weights, ``margin_delta``, grad/hess and ``margin + delta``."""

from benchmarks.chip import scopes

NAME = "leaf_grad_ms_per_round"
UNIT = "ms"
LAYER = "models: leaf values and grad/hess"
MOVES = "train_rows_per_s"
KINDS = ("fit",)


def reduce(evidence):
    return scopes.phase_ms(evidence, ("gbdt.leaf", "gbdt.grad_hess"), "round")
