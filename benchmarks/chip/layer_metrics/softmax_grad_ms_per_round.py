"""Device time a BOOSTING round spends on what it does once over the class
axis (``gbdt.softmax`` in the round step, ``objective="softmax"``): the
``[K, rows]`` softmax gradient from the round's one margin snapshot, the
margin's update by the K trees' leaves, and any transposition between the
class-major arrays inside the fit and the ``[rows, K]`` ones outside it.

``scopes.phase_ms(..., "round")`` divides by Mosaic calls / ``max_depth``:
the rounds of ``trees_per_call`` trees that the kernel's calls build (the
``gbdt.fit.dispatch`` span's field, 1 where it carries none).  A boosting
round of this objective grows ``model.num_class`` trees, so the value is
that times ``num_class / trees_per_call``: 1 where one call builds a
level of all K trees, K where a call builds one tree.  Nothing to read on
a program without the scope (every other objective, and a softmax program
from before the scope)."""

from benchmarks.chip import levels, scopes

NAME = "softmax_grad_ms_per_round"
UNIT = "ms"
LAYER = "models: softmax gradient and margin update over the class axis"
MOVES = "train_rows_per_s"
KINDS = ("fit",)


def reduce(evidence):
    ms = scopes.phase_ms(evidence, ("gbdt.softmax",), "round")
    if not ms:
        evidence["say"]("softmax_grad_ms_per_round: no op of the trace ran "
                        "under gbdt.softmax")
        return None
    trees, why = levels.trees_per_call(evidence)
    if trees is None:
        evidence["say"](f"softmax_grad_ms_per_round: {why}")
        return None
    classes = int((evidence["config"].get("model") or {}).get("num_class", 1))
    return ms * classes / trees
