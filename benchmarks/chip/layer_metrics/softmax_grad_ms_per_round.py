"""Device time a BOOSTING round spends on what it does once over the class
axis (``gbdt.softmax`` in the round step, ``objective="softmax"``): the
``[K, rows]`` softmax gradient from the round's one margin snapshot, the
margin's update by the K trees' leaves, and any transposition between the
class-major arrays inside the fit and the ``[rows, K]`` ones outside it.

``scopes.phase_ms(..., "round")`` divides by the trees the trace holds
(Mosaic calls / ``max_depth``: what the older readers call a round), and a
boosting round of this objective grows ``model.num_class`` of them, so the
value is that times K.  Nothing to read on a program without the scope
(every other objective, and a softmax program from before the scope)."""

from benchmarks.chip import scopes

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
    classes = int((evidence["config"].get("model") or {}).get("num_class", 1))
    return ms * classes
