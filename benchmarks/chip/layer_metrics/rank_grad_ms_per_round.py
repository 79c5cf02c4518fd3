"""Device time of one round's listwise gradient (``gbdt.rank`` in the round
step, ``objective="lambdarank"``): the sort of the rows by query and margin,
the pairs of every query, the sums back onto both ends of a pair, the
per-query normalisation and the sort back to row order.  Nothing to read
on a program without that scope (every per-row objective)."""

from benchmarks.chip import scopes

NAME = "rank_grad_ms_per_round"
UNIT = "ms"
LAYER = "models: lambdarank gradient over query groups"
MOVES = "train_rows_per_s"
KINDS = ("fit",)


def reduce(evidence):
    ms = scopes.phase_ms(evidence, ("gbdt.rank",), "round")
    if ms == 0.0:
        evidence["say"]("rank_grad_ms_per_round: no op of the trace ran "
                        "under gbdt.rank")
        return None
    return ms
