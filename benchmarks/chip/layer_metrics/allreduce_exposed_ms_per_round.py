"""All-reduce time of one round during which nothing else ran on the chip
(the per-level histogram psum that did not hide), on the worst chip."""

from benchmarks.chip import stats
from benchmarks.chip.layer_metrics import rounds_traced

NAME = "allreduce_exposed_ms_per_round"
UNIT = "ms"
LAYER = "collective: psum of the per-level histograms"
MOVES = "train_rows_per_s"
KINDS = ("fit",)


def reduce(evidence):
    worst = None
    for chip in evidence["trace"].chips:
        reduces = [o for o in chip.ops + chip.async_ops if o.is_all_reduce]
        rounds = rounds_traced(evidence, chip)
        if not reduces or not rounds:
            return None
        others = [(o.start_s, o.end_s) for o in chip.ops
                  if not o.is_all_reduce]
        exposed = stats.uncovered([(o.start_s, o.end_s) for o in reduces],
                                  others) / rounds
        worst = exposed if worst is None else max(worst, exposed)
    return 1e3 * worst
