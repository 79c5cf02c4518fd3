"""Mean rows per dispatched batch: ``dmlc_serve_rows_total`` over
``dmlc_serve_batches_total``."""

from benchmarks.chip.layer_metrics import counter_total

NAME = "serve_batch_rows_mean"
UNIT = "rows"
LAYER = "serve: scheduler.py MicroBatcher"
MOVES = "score_p99_ms"
KINDS = ("score",)


def reduce(evidence):
    rows = counter_total(evidence["counters"], "dmlc_serve_rows_total")
    batches = counter_total(evidence["counters"], "dmlc_serve_batches_total")
    if not rows or not batches:
        return None
    return rows / batches
