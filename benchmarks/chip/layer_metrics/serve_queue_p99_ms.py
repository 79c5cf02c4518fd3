"""99th percentile of the time a request waited in the MicroBatcher's
queue before its batch was dispatched (``serve.queue.wait`` spans)."""

from benchmarks.chip import stats
from benchmarks.chip.layer_metrics import span_seconds

NAME = "serve_queue_p99_ms"
UNIT = "ms"
LAYER = "serve: scheduler.py MicroBatcher"
MOVES = "score_p99_ms"
KINDS = ("score",)


def reduce(evidence):
    if evidence["spans"] is None:
        return None
    waits = span_seconds(evidence["spans"], "serve.queue.wait")
    p99 = stats.percentile(waits, 0.99)
    return None if p99 is None else 1e3 * p99
