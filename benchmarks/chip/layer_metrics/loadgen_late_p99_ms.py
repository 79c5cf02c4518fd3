"""How late the benchmark's own generator dispatched: actual minus
scheduled send time, 99th percentile.  A late generator flatters the
server's tail, so read ``score_p99_ms`` beside this."""

from benchmarks.chip import stats

NAME = "loadgen_late_p99_ms"
UNIT = "ms"
LAYER = "benchmark: load generator"
MOVES = "score_p99_ms"
KINDS = ("score",)


def reduce(evidence):
    return stats.percentile(
        stats.dispatch_lateness_ms(evidence["window"]["samples"]), 0.99)
