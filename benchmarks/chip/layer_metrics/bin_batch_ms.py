"""Median time of one ``next()`` of the ``binned_batches`` iterator the
loader pulls: densify + ``HostBinner.transform`` of one batch on the
consumer's thread, including any wait on the parser's queue (read it
beside ``parse_mb_per_s``).  Timed by the benchmark: the program has no
span there yet."""

from benchmarks.chip import stats

NAME = "bin_batch_ms"
UNIT = "ms"
LAYER = "bridge: dense_batches densify + HostBinner.transform"
MOVES = "ingest_rows_per_s"
KINDS = ("ingest",)


def reduce(evidence):
    seconds = evidence["window"].get("bin_batch_seconds")
    if not seconds:
        return None
    return 1e3 * stats.median(seconds)
