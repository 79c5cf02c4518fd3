"""The yardstick's arithmetic: percentiles, medians, interval unions.

Kept with the benchmark (a corrected copy of ``serve/loadgen.percentile``,
which PERF.md lists for a later PR to delete) so that no PR that claims a
gain can change how a number is reduced.  Pure Python + numpy, no JAX.
"""

from __future__ import annotations

import numpy as np


def percentile(values, q):
    """Linear-interpolated percentile (``q`` in [0, 1]) of an unsorted
    sample; ``None`` for an empty one."""
    if len(values) == 0:
        return None
    return float(np.quantile(np.asarray(values, np.float64), q))


def median(values):
    return percentile(values, 0.5)


def request_latencies_ms(samples):
    """Client latency of every ``ok`` request in ms, taken from the time
    the request was SCHEDULED to be sent (open loop: a late dispatch counts
    against the server, not for it)."""
    return [(s["done_s"] - s["scheduled_s"]) * 1e3
            for s in samples if s["outcome"] == "ok"]


def dispatch_lateness_ms(samples):
    """How late the generator sent each request: actual - scheduled, ms."""
    return [(s["sent_s"] - s["scheduled_s"]) * 1e3 for s in samples]


def union_seconds(intervals):
    """Total length of the union of ``(start, end)`` intervals."""
    total, end = 0.0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def uncovered(intervals, covers):
    """Length of the part of ``intervals`` (their union) that no interval
    of ``covers`` overlaps — e.g. all-reduce time with no compute beside
    it."""
    both = union_seconds(list(intervals) + list(covers))
    return both - union_seconds(covers)
