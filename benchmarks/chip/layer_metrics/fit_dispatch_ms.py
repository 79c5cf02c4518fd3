"""Host time of one ``GBDT.fit_binned`` call up to the return of the
jitted call (the ``gbdt.fit.dispatch`` span): argument staging and the
asynchronous dispatch, not the device's work."""

from benchmarks.chip import stats
from benchmarks.chip.layer_metrics import span_seconds

NAME = "fit_dispatch_ms"
UNIT = "ms"
LAYER = "models: GBDT.fit_binned on the host"
MOVES = "train_rows_per_s"
KINDS = ("fit",)


def reduce(evidence):
    if evidence["spans"] is None:
        return None
    seconds = span_seconds(evidence["spans"], "gbdt.fit.dispatch")
    return 1e3 * stats.median(seconds) if seconds else None
