"""Median time of one batch's predict, dispatch to synced result
(``serve.predict`` batch spans): ``HostBinner.transform``, the transfer,
``GBDT._predict_fn``'s tree scan and the sync."""

from benchmarks.chip import stats

NAME = "serve_predict_p50_ms"
UNIT = "ms"
LAYER = "serve: model_runtime.py + models GBDT._predict_fn"
MOVES = "score_p50_ms"
KINDS = ("score",)


def reduce(evidence):
    if evidence["spans"] is None:
        return None
    batches = [e["dur"] * 1e-3 for e in evidence["spans"]
               if e["name"] == "serve.predict"
               and "rows" not in e.get("args", {})]
    return stats.median(batches)
