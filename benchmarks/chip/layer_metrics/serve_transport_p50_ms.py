"""What the transport costs a request: client latency minus its
``serve.queue.wait`` and ``serve.predict`` spans, matched through the
``traceparent`` the generator sent; median.  HTTP, JSON, the handler
thread and the hop to and from the batcher are what is left."""

from benchmarks.chip import stats

NAME = "serve_transport_p50_ms"
UNIT = "ms"
LAYER = "serve: transport (server.py / eventloop.py, JSON)"
MOVES = "score_p50_ms"
KINDS = ("score",)


def reduce(evidence):
    if evidence["spans"] is None:
        return None
    inside = {}
    for e in evidence["spans"]:
        if e["name"] in ("serve.queue.wait", "serve.predict") \
                and "trace_id" in e and "parent_id" in e:
            if e["name"] == "serve.predict" \
                    and "rows" not in e.get("args", {}):
                continue                      # the batch's own span
            inside[e["trace_id"]] = inside.get(e["trace_id"], 0.0) \
                + e["dur"] * 1e-3
    rest = [(s["done_s"] - s["scheduled_s"]) * 1e3 - inside[s["trace_id"]]
            for s in evidence["window"]["samples"]
            if s["outcome"] == "ok" and s["trace_id"] in inside]
    return stats.median(rest)
