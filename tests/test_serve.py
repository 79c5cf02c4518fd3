"""Serving-path tests: scheduler coalescing/bucketing, model runtimes,
admission accounting, request parsing, and the HTTP surface end-to-end.

The failure paths (injected stalls, killed predict, reset storms) live in
tests/test_serve_chaos.py under the ``chaos`` marker.
"""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from dmlc_core_tpu.serve import (AdmissionController, BadRequest,
                                 MicroBatcher, ModelRuntime, Overloaded,
                                 ScoringServer, batch_buckets, build_runtime)
from dmlc_core_tpu.serve.server import parse_instances


# -- helpers ------------------------------------------------------------------

class StubRuntime(ModelRuntime):
    """Deterministic predict (row sums) that records every batch shape."""

    name = "stub"

    def __init__(self, num_feature=4):
        super().__init__(num_feature)
        self.shapes = []
        self.lock = threading.Lock()

    def predict(self, x):
        with self.lock:
            self.shapes.append(tuple(x.shape))
        return x.sum(axis=1)


def post(url, obj, timeout=10.0):
    """POST /v1/score; returns (status, parsed body) for 2xx and errors."""
    body = obj if isinstance(obj, bytes) else json.dumps(obj).encode()
    req = urllib.request.Request(
        url + "/v1/score", data=body,
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, json.load(resp)
    except urllib.error.HTTPError as e:
        return e.code, json.load(e)


def get(url, path, timeout=10.0):
    with urllib.request.urlopen(url + path, timeout=timeout) as resp:
        ctype = resp.headers.get("Content-Type", "")
        raw = resp.read()
        return resp.status, (json.loads(raw) if "json" in ctype
                             else raw.decode())


def get_until(url, path, found, seconds=5.0):
    """``get`` again until ``found(body)``, for a few seconds: a handler
    counts its request after the response is on the wire, so a read on a
    new connection right after a POST returned may come before the count.
    Returns the last read; the caller's asserts say what was missing."""
    deadline = time.monotonic() + seconds
    while True:
        status, body = get(url, path)
        if found(body) or time.monotonic() >= deadline:
            return status, body
        time.sleep(0.01)


# -- bucket ladder ------------------------------------------------------------

def test_batch_buckets_ladder_shape():
    assert batch_buckets(1) == [1]
    assert batch_buckets(4) == [1, 2, 3, 4]
    assert batch_buckets(64) == [1, 2, 3, 4, 6, 8, 12, 16, 24, 32, 48, 64]
    # a max_batch off the ladder caps the last rung exactly
    assert batch_buckets(5) == [1, 2, 3, 4, 5]
    with pytest.raises(ValueError):
        batch_buckets(0)


# -- request parsing ----------------------------------------------------------

def test_parse_instances_dense_sparse_mixed():
    x = parse_instances({"instances": [
        [1.0, 2.0, 3.0],
        {"index": [2], "value": [5.0]},
        {"index": [], "value": []},
    ]}, 3)
    np.testing.assert_allclose(x, [[1, 2, 3], [0, 0, 5], [0, 0, 0]])
    assert x.dtype == np.float32


def test_parse_instances_rejects_non_finite_values():
    # json.loads admits 1e400 (inf) and NaN; a 200 carrying them back
    # would be RFC-invalid JSON, so they stop at the door
    with pytest.raises(BadRequest, match="non-finite"):
        parse_instances({"instances": [[float("inf"), 0.0, 0.0]]}, 3)
    with pytest.raises(BadRequest, match="non-finite"):
        parse_instances({"instances": [[float("nan"), 0.0, 0.0]]}, 3)
    with pytest.raises(BadRequest, match="non-finite"):
        parse_instances({"instances": [
            {"index": [1], "value": [float("inf")]}]}, 3)


@pytest.mark.parametrize("body,frag", [
    ([1, 2], "body must be a JSON object"),
    ({}, "'instances'"),
    ({"instances": []}, "'instances'"),
    ({"instances": [[1.0]]}, "expected 3 features"),
    ({"instances": [["a", "b", "c"]]}, "non-numeric"),
    ({"instances": [{"index": [0]}]}, "equal-length"),
    ({"instances": [{"index": [3], "value": [1.0]}]}, "out of"),
    ({"instances": [{"index": [-1], "value": [1.0]}]}, "out of"),
    ({"instances": ["nope"]}, "each row"),
])
def test_parse_instances_rejects_malformed(body, frag):
    with pytest.raises(BadRequest, match=frag.replace("[", r"\[")):
        parse_instances(body, 3)


# -- admission ---------------------------------------------------------------

def test_admission_reserves_and_sheds():
    adm = AdmissionController(max_queue_bytes=100)
    adm.try_admit(60)
    adm.try_admit(40)
    assert adm.queued_bytes == 100
    with pytest.raises(Overloaded) as ei:
        adm.try_admit(1)
    err = ei.value
    assert err.status == 503 and err.code == "overloaded"
    assert err.payload()["error"]["retry_after"] >= 1
    assert "Retry-After" in err.headers()
    adm.release(60)
    adm.try_admit(10)  # admits again after drain
    assert adm.queued_bytes == 50


def test_admission_oversized_request_is_a_400_not_a_shed():
    adm = AdmissionController(max_queue_bytes=100)
    with pytest.raises(BadRequest):
        adm.try_admit(101)
    assert adm.queued_bytes == 0  # nothing reserved


def test_admission_retry_after_tracks_drain_rate_within_clamps():
    import time

    adm = AdmissionController(max_queue_bytes=100)
    adm.try_admit(100)
    # releases spread past the sampling window establish a drain EWMA;
    # back-to-back releases inside one window must NOT fabricate a rate
    adm.release(50)
    time.sleep(0.08)
    adm.release(30)
    assert adm._drain_rate is not None and adm._drain_rate > 0
    with pytest.raises(Overloaded) as ei:
        adm.try_admit(90)  # 20 still queued: 110 > 100 sheds
    ra = ei.value.retry_after
    assert 1.0 <= ra <= 30.0


def test_admission_microsecond_releases_do_not_swamp_drain_rate():
    adm = AdmissionController(max_queue_bytes=1000)
    adm.try_admit(1000)
    for _ in range(10):
        adm.release(100)  # all inside one sampling window
    # at most the first window could have closed; the rate, if any, must
    # not be the absurd bytes/microsecond of per-call spacing
    assert adm._drain_rate is None or adm._drain_rate < 1e9


def test_admission_release_never_goes_negative():
    adm = AdmissionController(max_queue_bytes=10)
    adm.release(5)
    assert adm.queued_bytes == 0


# -- scheduler ---------------------------------------------------------------

def test_scheduler_coalesces_concurrent_requests():
    rt = StubRuntime(num_feature=4)
    mb = MicroBatcher(rt, max_batch=16, max_delay_ms=30.0)
    mb.start()
    try:
        rows = [np.full((1, 4), i, np.float32) for i in range(8)]
        futures = [mb.submit(r) for r in rows]
        results = [f.result(timeout=10) for f in futures]
        for i, r in enumerate(results):
            np.testing.assert_allclose(r, [4.0 * i])
        # concurrent submits coalesced: fewer predict calls than requests
        assert len(rt.shapes) < 8
    finally:
        mb.close()


def test_scheduler_pads_to_bucket_ladder_shapes():
    rt = StubRuntime(num_feature=4)
    mb = MicroBatcher(rt, max_batch=8, max_delay_ms=20.0)
    mb.start()
    try:
        f = mb.submit(np.ones((5, 4), np.float32))
        np.testing.assert_allclose(f.result(timeout=10), [4.0] * 5)
        # 5 rows pad to the 6-rung, never an arbitrary shape
        assert rt.shapes == [(6, 4)]
        assert all(s[0] in mb.buckets for s in rt.shapes)
    finally:
        mb.close()


def test_scheduler_contract_violations_are_bad_requests():
    rt = StubRuntime(num_feature=4)
    mb = MicroBatcher(rt, max_batch=4, max_delay_ms=1.0)
    mb.start()
    try:
        with pytest.raises(BadRequest, match="empty"):
            mb.submit(np.zeros((0, 4), np.float32))
        with pytest.raises(BadRequest, match="max_batch"):
            mb.submit(np.zeros((5, 4), np.float32))
        with pytest.raises(BadRequest, match="instances must be"):
            mb.submit(np.zeros((2, 3), np.float32))
    finally:
        mb.close()


def test_scheduler_splits_overflow_across_batches():
    rt = StubRuntime(num_feature=2)
    mb = MicroBatcher(rt, max_batch=4, max_delay_ms=40.0)
    mb.start()
    try:
        a = mb.submit(np.ones((3, 2), np.float32))
        b = mb.submit(np.ones((3, 2), np.float32))
        np.testing.assert_allclose(a.result(timeout=10), [2.0] * 3)
        np.testing.assert_allclose(b.result(timeout=10), [2.0] * 3)
        # 3+3 > max_batch: the second request carried over to its own batch
        assert len(rt.shapes) == 2
    finally:
        mb.close()


def test_scheduler_submit_after_close_sheds_structurally():
    rt = StubRuntime()
    mb = MicroBatcher(rt, max_batch=4, max_delay_ms=1.0)
    mb.start()
    mb.close()
    with pytest.raises(Overloaded, match="shutting down"):
        mb.submit(np.ones((1, 4), np.float32))


def test_scheduler_releases_admission_bytes_on_completion():
    rt = StubRuntime(num_feature=4)
    adm = AdmissionController(max_queue_bytes=1 << 20)
    mb = MicroBatcher(rt, max_batch=8, max_delay_ms=1.0, admission=adm)
    mb.start()
    try:
        futures = [mb.submit(np.ones((2, 4), np.float32)) for _ in range(5)]
        for f in futures:
            f.result(timeout=10)
        assert adm.queued_bytes == 0
    finally:
        mb.close()


# -- model runtimes -----------------------------------------------------------

def test_linear_runtime_matches_model_math():
    rt = build_runtime("linear", 6, seed=3)
    x = np.random.RandomState(0).normal(size=(5, 6)).astype(np.float32)
    got = rt.predict(x)
    w, b = np.asarray(rt.params["w"]), float(rt.params["b"])
    want = 1.0 / (1.0 + np.exp(-(x @ w + b)))
    np.testing.assert_allclose(got, want, rtol=1e-5)


def test_mlp_runtime_matches_model_predict():
    rt = build_runtime("mlp", 5, seed=1, hidden="8", num_class=3)
    x = np.random.RandomState(1).normal(size=(4, 5)).astype(np.float32)
    got = rt.predict(x)
    want = np.asarray(rt.model.predict(rt.params, x))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    assert got.shape == (4, 3)
    np.testing.assert_allclose(got.sum(axis=1), 1.0, rtol=1e-4)


def test_gbdt_runtime_predicts_probabilities():
    rt = build_runtime("gbdt", 4, seed=2)
    x = np.random.RandomState(2).normal(size=(6, 4)).astype(np.float32)
    got = rt.predict(x)
    assert got.shape == (6,)
    assert np.all((got > 0) & (got < 1))
    want = np.asarray(rt.gbdt.predict(rt.ensemble, rt.gbdt.bin_features(x)))
    np.testing.assert_allclose(got, want, rtol=1e-6)


def test_gbdt_runtime_serves_through_the_binned_wire_skew_free():
    # the skew-free contract (ISSUE 15): serving scores ride the uint8
    # HostBinner wire and are BITWISE-equal to the float-path predict —
    # including on exact boundary values, where any binning skew would
    # flip a split decision
    rt = build_runtime("gbdt", 4, seed=2)
    assert rt.binner.dtype == np.uint8  # 16 bins fit the narrowest wire
    x = np.random.RandomState(3).normal(size=(12, 4)).astype(np.float32)
    x[0, :] = rt.gbdt.boundaries[np.arange(4), 0]   # ties go right
    x[1, :] = rt.gbdt.boundaries[np.arange(4), -1]
    np.testing.assert_array_equal(rt.predict(x), rt.predict_float(x))
    # and the wire really is the narrow dtype end to end
    assert rt.binner.transform(x).dtype == np.uint8


def test_runtime_warmup_compiles_each_bucket_once():
    rt = StubRuntime(num_feature=3)
    assert rt.warmup([1, 2, 4, 4, 2]) == 3
    assert sorted(rt.shapes) == [(1, 3), (2, 3), (4, 3)]


def test_build_runtime_unknown_kind():
    with pytest.raises(ValueError, match="unknown model kind"):
        build_runtime("resnet", 4)


# -- HTTP surface -------------------------------------------------------------

# parametrized over both transports: every HTTP contract test below runs
# against the threaded ThreadingHTTPServer AND the selectors event loop
# with zero test forks (docs/serving.md "Transport")
@pytest.fixture(scope="module", params=["threaded", "evloop"])
def linear_server(request):
    rt = build_runtime("linear", 4, seed=0)
    server = ScoringServer(rt, max_batch=4, max_delay_ms=1.0,
                           request_timeout_s=10.0,
                           transport=request.param)
    with server:
        yield server


def test_http_score_dense_and_sparse(linear_server):
    url = linear_server.url
    status, body = post(url, {"instances": [[0.5, 0.5, 0.5, 0.5]]})
    assert status == 200
    assert body["model"] == "linear" and body["num_rows"] == 1
    # every response names the model version that scored it (the
    # lifecycle drill's atomicity probe; 0 = unmanaged/day-0)
    assert body["version"] == 0
    assert len(body["predictions"]) == 1
    # the sparse form of the same row scores identically
    status, sparse = post(url, {"instances": [
        {"index": [0, 1, 2, 3], "value": [0.5, 0.5, 0.5, 0.5]}]})
    assert status == 200
    assert sparse["predictions"] == pytest.approx(body["predictions"])


def test_http_malformed_bodies_are_structured_400s(linear_server):
    url = linear_server.url
    status, body = post(url, b"{not json")
    assert status == 400 and body["error"]["code"] == "bad_request"
    status, body = post(url, {"instances": [[1.0]]})
    assert status == 400 and "expected 4 features" in body["error"]["message"]
    status, body = post(url, {"instances": "x"})
    assert status == 400 and body["error"]["code"] == "bad_request"


def test_http_unknown_paths_are_structured(linear_server):
    req = urllib.request.Request(
        linear_server.url + "/v1/wrong", data=b"{}",
        headers={"Content-Type": "application/json"})
    try:
        urllib.request.urlopen(req, timeout=10)
        raise AssertionError("expected 400")
    except urllib.error.HTTPError as e:
        assert e.code == 400 and json.load(e)["error"]["code"] == "bad_request"


def test_http_healthz_and_stats(linear_server):
    from dmlc_core_tpu import telemetry

    status, health = get(linear_server.url, "/healthz")
    assert status == 200 and health["status"] == "ok"
    assert health["model"] == "linear" and health["num_feature"] == 4

    was_enabled = telemetry.enabled()
    telemetry.enable()
    try:
        status, _ = post(linear_server.url, {"instances": [[0, 0, 0, 0]]})
        assert status == 200
        # series names render exactly as the offline report's table keys
        # (every request-path metric carries the model-slot label)
        key = 'dmlc_serve_requests_total{model="linear",status="200"}'
        timed = 'dmlc_serve_request_seconds{model="linear",status="200"}'
        # the handler observes the time after it counts the request
        status, stats = get_until(linear_server.url, "/stats",
                                  lambda s: timed in s["metrics"])
        assert status == 200
        assert stats["model"] == "linear"
        series = stats["metrics"]
        assert series[key] >= 1
        hist = series[timed]
        assert hist["count"] >= 1 and hist["p50"] is not None
        assert hist["p50"] <= hist["p99"]
        # the per-slot identity block rides /stats too
        assert stats["models"]["linear"]["family"] == "linear"
    finally:
        if not was_enabled:
            telemetry.disable()


def test_http_metrics_prometheus_form(linear_server):
    from dmlc_core_tpu import telemetry

    was_enabled = telemetry.enabled()
    telemetry.enable()
    try:
        status, _ = post(linear_server.url, {"instances": [[1, 1, 1, 1]]})
        assert status == 200
        status, text = get_until(linear_server.url, "/metrics",
                                 lambda t: "dmlc_serve_requests_total" in t)
        assert status == 200
        assert "dmlc_serve_requests_total" in text
        assert "# TYPE" in text
    finally:
        if not was_enabled:
            telemetry.disable()


def test_http_payload_too_large_is_413(linear_server, monkeypatch):
    from dmlc_core_tpu.serve import server as server_mod

    monkeypatch.setattr(server_mod, "MAX_BODY_BYTES", 64)
    status, body = post(linear_server.url,
                        {"instances": [[0.0, 0.0, 0.0, 0.0]] * 10})
    assert status == 413
    assert body["error"]["code"] == "payload_too_large"


def test_http_negative_content_length_rejected_not_hung(linear_server):
    # a hostile Content-Length must not turn into rfile.read(-1), which
    # would pin the handler thread until the client hangs up
    import http.client

    host, port = linear_server.address
    conn = http.client.HTTPConnection(host, port, timeout=5)
    try:
        conn.putrequest("POST", "/v1/score")
        conn.putheader("Content-Type", "application/json")
        conn.putheader("Content-Length", "-1")
        conn.endheaders()
        resp = conn.getresponse()
        assert resp.status == 400
        assert json.load(resp)["error"]["code"] == "bad_request"
    finally:
        conn.close()


def test_http_keepalive_connection_stays_in_sync(linear_server):
    # two requests down ONE persistent connection: the first response must
    # leave the stream positioned at the second request
    import http.client

    host, port = linear_server.address
    conn = http.client.HTTPConnection(host, port, timeout=10)
    try:
        for i in range(2):
            body = json.dumps({"instances": [[float(i)] * 4]})
            conn.request("POST", "/v1/score", body=body,
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            assert resp.status == 200
            assert len(json.load(resp)["predictions"]) == 1
    finally:
        conn.close()


def test_http_unknown_model_404_closes_keepalive_connection(linear_server):
    # the route-error path answers WITHOUT reading the body: keeping the
    # keep-alive connection would parse that unread body as the next
    # request line, so the 404 must close the connection
    import http.client

    host, port = linear_server.address
    conn = http.client.HTTPConnection(host, port, timeout=10)
    try:
        body = json.dumps({"instances": [[0.0] * 4]})
        conn.request("POST", "/v1/score/nope", body=body,
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        assert resp.status == 404
        assert json.load(resp)["error"]["code"] == "unknown_model"
        with pytest.raises((http.client.HTTPException, ConnectionError,
                            OSError)):
            conn.request("POST", "/v1/score", body=body,
                         headers={"Content-Type": "application/json"})
            conn.getresponse()
    finally:
        conn.close()


def test_http_concurrent_clients_all_answered(linear_server):
    url = linear_server.url
    results = []
    lock = threading.Lock()

    def client(i):
        status, body = post(url, {"instances": [[i, 0.0, 0.0, 0.0]]})
        with lock:
            results.append((i, status, body))

    threads = [threading.Thread(target=client, args=(i,)) for i in range(12)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30)
    assert len(results) == 12
    assert all(status == 200 for _, status, _ in results)
    # scores are per-row correct, not shuffled across the coalesced batch
    w0 = float(np.asarray(linear_server.runtime.params["w"])[0])
    b = float(linear_server.runtime.params["b"])
    for i, _, body in results:
        want = 1.0 / (1.0 + np.exp(-(i * w0 + b)))
        assert body["predictions"][0] == pytest.approx(want, rel=1e-4)


# -- loadgen drift canary ------------------------------------------------------

def test_loadgen_drift_bucketing_and_series():
    """The drift canary's accounting: per-window request counts and mean
    predictions, sorted, empty windows absent (docs/serving.md)."""
    from dmlc_core_tpu.serve.loadgen import _mean_prediction, _Recorder

    # scalar and softmax-row predictions flatten to one mean; junk skipped
    assert _mean_prediction([0.25, 0.75]) == pytest.approx(0.5)
    assert _mean_prediction([[0.2, 0.8], [0.4, 0.6]]) == pytest.approx(0.5)
    assert _mean_prediction(["oops", None]) is None
    assert _mean_prediction([]) is None

    rec = _Recorder()
    rec.record_drift(0, 0.2)
    rec.record_drift(0, 0.4)
    rec.record_drift(2, 0.9)           # window 1 empty: not emitted
    series = rec.drift_series(1.5)
    assert series == [
        {"window": 0, "t_s": 0.0, "n": 2, "mean_prediction": 0.3},
        {"window": 2, "t_s": 3.0, "n": 1, "mean_prediction": 0.9},
    ]


def test_loadgen_report_carries_drift_and_response_check(linear_server):
    """run_load end to end: the report's drift block covers every ok
    response bucketed by scheduled time, and a failing response_check
    turns would-be oks into ``invalid`` (the half-swap detector)."""
    from dmlc_core_tpu.serve.loadgen import run_load

    report = run_load(linear_server.url, qps=40, duration_s=1.0,
                      num_feature=4, seed=3, timeout_s=10.0,
                      drift_window_s=0.25)
    counts = report["counts"]
    assert counts["crashed"] == 0 and counts["ok"] > 0
    drift = report["drift"]
    assert drift["window_s"] == pytest.approx(0.25)
    series = drift["series"]
    assert series, "ok traffic must produce drift windows"
    assert sum(w["n"] for w in series) == counts["ok"]
    assert [w["window"] for w in series] == sorted(
        {w["window"] for w in series})
    for w in series:
        assert w["n"] >= 1 and np.isfinite(w["mean_prediction"])
        assert w["t_s"] == pytest.approx(w["window"] * 0.25)

    # the check sees (payload, rows): reject everything -> all invalid,
    # nothing recorded as drift (wrong scores must not pollute the canary)
    seen_rows = []

    def reject(payload, rows):
        seen_rows.append((payload["num_rows"], len(rows)))
        return False

    report2 = run_load(linear_server.url, qps=30, duration_s=0.5,
                       num_feature=4, seed=4, timeout_s=10.0,
                       response_check=reject)
    assert report2["counts"]["invalid"] > 0
    assert report2["counts"]["ok"] == 0
    assert report2["drift"]["series"] == []
    assert seen_rows and all(n == len_rows for n, len_rows in seen_rows)
