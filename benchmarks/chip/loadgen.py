#!/usr/bin/env python
"""The benchmark's open-loop load generator for ``POST /v1/score``.

A corrected copy of ``dmlc_core_tpu/serve/loadgen.py`` (which PERF.md lists
for a later PR to retire), kept with the yardstick.  It imports nothing
from ``dmlc_core_tpu`` and never imports JAX.  What it corrects:

- it runs in a CHILD PROCESS of its own (``python loadgen.py ...``), so its
  threads do not sit on the server's interpreter lock;
- requests go over KEEP-ALIVE connections (``http.client``), one per worker
  thread, not a new TCP connection each;
- rows per request are drawn from a heavy-tailed mix given as data;
- it reports how LATE it dispatched each request (actual - scheduled), so
  a starved generator is not read as a fast server;
- samples are handed back through a file, one JSON object per request.

Arrivals are a seeded Poisson process at a fixed rate; latency runs from
each request's SCHEDULED time.  Every request carries a W3C
``traceparent`` whose trace id the sample records, so server spans can be
matched to it.  Outcomes: ``ok`` (200 with a ``predictions`` list), else
the status class; anything not ``ok`` is a failure.
"""

from __future__ import annotations

import argparse
import http.client
import json
import queue
import sys
import threading
import time
from urllib.parse import urlsplit

import numpy as np


def schedule(rate, seconds, seed):
    """Seeded Poisson arrival offsets in ``[0, seconds)``."""
    rng = np.random.default_rng([int(seed), 0xA771])
    gaps = rng.exponential(1.0 / rate, int(rate * seconds * 1.5) + 64)
    at = np.cumsum(gaps)
    while at[-1] < seconds:                      # vanishingly rare top-up
        more = rng.exponential(1.0 / rate, at.size)
        at = np.concatenate([at, at[-1] + np.cumsum(more)])
    return at[at < seconds]


def rows_per_request(mix, n, seed):
    """Rows of each of ``n`` requests from ``mix``: a list of ``{"share",
    "min", "max"}`` (uniform integers in ``[min, max]`` with that share)."""
    rng = np.random.default_rng([int(seed), 0x2085])
    shares = np.asarray([m["share"] for m in mix], np.float64)
    which = rng.choice(len(mix), size=n, p=shares / shares.sum())
    lo = np.asarray([m["min"] for m in mix])[which]
    hi = np.asarray([m["max"] for m in mix])[which]
    return lo + (rng.random(n) * (hi - lo + 1)).astype(np.int64)


def request_rows(seed, index, n_rows, num_feature):
    """The feature rows of request ``index``: standard-normal float32
    rounded to 6 decimals (what the JSON body carries), a pure function of
    (seed, index) so that the checker can make them again."""
    rng = np.random.default_rng([int(seed), 0xB0D7, int(index)])
    return np.round(rng.standard_normal((int(n_rows), num_feature)), 6) \
        .astype(np.float32)


def trace_id(seed, index):
    rng = np.random.default_rng([int(seed), 0x7ACE, int(index)])
    return rng.bytes(16).hex()


def body(rows):
    return json.dumps({"instances": [[round(float(v), 6) for v in r]
                                     for r in rows]}).encode()


class _Worker(threading.Thread):
    """One keep-alive connection; takes requests from the shared queue."""

    def __init__(self, url, path, jobs, samples, timeout_s, clock0):
        super().__init__(daemon=True)
        self.netloc = urlsplit(url).netloc
        self.path, self.jobs, self.samples = path, jobs, samples
        self.timeout_s, self.clock0 = timeout_s, clock0
        self.conn = None

    def _post(self, payload, traceparent):
        if self.conn is None:
            self.conn = http.client.HTTPConnection(self.netloc,
                                                   timeout=self.timeout_s)
        self.conn.request("POST", self.path, body=payload, headers={
            "Content-Type": "application/json", "traceparent": traceparent})
        resp = self.conn.getresponse()
        return resp.status, resp.read()

    def run(self):
        while True:
            job = self.jobs.get()
            if job is None:
                return
            index, scheduled, payload, tid, n_rows, keep = job
            sent = time.perf_counter() - self.clock0
            sample = {"index": index, "scheduled_s": scheduled,
                      "sent_s": sent, "rows": n_rows, "trace_id": tid}
            try:
                status, raw = self._post(payload, f"00-{tid}-{tid[:16]}-01")
                sample["status"] = status
                preds = json.loads(raw).get("predictions") \
                    if status == 200 else None
                if isinstance(preds, list):
                    sample["outcome"] = "ok"
                    sample["n_predictions"] = len(preds)
                    if keep:
                        sample["predictions"] = preds
                else:
                    sample["outcome"] = f"http_{status}"
            except (OSError, http.client.HTTPException, ValueError) as exc:
                sample["outcome"] = f"error:{type(exc).__name__}"
                if self.conn is not None:
                    self.conn.close()
                    self.conn = None
            sample["done_s"] = time.perf_counter() - self.clock0
            self.samples.append(sample)      # list.append is atomic


def offer(url, path, arrivals, payloads, ids, rows, keep, workers, timeout_s):
    """Send every request at its scheduled offset; returns the samples."""
    jobs, samples = queue.SimpleQueue(), []
    clock0 = time.perf_counter()
    pool = [_Worker(url, path, jobs, samples, timeout_s, clock0)
            for _ in range(workers)]
    for w in pool:
        w.start()
    for i, at in enumerate(arrivals):
        delay = at - (time.perf_counter() - clock0)
        if delay > 0:
            time.sleep(delay)
        jobs.put((i, float(at), payloads[i], ids[i], int(rows[i]),
                  i in keep))
    for _ in pool:
        jobs.put(None)
    for w in pool:
        w.join(timeout_s + 5)
    return samples, time.perf_counter() - clock0


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--url", required=True)
    ap.add_argument("--path", default="/v1/score")
    ap.add_argument("--cell", required=True,
                    help="the cell's JSON file: rate, mix, workers, ...")
    ap.add_argument("--num-feature", type=int, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rate", type=float,
                    help="override the cell's rate (the knee sweep)")
    ap.add_argument("--keep", type=int, default=200,
                    help="requests whose predictions are kept for checking")
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    with open(args.cell) as f:
        cell = json.load(f)
    rate = args.rate or float(cell["rate_per_s"])
    warm_s = float(cell.get("warmup_s", 1.0))
    # warm-up requests come first (negative indices would collide: they are
    # drawn from a seed of their own), then the measured schedule
    plans = []
    for seconds, seed in ((warm_s, args.seed + 1_000_003),
                          (args.seconds, args.seed)):
        arrivals = schedule(rate, seconds, seed)
        rows = rows_per_request(cell["rows_per_request"], arrivals.size,
                                seed)
        payloads = [body(request_rows(seed, i, n, args.num_feature))
                    for i, n in enumerate(rows)]
        ids = [trace_id(seed, i) for i in range(arrivals.size)]
        plans.append((arrivals, payloads, ids, rows))
    rng = np.random.default_rng([args.seed, 0x4EE9])
    n = plans[1][0].size
    keep = set(rng.choice(n, size=min(args.keep, n), replace=False).tolist())
    workers = int(cell.get("workers", 32))
    timeout_s = float(cell.get("timeout_s", 10.0))
    offer(args.url, args.path, *plans[0], set(), workers, timeout_s)
    print("START", flush=True)      # the parent's window opens here
    samples, wall = offer(args.url, args.path, *plans[1], keep, workers,
                          timeout_s)
    with open(args.out, "w") as f:
        for s in sorted(samples, key=lambda s: s["index"]):
            f.write(json.dumps(s) + "\n")
    print(json.dumps({"offered": n, "recorded": len(samples),
                      "rate_per_s": rate, "wall_s": wall}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
