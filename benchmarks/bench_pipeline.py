#!/usr/bin/env python
"""Input-pipeline benchmark harnesses (the reference's tier-2 CLI tests:
split_read_test.cc, libsvm_parser_test.cc — they print MB/sec).

    python benchmarks/bench_pipeline.py split  <uri> [part] [nparts] [type]
    python benchmarks/bench_pipeline.py parser <uri> [format] [nthread]
    python benchmarks/bench_pipeline.py parser-ab <uri> [format] [out.json] [workers]
    python benchmarks/bench_pipeline.py cache-ab [rows] [out.json] [trace_dir]
    python benchmarks/bench_pipeline.py columnar-ab [rows] [out.json] [trace_dir]
    python benchmarks/bench_pipeline.py fleet-ab [workers] [rows] [out.json] [trace_dir]
    python benchmarks/bench_pipeline.py gen    <path> [rows] [features] [libsvm|libfm|csv]
    python benchmarks/bench_pipeline.py genrec <path.rec> [records] [bytes]
    python benchmarks/bench_pipeline.py infeed <path.rec> [record_bytes] [batch]

``parser-ab`` is the thread-vs-process A/B behind the pipeline-tuning
table in docs/performance.md: it drains the same corpus through the
single-worker, thread-pool, and process-pool (DMLC_PARSE_PROC) backends,
prints rows/s per stage (raw split read vs parse), and writes the JSON
record next to the telemetry artifact in CI (and into
benchmarks/results/ when run by hand).

``columnar-ab`` is the zero-copy columnar-ingest A/B behind the
"Columnar ingest" table in docs/performance.md: the same logical dataset
is drained through the cold text parser and through the Arrow/Parquet
front door (``data/arrow_ingest.py``), then through the Parquet ->
v2-page-cache build and a warm mmap epoch.  The Arrow stage runs under
``DMLC_ARROW_REQUIRE_ZERO_COPY=1`` and the engagement gate exits nonzero
if any column took the bulk-copy path (the
``dmlc_ingest_columns_total{mode}`` counters are the ground truth, plus a
direct buffer-identity assertion against the Arrow child buffers) — a
silent copy can never be logged as a zero-copy number.

``fleet-ab`` is the fleet-ingest scheduling A/B behind the "Fleet
ingest" section of docs/performance.md: N local worker processes drain
the same cold mock-S3 corpus to device-ready batches under static
``k % n`` assignment vs dynamic shard leasing
(``parallel/fleet_ingest.py`` + the tracker's ShardLeaseCoordinator),
each policy measured clean, with an injected straggler (a deterministic
2s-per-acquire delay fault on one worker), and — dynamic only — with a
worker killed mid-unit by the committed
``benchmarks/fleet_fault_plan.json``.  The kill scenario is the
engagement gate: it must show ``>= 1`` reassigned unit, a nonzero worker
exit code, and exactly-once coverage (ledger rows == corpus rows), or
the run exits nonzero — a scheduler that silently lost or double-counted
rows can never be logged as a speedup.

``cache-ab`` is the fleet-shared remote page cache A/B on a loopback
mock-S3 store: worker A cold-parses the remote corpus, builds the v2
cache, and publishes it (``DMLC_CACHE_REMOTE=1``); worker B — a fresh
"host" (its own ``DMLC_CACHE_LOCAL_DIR``) — fetches the published cache
through the ranged-read layer instead of re-parsing.  Prints rows/s per
stage, verifies the warm path actually engaged (a silent
fallback-to-parse exits nonzero rather than logging parse numbers as
cache numbers), and assembles the ``cache.fetch``/``cache.publish``
spans into a merged trace with the critical-path CLI.
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def bench_split(uri, part=0, nparts=1, type_="text"):
    from dmlc_core_tpu.io.input_split import create_input_split
    from dmlc_core_tpu.utils.profiler import ThroughputMeter

    split = create_input_split(uri, int(part), int(nparts), type_)
    from benchmarks.bench_common import drain

    meter = ThroughputMeter("split-read")
    drain(split, meter)
    split.close()
    print(meter.summary())


def bench_parser(uri, fmt="auto", nthread=2):
    from dmlc_core_tpu.data.factory import create_parser
    from dmlc_core_tpu.utils.profiler import ThroughputMeter

    parser = create_parser(uri, type=fmt, nthread=int(nthread))
    meter = ThroughputMeter("parse")
    rows = 0
    for block in parser:
        rows += block.size
        meter.add(0, nrows=block.size)
    meter.add(parser.bytes_read())
    print(f"{rows} rows; {meter.summary()}")
    print(f"parse-stage: {meter.rows_per_sec:.0f} rows/s")


def _drain_parser(uri, fmt, nthread, threaded, env=None):
    """One timed full drain; returns (rows, bytes, seconds)."""
    import time as _time

    from dmlc_core_tpu.data.factory import create_parser

    saved = {}
    for key, value in (env or {}).items():
        saved[key] = os.environ.get(key)
        os.environ[key] = value
    try:
        parser = create_parser(uri, type=fmt, nthread=nthread,
                               threaded=threaded)
        rows = 0
        t0 = _time.perf_counter()
        for block in parser:
            rows += block.size
        elapsed = _time.perf_counter() - t0
        nbytes = parser.bytes_read()
        if hasattr(parser, "close"):
            parser.close()
        return rows, nbytes, elapsed
    finally:
        for key, value in saved.items():
            if value is None:
                os.environ.pop(key, None)
            else:
                os.environ[key] = value


def bench_parser_ab(uri, fmt="auto", out_json=None, workers=None):
    """Thread-pool vs process-pool parse A/B with per-stage rows/s."""
    import json
    import platform
    import time as _time

    from dmlc_core_tpu.io.input_split import create_input_split

    nworkers = int(workers) if workers else (os.cpu_count() or 2)

    # stage 0: raw split read (the parse stages sit on top of this)
    split = create_input_split(uri, 0, 1, "text")
    t0 = _time.perf_counter()
    split_bytes = 0
    while True:
        chunk = split.next_chunk()
        if chunk is None:
            break
        split_bytes += len(chunk)
    split_s = _time.perf_counter() - t0
    split.close()

    configs = {
        "single": dict(nthread=1, threaded=False,
                       env={"DMLC_PARSE_PROC": "0"}),
        f"thread[{nworkers}]": dict(nthread=nworkers, threaded=True,
                                    env={"DMLC_PARSE_PROC": "0"}),
        # cold pays the one-per-process worker-pool bring-up inside the
        # drain; warm reuses the shared pool — the steady-state number
        f"proc[{nworkers}] cold": dict(nthread=nworkers, threaded=True,
                                       env={"DMLC_PARSE_PROC": str(nworkers)}),
        f"proc[{nworkers}] warm": dict(nthread=nworkers, threaded=True,
                                       env={"DMLC_PARSE_PROC": str(nworkers)}),
    }
    results = {"uri": uri, "format": fmt, "workers": nworkers,
               "host": {"cores": os.cpu_count(),
                        "python": platform.python_version()},
               "split_stage": {"bytes": split_bytes, "seconds": split_s,
                               "mb_per_s": split_bytes / (1 << 20) / max(split_s, 1e-9)},
               "configs": {}}
    print(f"split-stage: {results['split_stage']['mb_per_s']:.0f} MB/s raw read")
    print(f"{'config':>14}  {'rows/s':>10}  {'MB/s':>7}  {'vs single':>9}")
    base_rps = None
    for name, cfg in configs.items():
        rows, nbytes, secs = _drain_parser(uri, fmt, cfg["nthread"],
                                           cfg["threaded"], cfg["env"])
        rps = rows / max(secs, 1e-9)
        if base_rps is None:
            base_rps = max(rps, 1e-9)
        is_proc = cfg["env"].get("DMLC_PARSE_PROC", "0") not in ("0", "")
        engaged = True
        if is_proc:
            # the parser falls back to threads when worker bring-up fails
            # (or the native core disables the backend); a thread number
            # recorded as "proc" would silently poison the longitudinal
            # series this JSON exists for
            from dmlc_core_tpu.data import parse_proc as _pp

            engaged = _pp.engaged()
        results["configs"][name] = {
            "rows": rows, "bytes": nbytes, "seconds": secs,
            "rows_per_s": rps, "mb_per_s": nbytes / (1 << 20) / max(secs, 1e-9),
            "speedup_vs_single": rps / base_rps,
            "backend_engaged": engaged,
        }
        marker = "" if engaged else "  [FELL BACK TO THREADS]"
        print(f"{name:>14}  {rps:>10.0f}  "
              f"{results['configs'][name]['mb_per_s']:>7.1f}  "
              f"{rps / base_rps:>8.2f}x{marker}")
    # honest-capture guard (benchmarks/results/r6_parse_fanout/README.md):
    # a proc-vs-thread number taken on a small host must carry its caveat
    # IN the record, so a 2-core capture can never be read as the fleet bar
    cores = os.cpu_count() or 0
    results["cpu_count"] = cores
    if cores < 4:
        caveat = (f"host has {cores} cores: the >=3x proc-vs-thread fleet "
                  "bar needs >=4 cores — proc speedups here are "
                  "contention-bound lower bounds, not the bar")
        results["core_caveat"] = caveat
        print(f"CAVEAT: {caveat}")
    if out_json:
        with open(out_json, "w") as f:
            json.dump(results, f, indent=2)
        print(f"wrote {out_json}")
    return results


def bench_cache_ab(rows=400_000, out_json=None, trace_dir=None):
    """Cold-remote parse vs warm fleet-fetched cache on a loopback store.

    Exits nonzero when the warm path silently falls back to stream-parsing
    — a fallback's parse throughput recorded as a "cache fetch" number
    would poison the longitudinal series (and is exactly the failure the
    CI cache-bench job exists to catch)."""
    import json
    import tempfile
    import time as _time

    from dmlc_core_tpu import telemetry

    rows = int(rows)
    work = tempfile.mkdtemp(prefix="cache-ab-")
    trace_dir = trace_dir or os.path.join(work, "trace")
    os.makedirs(trace_dir, exist_ok=True)
    telemetry.enable(trace_dir)

    src = os.path.join(work, "data.libsvm")
    gen(src, rows=rows, features=28, fmt="libsvm")
    corpus_bytes = os.path.getsize(src)

    from tests.mock_s3 import MockS3

    server = MockS3().start()
    os.environ.update(AWS_ACCESS_KEY_ID="cache-ab",
                      AWS_SECRET_ACCESS_KEY="cache-ab",
                      AWS_REGION="us-east-1",
                      S3_ENDPOINT=f"http://127.0.0.1:{server.port}")
    with open(src, "rb") as f:
        server.objects[("bucket", "data.libsvm")] = f.read()

    from dmlc_core_tpu.data.factory import create_row_block_iter

    uri = "s3://bucket/data.libsvm#s3://bucket/caches/data.rbc"
    reg = telemetry.get_registry()
    hits = reg.counter("dmlc_cache_remote_hits_total")
    publishes = reg.counter("dmlc_cache_remote_publishes_total")
    rebuilds = reg.counter("dmlc_cache_rebuilds_total")
    fetched = reg.counter("dmlc_cache_remote_bytes_fetched_total")

    def one_worker(stage, host_dir):
        """One fleet worker: iterator construction (where the fetch or the
        parse+build+publish happens) plus a full epoch drain, timed as one
        stage — then a second epoch alone, the steady-state mmap number."""
        os.environ["DMLC_CACHE_LOCAL_DIR"] = host_dir
        with telemetry.span(f"cache_ab.{stage}", rows=rows):
            t0 = _time.perf_counter()
            it = create_row_block_iter(uri, type="libsvm")
            got = sum(b.size for b in it)
            elapsed = _time.perf_counter() - t0
        it.before_first()
        t0 = _time.perf_counter()
        got2 = sum(b.size for b in it)
        epoch2 = _time.perf_counter() - t0
        it.close()
        assert got == got2 == rows, f"{stage}: {got}/{got2} of {rows} rows"
        return elapsed, epoch2

    # page granularity is the fetch-pipeline unit: 8 MB pages give the
    # prefetch ring several in-flight ranged reads to overlap (one default
    # 64 MB page would serialize the whole warm fetch behind one request).
    # Depth 2 on the LOOPBACK store: client, server, and CRC share one
    # host's cores, so two streams already saturate it — the deeper
    # default ring is sized for real object stores with per-stream caps
    os.environ.setdefault("DMLC_CACHE_PAGE_BYTES", str(8 << 20))
    os.environ.setdefault("DMLC_CACHE_PREFETCH", "2")
    os.environ["DMLC_CACHE_REMOTE"] = "1"
    try:
        cold_s, cold_epoch2_s = one_worker("cold", os.path.join(work, "host-a"))
        cold_published = publishes.value >= 1
        warm_s, warm_epoch2_s = one_worker("warm", os.path.join(work, "host-b"))
        warm_engaged = (hits.value >= 1 and cold_published
                        and rebuilds.value == 0)
    finally:
        server.stop()
        os.environ.pop("DMLC_CACHE_REMOTE", None)
        os.environ.pop("DMLC_CACHE_LOCAL_DIR", None)

    results = {
        "rows": rows, "corpus_bytes": corpus_bytes,
        "remote_cache_bytes": int(fetched.value),
        "warm_fetch_engaged": warm_engaged,
        "stages": {
            "cold_parse_build_publish": {
                "seconds": cold_s, "rows_per_s": rows / max(cold_s, 1e-9)},
            "warm_fleet_fetch": {
                "seconds": warm_s, "rows_per_s": rows / max(warm_s, 1e-9)},
            "cold_epoch2_mmap": {
                "seconds": cold_epoch2_s,
                "rows_per_s": rows / max(cold_epoch2_s, 1e-9)},
            "warm_epoch2_mmap": {
                "seconds": warm_epoch2_s,
                "rows_per_s": rows / max(warm_epoch2_s, 1e-9)},
        },
        "warm_vs_cold_speedup": cold_s / max(warm_s, 1e-9),
    }
    print(f"{'stage':>26}  {'rows/s':>12}  {'seconds':>8}")
    for name, st in results["stages"].items():
        print(f"{name:>26}  {st['rows_per_s']:>12.0f}  {st['seconds']:>8.2f}")
    print(f"warm fleet fetch vs cold re-parse: "
          f"{results['warm_vs_cold_speedup']:.2f}x")

    telemetry.flush(trace_dir)
    from dmlc_core_tpu.telemetry import traceview

    merged = os.path.join(trace_dir, "merged.trace.json")
    traceview.main(trace_dir, out=merged, as_json=False, top=10)
    results["merged_trace"] = merged
    if out_json:
        with open(out_json, "w") as f:
            json.dump(results, f, indent=2)
        print(f"wrote {out_json}")
    if not warm_engaged:
        print("ERROR: warm fetch path did NOT engage — the 'warm' number "
              "above is a stream-parse fallback, not a cache fetch",
              file=sys.stderr)
        raise SystemExit(1)
    return results


def bench_fleet_ab(workers=4, rows=100_000, out_json=None, trace_dir=None):
    """Static k%n vs dynamic shard leasing: cold mock-S3 -> device-ready
    batches at N local worker processes.

    Five scenarios through the SAME coordinator wire path (so the A/B
    measures scheduling policy, not transport): static / dynamic clean,
    static / dynamic with one straggling worker (a deterministic 2s delay
    fault on every lease acquire of the last worker), and dynamic with a
    worker killed mid-unit by the committed
    benchmarks/fleet_fault_plan.json.  Exits nonzero unless every
    scenario achieved exactly-once coverage and the kill scenario
    demonstrably engaged (>= 1 reassigned unit, a dead worker, zero
    lost/duplicated rows)."""
    import json
    import multiprocessing as mp
    import tempfile
    import time as _time

    from dmlc_core_tpu import telemetry
    from dmlc_core_tpu.parallel import fleet_ingest
    from dmlc_core_tpu.telemetry import tracecontext
    from dmlc_core_tpu.tracker.rendezvous import (ShardLeaseCoordinator,
                                                  TrackerError)

    workers, rows = int(workers), int(rows)
    if workers < 2:
        # the committed kill plan targets worker w1, and a 1-worker
        # "fleet" has nothing to steal from — fail before burning four
        # scenarios to reach a guaranteed-misleading engagement error
        raise SystemExit("fleet-ab needs >= 2 workers (the committed "
                         "kill plan targets w1)")
    work = tempfile.mkdtemp(prefix="fleet-ab-")
    trace_dir = trace_dir or os.path.join(work, "trace")
    os.makedirs(trace_dir, exist_ok=True)
    telemetry.enable(trace_dir)
    # worker processes inherit this and flush their ingest.* spans beside
    # the coordinator's at exit (including the fault-exit flight path)
    os.environ["DMLC_TELEMETRY_DIR"] = trace_dir

    src = os.path.join(work, "fleet.libsvm")
    gen(src, rows=rows, features=28, fmt="libsvm")
    corpus_bytes = os.path.getsize(src)

    from tests.mock_s3 import MockS3

    server = MockS3().start()
    os.environ.update(AWS_ACCESS_KEY_ID="fleet-ab",
                      AWS_SECRET_ACCESS_KEY="fleet-ab",
                      AWS_REGION="us-east-1",
                      S3_ENDPOINT=f"http://127.0.0.1:{server.port}")
    with open(src, "rb") as f:
        server.objects[("bucket", "fleet.libsvm")] = f.read()
    uri = "s3://bucket/fleet.libsvm"

    lease_timeout = 2.0
    units = fleet_ingest.plan_units(uri, workers, fmt="libsvm",
                                    dense_features=28)
    straggler = f"w{workers - 1}"
    straggler_plan = json.dumps({"rules": [
        {"site": "io.fleet.lease", "kind": "delay", "seconds": 2.0,
         "times": None, "match": {"op": "acquire", "worker": straggler}}]})
    kill_plan = "@" + os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                   "fleet_fault_plan.json")
    ctx = mp.get_context("spawn")

    def run_scenario(name, mode, fault_plan=None):
        coord = ShardLeaseCoordinator("127.0.0.1", list(units), mode=mode,
                                      world_size=workers,
                                      lease_timeout=lease_timeout)
        coord.start()
        saved = {k: os.environ.get(k)
                 for k in ("DMLC_FAULT_PLAN",
                           tracecontext.TRACKER_TRACEPARENT_ENV)}
        os.environ[tracecontext.TRACKER_TRACEPARENT_ENV] = \
            tracecontext.format_traceparent(coord.trace)
        if fault_plan:
            os.environ["DMLC_FAULT_PLAN"] = fault_plan
        else:
            os.environ.pop("DMLC_FAULT_PLAN", None)
        try:
            procs = [ctx.Process(
                target=fleet_ingest.run_worker, args=(f"w{i}",),
                kwargs=dict(host="127.0.0.1", port=coord.port,
                            worker_index=i, lease_timeout=lease_timeout))
                for i in range(workers)]
            t0 = _time.perf_counter()
            for p in procs:
                p.start()
            for p in procs:
                p.join(timeout=600)
            elapsed = _time.perf_counter() - t0
            for p in procs:
                if p.is_alive():
                    p.terminate()
                    # reap, or exitcode stays None and a forcibly-killed
                    # worker is invisible to the dead-worker accounting
                    p.join(timeout=10)
            try:
                ledger = coord.result(timeout=10.0)
                coverage_error = None
            except TrackerError as exc:
                # incomplete coverage is a RESULT, not a crash: the table,
                # JSON and trace must still be written — they are the
                # diagnostics — and the end-of-run gate exits nonzero
                ledger = coord.ledger()
                coverage_error = str(exc)
        finally:
            for key, value in saved.items():
                if value is None:
                    os.environ.pop(key, None)
                else:
                    os.environ[key] = value
            coord.stop()
        got = sum(e["rows"] for e in ledger.values())
        per_worker = {}
        for entry in ledger.values():
            w = per_worker.setdefault(entry["worker"],
                                      {"units": 0, "rows": 0})
            w["units"] += 1
            w["rows"] += entry["rows"]
        out = {
            "mode": mode, "seconds": elapsed,
            "rows": got, "rows_per_s": got / max(elapsed, 1e-9),
            "coverage_exact": got == rows and coverage_error is None,
            "coverage_error": coverage_error,
            "units_assigned": coord.assigned_total,
            "units_committed": coord.committed_total,
            "units_reassigned": coord.reassigned_total,
            "commits_rejected": coord.rejected_total,
            "worker_exitcodes": [p.exitcode for p in procs],
            "per_worker": per_worker,
        }
        dead = sum(1 for c in out["worker_exitcodes"] if c)
        print(f"{name:>18}  {out['rows_per_s']:>10.0f} rows/s  "
              f"{elapsed:>6.2f}s  reassigned={coord.reassigned_total}"
              f"  dead_workers={dead}")
        if coverage_error:
            print(f"{name:>18}  COVERAGE INCOMPLETE: {coverage_error}")
        return out

    print(f"{'scenario':>18}  {'throughput':>16}  {'wall':>7}")
    scenarios = {
        "static": run_scenario("static", "static"),
        "dynamic": run_scenario("dynamic", "dynamic"),
        "static_straggler": run_scenario("static_straggler", "static",
                                         straggler_plan),
        "dynamic_straggler": run_scenario("dynamic_straggler", "dynamic",
                                          straggler_plan),
        "dynamic_kill": run_scenario("dynamic_kill", "dynamic", kill_plan),
    }
    server.stop()

    kill = scenarios["dynamic_kill"]
    kill_engaged = (kill["units_reassigned"] >= 1 and kill["coverage_exact"]
                    and any(kill["worker_exitcodes"]))
    speedup = (scenarios["dynamic_straggler"]["rows_per_s"]
               / max(scenarios["static_straggler"]["rows_per_s"], 1e-9))
    cores = os.cpu_count() or 0
    results = {
        "workers": workers, "rows": rows, "corpus_bytes": corpus_bytes,
        "units": len(units), "lease_timeout_s": lease_timeout,
        "cpu_count": cores,
        "scenarios": scenarios,
        "straggler_speedup_dynamic_vs_static": speedup,
        "kill_scenario_engaged": kill_engaged,
    }
    if cores < 4:
        results["core_caveat"] = (
            f"host has {cores} cores: clean-scenario throughput is "
            "contention-bound; the straggler A/B is sleep-dominated and "
            "remains meaningful")
    print(f"straggler scenario: dynamic vs static {speedup:.2f}x; "
          f"kill scenario: reassigned={kill['units_reassigned']}, "
          f"coverage_exact={kill['coverage_exact']}, "
          f"exitcodes={kill['worker_exitcodes']}")

    telemetry.flush(trace_dir)
    from dmlc_core_tpu.telemetry import traceview

    merged = os.path.join(trace_dir, "merged.trace.json")
    traceview.main(trace_dir, out=merged, as_json=False, top=10)
    results["merged_trace"] = merged
    if out_json:
        with open(out_json, "w") as f:
            json.dump(results, f, indent=2)
        print(f"wrote {out_json}")
    bad = [name for name, sc in scenarios.items()
           if not sc["coverage_exact"]]
    if bad or not kill_engaged:
        print("ERROR: fleet A/B did not engage — "
              f"incomplete-coverage scenarios {bad or 'none'}, "
              f"kill scenario engaged={kill_engaged} "
              f"(reassigned={kill['units_reassigned']}, "
              f"exitcodes={kill['worker_exitcodes']}); the numbers above "
              "must not enter the longitudinal series", file=sys.stderr)
        raise SystemExit(1)
    return results


def _gen_columnar_corpus(work, rows, features=28, seed=0):
    """The same logical dataset three times: libsvm text, sparse-schema
    Parquet, and sparse-schema Arrow IPC (label float32 + large_list
    index/value), written from one array draw so the A/B — and the
    byte-identity check — compare like against like.  Values are written
    with full float64-repr precision so the text parse round-trips to the
    identical float32 bits."""
    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq

    rng = np.random.RandomState(seed)
    text_path = os.path.join(work, "data.libsvm")
    parquet_path = os.path.join(work, "data.parquet")
    ipc_path = os.path.join(work, "data.arrow")
    pq_writer = ipc_writer = None
    with open(text_path, "w") as f:
        for start in range(0, rows, 65536):
            n = min(65536, rows - start)
            x = rng.randn(n, features).astype(np.float32)
            y = rng.randint(0, 2, n).astype(np.float32)
            lines = []
            for i in range(n):
                feats = " ".join(f"{j}:{float(x[i, j])!r}"
                                 for j in range(features))
                lines.append(f"{int(y[i])} {feats}")
            f.write("\n".join(lines) + "\n")
            offsets = np.arange(n + 1, dtype=np.int64) * features
            index = np.tile(np.arange(features, dtype=np.uint32), n)
            table = pa.table({
                "label": pa.array(y, type=pa.float32()),
                "index": pa.LargeListArray.from_arrays(
                    offsets, pa.array(index, type=pa.uint32())),
                "value": pa.LargeListArray.from_arrays(
                    offsets, pa.array(x.reshape(-1), type=pa.float32())),
            })
            if pq_writer is None:
                # uncompressed PLAIN pages: the A/B measures the ingest
                # boundary, not a codec
                pq_writer = pq.ParquetWriter(parquet_path, table.schema,
                                             compression="none",
                                             use_dictionary=False)
                ipc_writer = pa.ipc.new_file(ipc_path, table.schema)
            pq_writer.write_table(table)
            for batch in table.to_batches():
                ipc_writer.write_batch(batch)
    pq_writer.close()
    ipc_writer.close()
    print(f"wrote {rows} rows: {os.path.getsize(text_path) / (1 << 20):.1f} "
          f"MB libsvm text, {os.path.getsize(parquet_path) / (1 << 20):.1f} "
          f"MB parquet, {os.path.getsize(ipc_path) / (1 << 20):.1f} MB "
          "arrow ipc")
    return text_path, parquet_path, ipc_path


def bench_columnar_ab(rows=400_000, out_json=None, trace_dir=None):
    """Cold text parse vs zero-copy Arrow/Parquet ingest vs warm page cache.

    Exits nonzero when the zero-copy path did not engage — a bulk-copy
    fallback's throughput recorded as a "zero-copy ingest" number would
    poison the longitudinal series, exactly like cache-ab's
    fallback-to-parse gate."""
    import json
    import tempfile
    import time as _time

    import numpy as np

    from dmlc_core_tpu import telemetry
    from dmlc_core_tpu.data.arrow_ingest import require_pyarrow

    require_pyarrow()   # loud gate: this A/B is ABOUT the pyarrow path
    rows = int(rows)
    work = tempfile.mkdtemp(prefix="columnar-ab-")
    trace_dir = trace_dir or os.path.join(work, "trace")
    os.makedirs(trace_dir, exist_ok=True)
    telemetry.enable(trace_dir)

    text_path, parquet_path, ipc_path = _gen_columnar_corpus(work, rows)
    from dmlc_core_tpu.data.factory import create_parser, create_row_block_iter

    def drain(uri, stage, **kwargs):
        with telemetry.span(f"columnar_ab.{stage}", rows=rows):
            t0 = _time.perf_counter()
            parser = create_parser(uri, **kwargs)
            got = nnz = 0
            label_sum = np.float64(0.0)
            for block in parser:
                got += block.size
                nnz += block.num_nonzero
                label_sum += np.float64(block.label.sum(dtype=np.float64))
            elapsed = _time.perf_counter() - t0
            if hasattr(parser, "close"):
                parser.close()
        assert got == rows, f"{stage}: {got} of {rows} rows"
        return elapsed, nnz, float(label_sum)

    cold_s, text_nnz, text_labels = drain(text_path, "cold_text_parse",
                                          type="libsvm")

    # the columnar stages run strict: ANY bulk-copy column materialization
    # raises instead of silently degrading the number being measured
    os.environ["DMLC_ARROW_REQUIRE_ZERO_COPY"] = "1"
    try:
        parquet_s, pq_nnz, pq_labels = drain(parquet_path, "parquet_ingest")
        ipc_s, ipc_nnz, ipc_labels = drain(ipc_path, "arrow_ipc_ingest")
    finally:
        os.environ.pop("DMLC_ARROW_REQUIRE_ZERO_COPY", None)
    for name, got in (("parquet", (pq_nnz, pq_labels)),
                      ("arrow ipc", (ipc_nnz, ipc_labels))):
        assert got == (text_nnz, text_labels), (
            f"{name} corpus disagrees with the text corpus: "
            f"{got} vs {(text_nnz, text_labels)}")

    # direct buffer-identity witness, independent of the counters: the
    # CSR value column of IPC batch 0 aliases the file MAPPING itself
    import pyarrow as pa

    from dmlc_core_tpu.data.arrow_ingest import table_to_block

    mm = pa.memory_map(ipc_path)
    table = pa.Table.from_batches(
        [pa.ipc.open_file(mm).get_batch(0)])
    block, stats = table_to_block(table)
    child = table.column("value").chunk(0).values
    arrow_view = np.frombuffer(child.buffers()[1], dtype=np.float32,
                               count=len(child) + child.offset)
    buffer_identical = bool(np.shares_memory(block.value, arrow_view))
    del block, table, child, arrow_view

    # engagement gate ground truth: the ingest counters for the WHOLE drain
    metrics = telemetry.snapshot()["metrics"]

    def mode_count(mode):
        fam = metrics.get("dmlc_ingest_columns_total", {"samples": []})
        return sum(s["value"] for s in fam["samples"]
                   if s.get("labels", {}).get("mode") == mode)

    zero_copy_cols = mode_count("zero_copy")
    bulk_copy_cols = mode_count("bulk_copy")
    zero_copy_engaged = (zero_copy_cols > 0 and bulk_copy_cols == 0
                         and buffer_identical)

    # parquet -> v2 page cache (build epoch), then the warm mmap epoch
    cache = os.path.join(work, "data.cache")
    with telemetry.span("columnar_ab.cache_build_from_parquet", rows=rows):
        t0 = _time.perf_counter()
        it = create_row_block_iter(f"{parquet_path}#{cache}")
        got = sum(b.size for b in it)
        build_s = _time.perf_counter() - t0
    assert got == rows, f"cache build: {got} of {rows} rows"
    it.before_first()
    t0 = _time.perf_counter()
    got2 = sum(b.size for b in it)
    warm_s = _time.perf_counter() - t0
    it.close()
    assert got2 == rows, f"warm epoch: {got2} of {rows} rows"

    results = {
        "rows": rows,
        "text_bytes": os.path.getsize(text_path),
        "parquet_bytes": os.path.getsize(parquet_path),
        "arrow_ipc_bytes": os.path.getsize(ipc_path),
        "zero_copy_engaged": zero_copy_engaged,
        "zero_copy_columns": int(zero_copy_cols),
        "bulk_copy_columns": int(bulk_copy_cols),
        "buffer_identity": buffer_identical,
        "stages": {
            "cold_text_parse": {
                "seconds": cold_s, "rows_per_s": rows / max(cold_s, 1e-9)},
            "parquet_ingest": {
                "seconds": parquet_s,
                "rows_per_s": rows / max(parquet_s, 1e-9)},
            "arrow_ipc_ingest": {
                "seconds": ipc_s, "rows_per_s": rows / max(ipc_s, 1e-9)},
            "cache_build_from_parquet": {
                "seconds": build_s, "rows_per_s": rows / max(build_s, 1e-9)},
            "warm_mmap_epoch2": {
                "seconds": warm_s, "rows_per_s": rows / max(warm_s, 1e-9)},
        },
        "parquet_vs_text_speedup": cold_s / max(parquet_s, 1e-9),
        "arrow_vs_text_speedup": cold_s / max(ipc_s, 1e-9),
    }
    print(f"{'stage':>26}  {'rows/s':>12}  {'seconds':>8}")
    for name, st in results["stages"].items():
        print(f"{name:>26}  {st['rows_per_s']:>12.0f}  {st['seconds']:>8.2f}")
    print(f"parquet ingest vs cold text parse: "
          f"{results['parquet_vs_text_speedup']:.2f}x; arrow ipc: "
          f"{results['arrow_vs_text_speedup']:.2f}x  "
          f"(zero-copy cols {zero_copy_cols}, bulk-copy {bulk_copy_cols})")

    telemetry.flush(trace_dir)
    from dmlc_core_tpu.telemetry import traceview

    merged = os.path.join(trace_dir, "merged.trace.json")
    traceview.main(trace_dir, out=merged, as_json=False, top=10)
    results["merged_trace"] = merged
    if out_json:
        with open(out_json, "w") as f:
            json.dump(results, f, indent=2)
        print(f"wrote {out_json}")
    if not zero_copy_engaged:
        print("ERROR: zero-copy ingest did NOT engage — the 'arrow_ingest' "
              "number above includes bulk-copy column materialization "
              f"(zero_copy={zero_copy_cols}, bulk_copy={bulk_copy_cols}, "
              f"buffer_identity={buffer_identical})", file=sys.stderr)
        raise SystemExit(1)
    return results


def gen(path, rows=1_000_000, features=28, fmt="libsvm"):
    """Synthetic HIGGS-like text file for benchmarking.

    ``fmt``: ``libsvm`` (``label j:v ...``), ``libfm`` (``label j:j:v ...``
    field==index triples) or ``csv`` (``label,v,...``) — the same data in
    each syntax so parser A/Bs compare like against like.
    """
    import numpy as np

    rows, features = int(rows), int(features)
    rng = np.random.RandomState(0)
    with open(path, "w") as f:
        for start in range(0, rows, 10000):
            n = min(10000, rows - start)
            x = rng.randn(n, features)
            y = rng.randint(0, 2, n)
            lines = []
            for i in range(n):
                if fmt == "csv":
                    row = ",".join(f"{x[i, j]:.4f}" for j in range(features))
                    lines.append(f"{y[i]},{row}")
                elif fmt == "libfm":
                    feats = " ".join(f"{j}:{j}:{x[i, j]:.4f}"
                                     for j in range(features))
                    lines.append(f"{y[i]} {feats}")
                else:
                    feats = " ".join(f"{j}:{x[i, j]:.4f}"
                                     for j in range(features))
                    lines.append(f"{y[i]} {feats}")
            f.write("\n".join(lines) + "\n")
    print(f"wrote {rows} {fmt} rows to {path} "
          f"({os.path.getsize(path) / (1 << 20):.1f} MB)")


def genrec(path, records=100_000, nbytes=600):
    """Fixed-size binary records in a .rec file (ImageNet-shard stand-in)."""
    import numpy as np

    from dmlc_core_tpu.io.recordio import RecordIOWriter
    from dmlc_core_tpu.io.stream import create_stream

    records, nbytes = int(records), int(nbytes)
    rng = np.random.RandomState(0)
    with create_stream(path, "w") as fo:
        writer = RecordIOWriter(fo)
        for start in range(0, records, 4096):
            n = min(4096, records - start)
            blob = rng.bytes(n * nbytes)
            for i in range(n):
                writer.write_record(blob[i * nbytes:(i + 1) * nbytes])
    print(f"wrote {records} x {nbytes}B records to {path} "
          f"({os.path.getsize(path) / (1 << 20):.1f} MB)")


def bench_infeed(uri, record_bytes=600, batch=256):
    """RecordIO shard -> ThreadedIter chunks -> batched device arrays
    (BASELINE.json config: "RecordIO ThreadedIter -> TPU infeed").

    Measures end-to-end bytes/sec landed on the default device, overlapping
    host decode with device transfer via an in-flight handle.
    """
    import jax
    import numpy as np

    from dmlc_core_tpu.device import init_device
    from dmlc_core_tpu.io.input_split import create_input_split
    from dmlc_core_tpu.io.recordio import RecordIOChunkReader
    from dmlc_core_tpu.utils.profiler import ThroughputMeter

    init_device()
    record_bytes, batch = int(record_bytes), int(batch)
    device = jax.devices()[0]
    split = create_input_split(uri, 0, 1, type="recordio")
    meter = ThroughputMeter("infeed")
    pending = None
    nrec = 0

    def flush(part):
        # one host copy (contiguous snapshot) straight into device_put; the
        # previous transfer drains while this chunk keeps decoding
        nonlocal pending
        arr = jax.device_put(np.ascontiguousarray(part), device)
        if pending is not None:
            pending.block_until_ready()
        pending = arr

    from dmlc_core_tpu import native_bridge

    while True:
        chunk = split.next_chunk()
        if chunk is None:
            break
        rows = None
        if native_bridge.available():
            head, plen, escaped, _, _ = native_bridge.recordio_scan(
                chunk, 0, len(chunk))
            if (len(head) > 1 and not escaped.any()
                    and (plen == record_bytes).all()):
                stride = int(head[1] - head[0])
                if (np.diff(head) == stride).all():
                    # fixed-size unescaped records at uniform stride: a
                    # zero-copy strided view instead of a per-record loop
                    arr = np.frombuffer(chunk, dtype=np.uint8)
                    rows = np.lib.stride_tricks.as_strided(
                        arr[int(head[0]) + 8:],
                        shape=(len(head), record_bytes),
                        strides=(stride, 1))
        if rows is None:
            reader = RecordIOChunkReader(chunk)
            out = []
            while True:
                rec = reader.next_record()
                if rec is None:
                    break
                src = np.frombuffer(rec, dtype=np.uint8)
                if len(src) != record_bytes:
                    raise ValueError(
                        f"record of {len(src)}B does not match "
                        f"record_bytes={record_bytes}; pass the actual size")
                out.append(src)
            rows = np.stack(out) if out else np.empty((0, record_bytes),
                                                      np.uint8)
        for start in range(0, len(rows), batch):
            part = rows[start:start + batch]
            nrec += len(part)
            flush(part)
            meter.add(part.size, nrows=len(part))
    if pending is not None:
        pending.block_until_ready()
    split.close()
    print(f"{nrec} records -> {jax.devices()[0]}; {meter.summary()}")


def main():
    if len(sys.argv) < 3 and sys.argv[1:] not in (["cache-ab"],
                                                  ["columnar-ab"],
                                                  ["fleet-ab"]):
        print(__doc__)   # the -ab harnesses are self-contained; everything
        return 2         # else needs at least a URI/path argument
    cmd, args = sys.argv[1], sys.argv[2:]
    {"split": bench_split, "parser": bench_parser,
     "parser-ab": bench_parser_ab, "cache-ab": bench_cache_ab,
     "columnar-ab": bench_columnar_ab, "fleet-ab": bench_fleet_ab,
     "gen": gen, "genrec": genrec, "infeed": bench_infeed}[cmd](*args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
