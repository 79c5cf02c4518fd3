#!/usr/bin/env python
"""Serving load harness: the SLO proof and the knee-curve capture.

    python benchmarks/bench_serving.py smoke [--out slo.json]
        [--fault-plan benchmarks/serving_fault_plan.json | none]
    python benchmarks/bench_serving.py knee [--out knee.json]
        [--qps 50,100,200] [--knobs 1:0.5,8:2,32:5] [--duration 3]

    python benchmarks/bench_serving.py lifecycle [--out lifecycle.json]
        [--fault-plan benchmarks/lifecycle_fault_plan.json | none]
        [--swaps 3] [--qps 80] [--duration 5]

``smoke`` is the CI gate (docs/serving.md "SLO methodology"): it starts an
in-process scoring server, drives open-loop traffic through an **active
fault plan** (injected request stalls, a 503 storm, a queue stall, one
killed predict call), and exits non-zero unless every request either
completed or was shed with a structured 503 — ``crashed == 0`` — and the
faults demonstrably fired.  The JSON report it writes is the artifact.

``knee`` sweeps offered load across 2-3 ``max_batch:max_delay_ms`` knob
settings and records client-side latency quantiles per point — the
latency/throughput knee curve committed under benchmarks/results/.

``lifecycle`` is the hot-swap campaign gate (docs/serving.md "Model
lifecycle"): a watched model slot serves open-loop traffic through a 503
storm while a trainer thread publishes new checkpoint versions —
including ONE whose validation is killed by the fault plan — and the run
exits non-zero unless ``crashed == 0``, ``invalid == 0`` (every 200's
predictions match the model version it names: no request ever saw a
half-swapped model), at least ``--swaps - 1`` swaps completed, and
previous-good kept serving across the rejected candidate.  The report
carries a before/during-swaps latency table.

    python benchmarks/bench_serving.py continuous [--out continuous.json]
        [--fault-plan benchmarks/continuous_fault_plan.json | none]
        [--files 14] [--qps 40] [--duration 75]

``continuous`` is the whole-ring chaos drill (docs/training.md): a REAL
trainer daemon subprocess (``python -m dmlc_core_tpu.train``) consumes a
spool whose label distribution shifts over time, publishing GBDT
checkpoints a watched serving slot hot-swaps under open-loop load.  The
committed plan kills the trainer mid-round (the supervisor relaunches it
and asserts it resumed from the last valid manifest), tears one publish
mid-blob (the trainer's own verify must reject it and re-publish the
same step), and storms the server with injected 503s mid-swap; one spool
file is poisoned (all-NaN features) and must be quarantined, not fatal.
Every 200's predictions are re-scored against a reference runtime built
from the exact checkpoint version the response names (``invalid`` on any
mismatch), and the gate demands ``crashed == 0``, ``invalid == 0``,
>= 2 completed swaps, >= 1 kill survived with correct resume provenance,
>= 1 rejected publish, >= 1 quarantined batch, and the scoring-drift
canary rising with the shifted distribution.

    python benchmarks/bench_serving.py router [--out router.json]
        [--fault-plan benchmarks/router_fault_plan.json | none]
        [--replicas 3] [--qps 50] [--duration 12] [--roll-duration 30]

    python benchmarks/bench_serving.py c10k [--out c10k.json]
        [--transport evloop] [--connections 10000] [--active 32]
        [--churn-per-s 50] [--duration 10]

``c10k`` is the event-loop transport's concurrency proof (docs/serving.md
"Transport"): a REAL server subprocess (two fd budgets: ~10k client
sockets here, ~10k accepted there), an idle keep-alive army of
``--connections`` sockets churning at ``--churn-per-s`` while
``--active`` workers score continuously.  Exits non-zero on any refused
connect, any reset, any idle connection the server dropped early, or an
army that never reached its target.

    python benchmarks/bench_serving.py evloop-ab [--out ab.json]
        [--qps 150] [--duration 6] [--rows 2]

``evloop-ab`` races the two transports at matched offered load and
reports client p50/p99 per transport plus the server-side per-stage p99
attribution (request vs queue vs predict vs transport residue) that
names where any tail difference lives.

``router`` is the multi-replica chaos drill (docs/serving.md
"Multi-replica tier"): a ReplicaFleet of real scoring subprocesses
behind an in-process RouterServer, four storms in sequence —
(1) **kill**: SIGKILL one replica mid-storm while the committed plan
also resets router→replica connects; availability during the kill
window must stay >= 99.5% and the supervisor must relaunch the corpse;
(2) **hedge**: replica 0 loads the plan's ``serve.request`` delay rule
(the straggler) and the same fleet is driven twice — hedging OFF then
ON; the hedged p99 must beat the unhedged p99 and no hedge may ever be
double-counted (loadgen ``accounting``); (3) **saturate**: tiny replica
queues at double qps until every replica sheds, proving the router's
own structured 503 (``all_saturated``, with Retry-After); (4)
**rolling**: ``fleet.rolling_restart()`` drains and restarts every
replica under load — ``crashed == 0`` throughout is the gate.
"""

import argparse
import json
import os
import platform
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

DEFAULT_PLAN = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "serving_fault_plan.json")
LIFECYCLE_PLAN = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "lifecycle_fault_plan.json")
CONTINUOUS_PLAN = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "continuous_fault_plan.json")
ROUTER_PLAN = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "router_fault_plan.json")
NUM_FEATURE = 16


def _host_info():
    return {"cores": os.cpu_count(), "python": platform.python_version(),
            "platform": platform.platform(),
            "jax_platforms": os.environ["JAX_PLATFORMS"]}


def _start_server(max_batch, max_delay_ms, max_queue_bytes=None):
    from dmlc_core_tpu import telemetry
    from dmlc_core_tpu.serve import ScoringServer, build_runtime

    telemetry.enable()
    runtime = build_runtime("linear", NUM_FEATURE)
    return ScoringServer(runtime, max_batch=max_batch,
                         max_delay_ms=max_delay_ms,
                         max_queue_bytes=max_queue_bytes).start()


def run_smoke(args) -> int:
    from dmlc_core_tpu import fault
    from dmlc_core_tpu.serve.loadgen import run_load

    plan_path = args.fault_plan
    plan_active = plan_path.lower() != "none"
    if plan_active:
        with open(plan_path, encoding="utf-8") as f:
            fault.configure(f.read())
    server = _start_server(max_batch=32, max_delay_ms=2.0)
    try:
        report = run_load(server.url, qps=args.qps, duration_s=args.duration,
                          num_feature=NUM_FEATURE, rows_per_request=2,
                          seed=7, timeout_s=8.0)
    finally:
        server.close()
    report["fault_plan"] = plan_path if plan_active else None
    report["host"] = _host_info()
    fired = [(site, kind) for site, kind, _ in fault.fires()]
    report["faults_fired"] = sorted(set(fired))

    counts = report["counts"]
    failures = []
    if counts["ok"] == 0:
        failures.append("no request succeeded")
    if counts["crashed"] or counts["error"]:
        failures.append(
            f"{counts['crashed']} crashed + {counts['error']} unstructured "
            "errors — the degradation contract is broken")
    if plan_active:
        if counts["shed"] == 0:
            failures.append("fault plan active but nothing was shed "
                            "(plan not reaching the server?)")
        if ("serve.predict", "error") not in fired:
            failures.append("the killed-predict fault never fired")
        if not any(site == "serve.queue" for site, _ in fired):
            failures.append("the queue-stall fault never fired")
    report["slo_ok"] = not failures
    report["slo_failures"] = failures

    if args.out:
        with open(args.out, "w", encoding="utf-8") as f:
            json.dump(report, f, indent=1, sort_keys=True)
    print(json.dumps(report, indent=1, sort_keys=True))
    lat = report["latency_ms"]
    print(f"\nSLO smoke: {counts['ok']} ok / {counts['shed']} shed / "
          f"{counts['timeout']} timeout / {counts['crashed']} crashed "
          f"of {report['requests']} @ {args.qps} qps offered; "
          f"p50={lat['p50']}ms p99={lat['p99']}ms "
          f"shed_rate={report['shed_rate']}")
    for msg in failures:
        print(f"SLO FAILURE: {msg}")
    if plan_active:
        print(f"faults fired: {report['faults_fired']}")
    return 0 if not failures else 1


def run_knee(args) -> int:
    from dmlc_core_tpu import telemetry
    from dmlc_core_tpu.serve.loadgen import run_load

    qps_list = [float(q) for q in args.qps_list.split(",")]
    knobs = []
    for spec in args.knobs.split(","):
        batch, delay = spec.split(":")
        knobs.append((int(batch), float(delay)))
    runs = []
    for max_batch, delay_ms in knobs:
        for qps in qps_list:
            telemetry.reset()  # fresh server-side histograms per point
            server = _start_server(max_batch=max_batch,
                                   max_delay_ms=delay_ms)
            try:
                rep = run_load(server.url, qps=qps,
                               duration_s=args.duration,
                               num_feature=NUM_FEATURE,
                               rows_per_request=args.rows, seed=11)
            finally:
                server.close()
            lat = rep["latency_ms"]
            runs.append({"max_batch": max_batch, "max_delay_ms": delay_ms,
                         "offered_qps": qps,
                         "achieved_qps": rep["achieved_qps"],
                         "shed_rate": rep["shed_rate"],
                         "counts": rep["counts"],
                         "latency_ms": lat,
                         "server": rep.get("server")})
            print(f"batch={max_batch:<3} delay={delay_ms:<4}ms "
                  f"offered={qps:<6g} achieved={rep['achieved_qps']:<7g} "
                  f"p50={lat['p50']}ms p99={lat['p99']}ms "
                  f"shed={rep['shed_rate']}")
    out = {"host": _host_info(), "num_feature": NUM_FEATURE,
           "rows_per_request": args.rows, "duration_s": args.duration,
           "runs": runs}
    if args.out:
        with open(args.out, "w", encoding="utf-8") as f:
            json.dump(out, f, indent=1, sort_keys=True)
        print(f"wrote {args.out}")
    return 0


def _launch_server_subprocess(extra_args=(), extra_env=None):
    """A REAL scoring-server subprocess on an ephemeral port (the c10k
    drill needs two fd budgets: ~10k client sockets here, ~10k accepted
    sockets there — one process cannot hold both under the rlimit).
    Scrapes the stable ``serving <name> on <url>`` line for the URL."""
    import subprocess
    import threading

    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
    env.update(extra_env or {})
    cmd = [sys.executable, "-m", "dmlc_core_tpu.serve", "--model",
           "linear", "--num-feature", str(NUM_FEATURE), "--port", "0",
           *extra_args]
    proc = subprocess.Popen(cmd, cwd=repo_root, env=env,
                            stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True)
    url = None
    for line in proc.stdout:
        if line.startswith("serving ") and " on " in line:
            url = line.split(" on ", 1)[1].split()[0]
            break
    if url is None:
        proc.kill()
        raise RuntimeError("server subprocess died before binding")
    # keep draining stdout so the child never blocks on a full pipe
    threading.Thread(target=lambda: proc.stdout.read(),
                     daemon=True).start()
    return proc, url


def _stop_server_subprocess(proc):
    import signal as _signal

    proc.send_signal(_signal.SIGTERM)
    try:
        proc.wait(30)
    except Exception:
        proc.kill()
        proc.wait(10)


def run_c10k(args) -> int:
    """The 10k-concurrent-connections proof: a real evloop server
    subprocess, an idle keep-alive army of --connections sockets churning
    while --active workers score continuously.  Gate: zero refused
    connects, zero resets, zero idle connections dropped early, and the
    army actually reached the target."""
    from dmlc_core_tpu.serve.loadgen import run_churn

    proc, url = _launch_server_subprocess(
        extra_args=["--transport", args.transport, "--max-batch", "32",
                    "--max-delay-ms", "2.0"],
        extra_env={"DMLC_SERVE_IDLE_S": str(max(120.0,
                                                args.duration * 4))})
    try:
        report = run_churn(url, connections=args.connections,
                           duration_s=args.duration,
                           num_feature=NUM_FEATURE, active=args.active,
                           churn_per_s=args.churn_per_s, seed=5)
    finally:
        _stop_server_subprocess(proc)
    report["transport"] = args.transport
    report["host"] = _host_info()

    conns = report["connections"]
    failures = []
    if conns["refused"]:
        failures.append(f"{conns['refused']} connects refused — the "
                        "accept path shed at the kernel")
    if conns["resets"]:
        failures.append(f"{conns['resets']} connections reset "
                        "mid-request")
    if conns["closed_by_server"]:
        failures.append(f"{conns['closed_by_server']} idle keep-alive "
                        "connections dropped before the window closed")
    if conns["peak_open"] < args.connections:
        failures.append(f"peak open {conns['peak_open']} never reached "
                        f"the {args.connections} target")
    if report["requests"]["ok"] == 0:
        failures.append("no request scored while the army held")
    report["c10k_ok"] = not failures
    report["c10k_failures"] = failures

    if args.out:
        with open(args.out, "w", encoding="utf-8") as f:
            json.dump(report, f, indent=1, sort_keys=True)
    print(json.dumps(report, indent=1, sort_keys=True))
    print(f"\nc10k[{args.transport}]: peak {conns['peak_open']} open "
          f"({conns['churned']} churned), {conns['refused']} refused, "
          f"{conns['resets']} reset, {conns['closed_by_server']} dropped; "
          f"{report['requests']['ok']} scored @ "
          f"p99={report['latency_ms']['p99']}ms")
    for msg in failures:
        print(f"C10K FAILURE: {msg}")
    return 0 if not failures else 1


def _stage_p99_ms(server_stats):
    """Per-stage p99s (ms) from a /stats snapshot: where the tail
    actually lives.  transport_ms = whole-request p99 minus the
    queue+predict p99s — parse, socket writes, and scheduling."""
    stages = {}
    for key, val in (server_stats or {}).get("metrics", {}).items():
        if not isinstance(val, dict) or "p99" not in val:
            continue
        name = key.split("{", 1)[0]
        short = {"dmlc_serve_request_seconds": "request",
                 "dmlc_serve_queue_seconds": "queue",
                 "dmlc_serve_predict_seconds": "predict"}.get(name)
        if short is None:
            continue
        stages[short] = max(stages.get(short, 0.0), val["p99"] * 1e3)
    if "request" in stages:
        stages["transport"] = round(
            stages["request"] - stages.get("queue", 0.0)
            - stages.get("predict", 0.0), 3)
    return {k: round(v, 3) for k, v in stages.items()}


def run_evloop_ab(args) -> int:
    """A/B the two transports at matched offered load: same qps, same
    duration, same seed — client p50/p99 plus the server-side per-stage
    p99 attribution (request vs queue vs predict vs transport) that
    names where any tail difference comes from."""
    from dmlc_core_tpu import telemetry
    from dmlc_core_tpu.serve.loadgen import run_load

    runs = {}
    for transport in ("threaded", "evloop"):
        telemetry.reset()  # fresh server-side histograms per leg
        from dmlc_core_tpu.serve import ScoringServer, build_runtime

        telemetry.enable()
        server = ScoringServer(build_runtime("linear", NUM_FEATURE),
                               max_batch=32, max_delay_ms=2.0,
                               transport=transport).start()
        try:
            rep = run_load(server.url, qps=args.qps,
                           duration_s=args.duration,
                           num_feature=NUM_FEATURE,
                           rows_per_request=args.rows, seed=17,
                           timeout_s=8.0)
        finally:
            server.close()
        runs[transport] = {
            "counts": rep["counts"],
            "connections": rep["connections"],
            "achieved_qps": rep["achieved_qps"],
            "latency_ms": rep["latency_ms"],
            "latency_all_ms": rep["latency_all_ms"],
            "slowest_traces": rep["slowest_traces"],
            "stage_p99_ms": _stage_p99_ms(rep.get("server")),
        }
        lat = rep["latency_ms"]
        print(f"{transport:<9} offered={args.qps:g} "
              f"achieved={rep['achieved_qps']:<7g} p50={lat['p50']}ms "
              f"p99={lat['p99']}ms stages={runs[transport]['stage_p99_ms']}")

    report = {"host": _host_info(), "qps": args.qps,
              "duration_s": args.duration, "rows_per_request": args.rows,
              "num_feature": NUM_FEATURE, "runs": runs}
    failures = []
    for transport, r in runs.items():
        c = r["counts"]
        if c["crashed"] or c["error"]:
            failures.append(f"{transport}: {c['crashed']} crashed + "
                            f"{c['error']} unstructured errors")
        if c["ok"] == 0:
            failures.append(f"{transport}: no request succeeded")
    report["ab_ok"] = not failures
    report["ab_failures"] = failures
    if args.out:
        with open(args.out, "w", encoding="utf-8") as f:
            json.dump(report, f, indent=1, sort_keys=True)
        print(f"wrote {args.out}")
    for msg in failures:
        print(f"AB FAILURE: {msg}")
    return 0 if not failures else 1


def _bias_for(step: int) -> float:
    """Per-version bias for the campaign's w=0 logistic model: every
    prediction equals sigmoid(bias(step)), so the prediction value IS
    the model version — the half-swapped-model detector."""
    return -2.0 + 0.5 * step


def run_lifecycle(args) -> int:
    import math
    import tempfile
    import threading
    import time

    import numpy as np

    from dmlc_core_tpu import fault, telemetry
    from dmlc_core_tpu.bridge.checkpoint import CheckpointManager
    from dmlc_core_tpu.serve import (CheckpointWatcher, ModelRegistry,
                                     ScoringServer, build_runtime,
                                     runtime_builder)
    from dmlc_core_tpu.serve.loadgen import run_load

    telemetry.enable()
    plan_path = args.fault_plan
    plan_active = plan_path.lower() != "none"
    if plan_active:
        with open(plan_path, encoding="utf-8") as f:
            fault.configure(f.read())

    ckpt_dir = tempfile.mkdtemp(prefix="lifecycle-ckpt-")
    mgr = CheckpointManager(ckpt_dir, keep=args.swaps + 2)

    def publish(step):
        mgr.save(step, {"w": np.zeros(NUM_FEATURE, np.float32),
                        "b": np.float32(_bias_for(step))}, async_=False)

    def check(payload, rows=None):
        v = payload.get("version")
        if not isinstance(v, int):
            return False
        want = 1.0 / (1.0 + math.exp(-_bias_for(v)))
        return all(abs(p - want) < 1e-5 for p in payload["predictions"])

    publish(1)
    registry = ModelRegistry()
    registry.add("champion",
                 build_runtime("linear", NUM_FEATURE,
                               checkpoint=mgr.step_uri(1)),
                 version=1, max_batch=32, max_delay_ms=2.0, default=True)
    last_step = 1 + args.swaps
    report = {"fault_plan": plan_path if plan_active else None,
              "host": _host_info(), "swaps_published": args.swaps,
              "checkpoint_dir": ckpt_dir}
    with ScoringServer(registry, request_timeout_s=8.0) as server:
        watcher = CheckpointWatcher(registry, "champion", ckpt_dir,
                                    runtime_builder("linear", NUM_FEATURE),
                                    poll_s=0.25, manager=mgr)
        with watcher:
            # phase A: steady state, no swaps — the "before" latency
            report["before"] = run_load(
                server.url, qps=args.qps, duration_s=args.duration / 2,
                num_feature=NUM_FEATURE, rows_per_request=2, seed=7,
                timeout_s=8.0, model="champion", response_check=check)

            # phase B: the trainer publishes a new version per
            # swap-interval while the storm + load run — paced on the
            # watcher's progress odometer (swaps + rejections), because
            # the watcher is latest-wins: un-paced publishes would
            # legitimately skip intermediate steps and the plan's
            # validation kill could land on the final one
            def trainer():
                for step in range(2, last_step + 1):
                    time.sleep(args.swap_interval)
                    progress = (watcher.swaps_completed
                                + watcher.rejections)
                    publish(step)
                    deadline = time.monotonic() + 30
                    while (watcher.swaps_completed + watcher.rejections
                           <= progress and time.monotonic() < deadline):
                        time.sleep(0.05)

            t = threading.Thread(target=trainer)
            t.start()
            report["during"] = run_load(
                server.url, qps=args.qps, duration_s=args.duration,
                num_feature=NUM_FEATURE, rows_per_request=2, seed=11,
                timeout_s=8.0, model="champion", response_check=check)
            t.join(30)
            deadline = time.monotonic() + 15
            while (watcher.swaps_completed < args.swaps - 1
                   and time.monotonic() < deadline):
                time.sleep(0.2)
            report["swaps_completed"] = watcher.swaps_completed
            report["final_version"] = registry.get("champion").version
    fired = [(site, kind) for site, kind, _ in fault.fires()]
    report["faults_fired"] = sorted(set(fired))

    failures = []
    for phase in ("before", "during"):
        c = report[phase]["counts"]
        if c["crashed"] or c["error"]:
            failures.append(f"{phase}: {c['crashed']} crashed + "
                            f"{c['error']} unstructured errors")
        if c["invalid"]:
            failures.append(
                f"{phase}: {c['invalid']} responses whose predictions do "
                "not match the version that claims to have served them — "
                "a half-swapped or mixed-version model answered")
        if c["ok"] == 0:
            failures.append(f"{phase}: no request succeeded")
    # the plan kills exactly one validation: one candidate is rejected,
    # every other published step must have swapped in
    want_swaps = args.swaps - (1 if plan_active else 0)
    if report["swaps_completed"] < max(2, want_swaps):
        failures.append(
            f"only {report['swaps_completed']} hot swaps completed "
            f"(wanted >= {max(2, want_swaps)})")
    if report["final_version"] != last_step:
        failures.append(
            f"final version {report['final_version']} != last published "
            f"good step {last_step} — previous-good/recovery broke")
    if plan_active:
        if ("serve.swap", "error") not in fired:
            failures.append("the validation-kill fault never fired")
        if not any(s == "serve.request" for s, _ in fired):
            failures.append("the 503 storm never fired")
        shed = (report["before"]["counts"]["shed"]
                + report["during"]["counts"]["shed"])
        if shed == 0:
            failures.append("storm active but nothing shed")
    report["slo_ok"] = not failures
    report["slo_failures"] = failures

    if args.out:
        with open(args.out, "w", encoding="utf-8") as f:
            json.dump(report, f, indent=1, sort_keys=True)
    print(json.dumps({k: v for k, v in report.items()
                      if k != "checkpoint_dir"}, indent=1, sort_keys=True))
    print("\nlifecycle campaign: "
          f"{report['swaps_completed']} hot swaps, final version "
          f"v{report['final_version']}")
    print(f"{'phase':<8} {'ok':>5} {'shed':>5} {'invalid':>7} "
          f"{'crashed':>7} {'p50ms':>8} {'p99ms':>8}")
    for phase in ("before", "during"):
        c = report[phase]["counts"]
        lat = report[phase]["latency_ms"]
        print(f"{phase:<8} {c['ok']:>5} {c['shed']:>5} {c['invalid']:>7} "
              f"{c['crashed']:>7} {str(lat['p50']):>8} "
              f"{str(lat['p99']):>8}")
    for msg in failures:
        print(f"LIFECYCLE FAILURE: {msg}")
    return 0 if not failures else 1


def run_continuous(args) -> int:
    import subprocess
    import tempfile
    import threading
    import time

    import numpy as np

    from dmlc_core_tpu import fault, telemetry
    from dmlc_core_tpu.bridge.checkpoint import CheckpointManager
    from dmlc_core_tpu.serve import (CheckpointWatcher, ModelRegistry,
                                     ScoringServer, build_runtime,
                                     runtime_builder)
    from dmlc_core_tpu.serve.loadgen import run_load
    from dmlc_core_tpu.train.source import DONE_SENTINEL

    telemetry.enable()
    plan_path = args.fault_plan
    plan_active = plan_path.lower() != "none"
    if plan_active:
        # the driver loads the same committed plan the trainer subprocess
        # gets via DMLC_FAULT_PLAN: serve.* rules fire here, train.* rules
        # fire in the daemon — one plan file describes the whole drill
        with open(plan_path, encoding="utf-8") as f:
            fault.configure(f.read())

    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spool = tempfile.mkdtemp(prefix="continuous-spool-")
    ckpt = tempfile.mkdtemp(prefix="continuous-ckpt-")
    mgr = CheckpointManager(ckpt, keep=args.files)
    rng = np.random.default_rng(5)
    n_files = args.files
    poison_index = 7 if n_files > 8 else n_files // 2

    def label_rate(i: int) -> float:
        # the distribution shift the drift canary must track
        return 0.12 + (0.88 - 0.12) * i / max(1, n_files - 1)

    def write_spool_file(i: int) -> None:
        name = f"part-{i:04d}.libsvm"
        tmp = os.path.join(spool, f".tmp-{name}")
        with open(tmp, "w", encoding="utf-8") as f:
            for _ in range(200):
                if i == poison_index:
                    feats = " ".join(f"{j}:nan" for j in range(NUM_FEATURE))
                    f.write(f"0 {feats}\n")
                    continue
                x = rng.normal(size=NUM_FEATURE)
                y = int(rng.random() < label_rate(i))
                feats = " ".join(f"{j}:{x[j]:.5f}"
                                 for j in range(NUM_FEATURE))
                f.write(f"{y} {feats}\n")
        # atomic rename: the daemon's DirectorySource must never parse a
        # half-written spool file (".tmp-*" names are skipped by contract)
        os.replace(tmp, os.path.join(spool, name))

    # the serving side, filled in once the first checkpoint lands; the
    # spool writer paces itself on it so the ring stays coupled on any
    # machine speed (the lifecycle-campaign pacing pattern)
    serving = {"registry": None, "watcher": None}

    def progress() -> int:
        # serving version once the slot exists, else the newest published
        # step — so pacing works during bootstrap too
        registry = serving["registry"]
        if registry is not None:
            return registry.get("champion").version
        step, _ = mgr.latest_valid()
        return step or 0

    def writer() -> None:
        for i in range(n_files):
            write_spool_file(i)
            if i % 2 == 1:
                # each file pair funds one publish (4 rounds): hold the
                # next pair until the ring absorbed this one, so the
                # drift canary sees the shift arrive — bounded wait, a
                # killed trainer must not wedge the spool
                v0 = progress()
                deadline = time.monotonic() + 10
                while (progress() <= v0
                       and time.monotonic() < deadline):
                    time.sleep(0.1)
        open(os.path.join(spool, DONE_SENTINEL), "w").close()

    incarnations = []

    def launch_trainer(inc: int):
        env = dict(os.environ)
        env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH",
                                                             "")
        if plan_active:
            env["DMLC_FAULT_PLAN"] = "@" + os.path.abspath(plan_path)
        state_path = os.path.join(ckpt, f"state-{inc}.json")
        cmd = [sys.executable, "-m", "dmlc_core_tpu.train",
               "--data", spool, "--ckpt", ckpt,
               "--num-feature", str(NUM_FEATURE),
               "--rounds-per-batch", "2", "--publish-every-rounds", "4",
               "--poll-s", "0.1", "--keep", str(args.files),
               "--learning-rate", "0.3", "--max-depth", "3",
               "--num-bins", "32", "--exit-when-idle",
               "--incarnation", str(inc), "--state-file", state_path]
        proc = subprocess.run(cmd, cwd=repo_root, env=env,
                              capture_output=True, text=True, timeout=600)
        state = None
        if os.path.exists(state_path):
            with open(state_path, encoding="utf-8") as f:
                state = json.load(f)
        return proc.returncode, state, proc.stderr[-2000:]

    def supervise() -> None:
        inc = 1
        while inc <= 5:
            # snapshot what a correct resume must restore BEFORE the
            # relaunch — the provenance the gate checks
            expect = None
            if inc > 1:
                expect, _ = mgr.latest_valid(verify=True,
                                             skip_unpublished=True)
            rc, state, stderr = launch_trainer(inc)
            incarnations.append({"incarnation": inc, "rc": rc,
                                 "expected_resume": expect,
                                 "state": state, "stderr_tail": stderr})
            print(f"trainer incarnation {inc} exited rc={rc} "
                  f"state={state}")
            if rc != 43:  # 43 = the plan's injected mid-round kill
                return
            inc += 1

    threading.Thread(target=writer, daemon=True).start()
    sup = threading.Thread(target=supervise)
    sup.start()

    # bootstrap: wait for the daemon's first valid manifest, then serve it
    deadline = time.monotonic() + 240
    first_step = None
    while time.monotonic() < deadline:
        first_step, _ = mgr.latest_valid(verify=True)
        if first_step is not None:
            break
        time.sleep(0.2)
    report = {"fault_plan": plan_path if plan_active else None,
              "host": _host_info(), "files": n_files,
              "poison_index": poison_index, "checkpoint_dir": ckpt}
    if first_step is None:
        sup.join(60)
        report["slo_ok"] = False
        report["slo_failures"] = ["trainer never published a valid "
                                  "checkpoint"]
        report["incarnations"] = incarnations
        print(json.dumps(report, indent=1, sort_keys=True))
        return 1

    registry = ModelRegistry()
    registry.add("champion",
                 build_runtime("gbdt", NUM_FEATURE,
                               checkpoint=mgr.step_uri(first_step)),
                 version=first_step, max_batch=32, max_delay_ms=2.0,
                 default=True)

    # reference check: rebuild THE version each 200 names from its own
    # checkpoint and re-score this request's rows — any mismatch is a
    # response served by a model other than the one it claims (invalid)
    ref_lock = threading.Lock()
    ref_runtimes = {}

    def check(payload, rows=None):
        v = payload.get("version")
        if not isinstance(v, int) or rows is None:
            return False
        with ref_lock:
            rt = ref_runtimes.get(v)
            if rt is None:
                try:
                    rt = build_runtime("gbdt", NUM_FEATURE,
                                       checkpoint=mgr.step_uri(v))
                except Exception:
                    return False  # a version that is not in the store
                ref_runtimes[v] = rt
            want = np.asarray(
                rt.predict(np.asarray(rows, np.float32))).reshape(-1)
        got = np.asarray(payload["predictions"], np.float64).reshape(-1)
        return got.shape == want.shape \
            and bool(np.allclose(got, want, atol=1e-4))

    with ScoringServer(registry, request_timeout_s=8.0) as server:
        watcher = CheckpointWatcher(registry, "champion", ckpt,
                                    runtime_builder("gbdt", NUM_FEATURE),
                                    poll_s=0.25, manager=mgr)
        with watcher:
            serving["registry"] = registry
            serving["watcher"] = watcher
            report["load"] = run_load(
                server.url, qps=args.qps, duration_s=args.duration,
                num_feature=NUM_FEATURE, rows_per_request=2, seed=13,
                timeout_s=8.0, model="champion", response_check=check)
            sup.join(300)
            # let the watcher absorb whatever the last incarnation
            # published after the load window closed
            last_step, _ = mgr.latest_valid()
            deadline = time.monotonic() + 30
            while (registry.get("champion").version < (last_step or 0)
                   and time.monotonic() < deadline):
                time.sleep(0.2)
            report["swaps_completed"] = watcher.swaps_completed
            report["watcher_rejections"] = watcher.rejections
            report["final_version"] = registry.get("champion").version
    report["last_step"] = last_step
    report["incarnations"] = [
        {k: v for k, v in inc.items() if k != "stderr_tail"}
        for inc in incarnations]
    fired = [(site, kind) for site, kind, _ in fault.fires()]
    report["faults_fired"] = sorted(set(fired))

    kills = sum(1 for inc in incarnations if inc["rc"] == 43)
    rejected = sum((inc["state"] or {}).get("publish_rejections", 0)
                   for inc in incarnations)
    quarantined = sum((inc["state"] or {}).get("quarantined", 0)
                      for inc in incarnations)
    report["kills"] = kills
    report["publish_rejections"] = rejected
    report["quarantined"] = quarantined

    failures = []
    c = report["load"]["counts"]
    if c["crashed"] or c["error"]:
        failures.append(f"{c['crashed']} crashed + {c['error']} "
                        "unstructured errors — degradation contract broken")
    if c["invalid"]:
        failures.append(
            f"{c['invalid']} responses whose predictions do not re-score "
            "under the checkpoint version they claim served them")
    if c["ok"] == 0:
        failures.append("no request succeeded")
    if not incarnations or incarnations[-1]["rc"] != 0:
        failures.append("the trainer ring never completed cleanly "
                        f"(incarnations: {[i['rc'] for i in incarnations]})")
    for inc in incarnations:
        if inc["rc"] not in (0, 43):
            failures.append(f"incarnation {inc['incarnation']} died with "
                            f"unexpected rc={inc['rc']}")
        if (inc["incarnation"] > 1 and inc["state"] is not None
                and inc["state"].get("resumed_from")
                != inc["expected_resume"]):
            failures.append(
                f"incarnation {inc['incarnation']} resumed from "
                f"{inc['state'].get('resumed_from')}, not the last valid "
                f"manifest {inc['expected_resume']}")
    if report["swaps_completed"] < 2:
        failures.append(f"only {report['swaps_completed']} hot swaps "
                        "completed (wanted >= 2)")
    if report["final_version"] != last_step:
        failures.append(f"final version {report['final_version']} != "
                        f"last published step {last_step}")
    if plan_active:
        if kills < 1:
            failures.append("the mid-round trainer kill never fired")
        if rejected < 1:
            failures.append("the torn publish was never rejected "
                            "(truncate rule not reaching the verify?)")
        if ("serve.request", "http_status") not in fired:
            failures.append("the 503 storm never fired")
        if c["shed"] == 0:
            failures.append("storm active but nothing shed")
    if quarantined < 1:
        failures.append("the poisoned spool file was never quarantined")
    series = report["load"]["drift"]["series"]
    if len(series) < 6:
        failures.append(f"drift canary has only {len(series)} windows")
    else:
        third = len(series) // 3
        early = sum(w["mean_prediction"] for w in series[:third]) / third
        late = sum(w["mean_prediction"]
                   for w in series[-third:]) / third
        report["drift_early"] = round(early, 4)
        report["drift_late"] = round(late, 4)
        if late - early < 0.15:
            failures.append(
                f"scoring drift {early:.3f} -> {late:.3f} does not track "
                "the shifted label distribution (wanted rise >= 0.15)")
    report["slo_ok"] = not failures
    report["slo_failures"] = failures

    if args.out:
        with open(args.out, "w", encoding="utf-8") as f:
            json.dump(report, f, indent=1, sort_keys=True)
    print(json.dumps({k: v for k, v in report.items()
                      if k not in ("checkpoint_dir", "incarnations")},
                     indent=1, sort_keys=True))
    print(f"\ncontinuous ring: {len(incarnations)} trainer "
          f"incarnation(s), {kills} kill(s) survived, "
          f"{report['swaps_completed']} hot swaps, final v"
          f"{report['final_version']}, {rejected} rejected publish(es), "
          f"{quarantined} quarantined batch(es)")
    if "drift_early" in report:
        print(f"scoring drift: {report['drift_early']} -> "
              f"{report['drift_late']} over {len(series)} windows")
    for msg in failures:
        print(f"CONTINUOUS FAILURE: {msg}")
    return 0 if not failures else 1


def run_router(args) -> int:
    import math
    import tempfile
    import threading
    import time

    import numpy as np

    from dmlc_core_tpu import fault, telemetry
    from dmlc_core_tpu.bridge.checkpoint import CheckpointManager
    from dmlc_core_tpu.serve.fleet import ReplicaFleet
    from dmlc_core_tpu.serve.loadgen import OUTCOMES, run_load
    from dmlc_core_tpu.serve.router import RouterServer

    telemetry.enable()
    plan_path = args.fault_plan
    plan_active = plan_path.lower() != "none"
    if plan_active:
        with open(plan_path, encoding="utf-8") as f:
            fault.configure(f.read())

    def counter(name, **labels):
        """Sum of a dmlc counter's children whose labels match."""
        total = 0.0
        for fam in telemetry.get_registry().families():
            if fam.name != name:
                continue
            for key, child in fam.samples():
                kd = dict(key)
                if all(kd.get(k) == v for k, v in labels.items()):
                    total += child.value
        return total

    # every replica serves the SAME w=0 logistic checkpoint: each
    # prediction must equal sigmoid(bias) exactly, and CLI-launched
    # replicas register their slot at version 0 — any other claim, or
    # any other prediction value, is cross-replica skew -> `invalid`
    ckpt_dir = tempfile.mkdtemp(prefix="router-ckpt-")
    mgr = CheckpointManager(ckpt_dir, keep=2)
    mgr.save(1, {"w": np.zeros(NUM_FEATURE, np.float32),
                 "b": np.float32(_bias_for(1))}, async_=False)
    want = 1.0 / (1.0 + math.exp(-_bias_for(1)))

    def check(payload, rows=None):
        if payload.get("version") != 0:
            return False
        return all(abs(p - want) < 1e-5 for p in payload["predictions"])

    log_root = tempfile.mkdtemp(prefix="router-logs-")

    def make_fleet(tag, **overrides):
        kw = dict(model="linear", num_feature=NUM_FEATURE, seed=0,
                  checkpoint=mgr.step_uri(1), max_batch=32,
                  max_delay_ms=2.0, request_timeout_s=8.0,
                  log_dir=os.path.join(log_root, tag), auto_restart=True)
        kw.update(overrides)
        return ReplicaFleet(args.replicas, **kw)

    def make_router(fleet, **overrides):
        kw = dict(probe_interval_s=0.2, try_timeout_s=3.0,
                  request_timeout_s=8.0)
        kw.update(overrides)
        router = RouterServer(fleet.urls, **kw)
        router.start()
        return router

    window_s = 0.5
    report = {"fault_plan": plan_path if plan_active else None,
              "host": _host_info(), "replicas": args.replicas,
              "checkpoint_dir": ckpt_dir, "replica_logs": log_root,
              "phases": {}}
    failures = []

    def gate_counts(phase, load, *, want_ok=True):
        c = load["counts"]
        if c["crashed"] or c["error"]:
            failures.append(
                f"{phase}: {c['crashed']} crashed + {c['error']} "
                "unstructured errors — the degradation contract is broken")
        if c["invalid"]:
            failures.append(
                f"{phase}: {c['invalid']} responses with skewed "
                "predictions — a replica answered with the wrong params")
        if want_ok and c["ok"] == 0:
            failures.append(f"{phase}: no request succeeded")
        if not load["accounting"]["ok"]:
            failures.append(
                f"{phase}: {load['accounting']['recorded']} outcomes "
                f"recorded for {load['accounting']['requests']} requests "
                "— a hedged response was double-delivered")

    # ---- phase 1: SIGKILL one replica mid-storm -------------------------
    # the committed plan's connect-reset rule also fires here: the router
    # must absorb both the corpse and the resets with failover retries
    kill_at = max(2.0, args.duration * 0.35)
    print(f"router/kill: {args.replicas} replicas, SIGKILL r0 at "
          f"t={kill_at:.1f}s of {args.duration:.0f}s...", flush=True)
    fleet = make_fleet("kill")
    fleet.start()
    router = make_router(fleet)
    try:
        killer = threading.Timer(kill_at, fleet.kill, args=(0,))
        killer.daemon = True
        killer.start()
        load = run_load(router.url, qps=args.qps,
                        duration_s=args.duration, num_feature=NUM_FEATURE,
                        rows_per_request=2, seed=19, timeout_s=8.0,
                        response_check=check, drift_window_s=window_s)
        killer.join(10.0)
        time.sleep(2.0)  # let hedge losers finish: their spans must close
        phase = {"load": load, "kill_at_s": kill_at,
                 "launches": fleet.launches(), "router": router.stats()}
    finally:
        router.close()
        fleet.close()
    # availability = structured-answer fraction over the scheduled-time
    # windows that bracket the kill (shed/timeout/rejected all count as
    # answered: the contract is "nothing vanished", not "nothing failed")
    kill_lo, kill_hi = kill_at - window_s, kill_at + 2.0
    windows = [w for w in load["outcome_windows"]["series"]
               if kill_lo <= w["t_s"] <= kill_hi]
    total = sum(sum(w[k] for k in OUTCOMES) for w in windows)
    unanswered = sum(w["crashed"] + w["error"] + w["invalid"]
                     for w in windows)
    availability = (1.0 - unanswered / total) if total else None
    phase["kill_window"] = {
        "t_lo_s": kill_lo, "t_hi_s": kill_hi, "requests": total,
        "unanswered": unanswered,
        "availability": None if availability is None
        else round(availability, 5)}
    report["phases"]["kill"] = phase
    gate_counts("kill", load)
    if availability is None or availability < 0.995:
        failures.append(
            f"kill: availability {availability} < 99.5% during the kill "
            f"window [{kill_lo:.1f}s, {kill_hi:.1f}s]")
    if phase["launches"][0] < 2:
        failures.append("kill: the killed replica was never relaunched")

    # ---- phase 2: straggler replica, hedging OFF then ON ----------------
    # replica 0 loads the committed plan itself: its serve.request delay
    # rule makes it the straggler (the driver holds the same plan but has
    # no serve.request site, so the rule is inert here)
    straggler_env = ({0: {"DMLC_FAULT_PLAN": "@" + os.path.abspath(
        plan_path)}} if plan_active else None)
    print("router/hedge: straggler on r0, unhedged vs hedged...",
          flush=True)
    fleet = make_fleet("hedge", per_replica_env=straggler_env)
    fleet.start()
    hedge_phase = {}
    try:
        for mode, hedged in (("unhedged", False), ("hedged", True)):
            fired0 = counter("dmlc_router_hedges_total", outcome="fired")
            won0 = counter("dmlc_router_hedges_total",
                           outcome="hedge_won")
            router = make_router(fleet, hedge=hedged)
            try:
                load = run_load(
                    router.url, qps=args.qps,
                    duration_s=max(6.0, args.duration * 0.8),
                    num_feature=NUM_FEATURE, rows_per_request=2,
                    seed=23 if hedged else 29, timeout_s=8.0,
                    response_check=check, drift_window_s=window_s)
                time.sleep(2.0)
                hedge_phase[mode] = {
                    "load": load,
                    "hedges_fired": counter("dmlc_router_hedges_total",
                                            outcome="fired") - fired0,
                    "hedges_won": counter("dmlc_router_hedges_total",
                                          outcome="hedge_won") - won0,
                    "hedge_delay_s": router.health()["hedge_delay_s"],
                }
            finally:
                router.close()
    finally:
        fleet.close()
    report["phases"]["hedge"] = hedge_phase
    for mode in ("unhedged", "hedged"):
        gate_counts(f"hedge/{mode}", hedge_phase[mode]["load"])
    if hedge_phase["unhedged"]["hedges_fired"]:
        failures.append("hedge: hedges fired with hedging disabled")
    if plan_active:
        un_p99 = hedge_phase["unhedged"]["load"]["latency_ms"]["p99"]
        he_p99 = hedge_phase["hedged"]["load"]["latency_ms"]["p99"]
        if un_p99 is None or he_p99 is None or he_p99 >= un_p99:
            failures.append(
                f"hedge: hedged p99 {he_p99}ms did not beat the "
                f"straggler's unhedged p99 {un_p99}ms")
        if hedge_phase["hedged"]["hedges_fired"] == 0:
            failures.append("hedge: straggler active but no hedge fired")

    # ---- phase 3: saturate every replica --------------------------------
    # tiny per-replica queues at double qps, with EVERY replica loading
    # the plan: its serve.predict delay rule (matched to this fleet's
    # slot name) holds each batch's admission bytes, so the 2 KiB queues
    # genuinely fill.  Once every replica has answered 503, the router
    # must shed from its OWN admission view — a structured router 503
    # with Retry-After, not a forward
    print("router/saturate: tiny queues at double qps...", flush=True)
    plan_env = ({i: {"DMLC_FAULT_PLAN": "@" + os.path.abspath(plan_path)}
                 for i in range(args.replicas)} if plan_active else None)
    fleet = make_fleet("saturate", model_name="saturated", max_batch=8,
                       max_delay_ms=120.0, max_queue_bytes=2048,
                       per_replica_env=plan_env)
    fleet.start()
    shed0 = counter("dmlc_router_shed_total", reason="all_saturated")
    router = make_router(fleet, hedge=False)
    try:
        load = run_load(router.url, qps=args.qps * 2,
                        duration_s=max(5.0, args.duration * 0.6),
                        num_feature=NUM_FEATURE, rows_per_request=4,
                        seed=31, timeout_s=8.0, response_check=check,
                        drift_window_s=window_s)
        time.sleep(2.0)
        phase = {"load": load,
                 "router_all_saturated_sheds": counter(
                     "dmlc_router_shed_total",
                     reason="all_saturated") - shed0}
    finally:
        router.close()
        fleet.close()
    report["phases"]["saturate"] = phase
    gate_counts("saturate", load, want_ok=False)
    if plan_active:
        if load["counts"]["shed"] == 0:
            failures.append("saturate: nothing was shed at double qps "
                            "against 2 KiB queues")
        if phase["router_all_saturated_sheds"] < 1:
            failures.append(
                "saturate: the router never shed from its own admission "
                "view (no all_saturated 503) — every shed was a forward")

    # ---- phase 4: rolling restart of the whole fleet --------------------
    print("router/rolling: drain+restart every replica under load...",
          flush=True)
    fleet = make_fleet("rolling")
    fleet.start()
    router = make_router(fleet)
    roll = {}

    def roller():
        try:
            time.sleep(1.5)
            fleet.rolling_restart(settle_s=0.6)
            roll["completed"] = True
        except Exception as e:
            roll["error"] = repr(e)

    try:
        t = threading.Thread(target=roller)
        t.start()
        load = run_load(router.url, qps=args.qps,
                        duration_s=args.roll_duration,
                        num_feature=NUM_FEATURE, rows_per_request=2,
                        seed=37, timeout_s=8.0, response_check=check,
                        drift_window_s=window_s)
        t.join(120.0)
        # longer settle than the other phases: this is the last storm, so
        # any forward attempt still in flight when the DRIVER exits would
        # orphan the replica span it parented
        time.sleep(3.5)
        phase = {"load": load, "launches": fleet.launches(),
                 "rolling_completed": bool(roll.get("completed")),
                 "rolling_error": roll.get("error")}
    finally:
        router.close()
        fleet.close()
    report["phases"]["rolling"] = phase
    gate_counts("rolling", load)
    if not phase["rolling_completed"]:
        failures.append(
            f"rolling: restart never completed ({roll.get('error')})")
    short = [i for i, n in enumerate(phase["launches"]) if n < 2]
    if short:
        failures.append(
            f"rolling: replicas {short} were never restarted "
            f"(launches={phase['launches']})")

    fired = [(site, kind) for site, kind, _ in fault.fires()]
    report["faults_fired"] = sorted(set(fired))
    if plan_active and ("serve.router.forward", "reset") not in fired:
        failures.append("the connect-reset fault never fired at "
                        "serve.router.forward")
    report["slo_ok"] = not failures
    report["slo_failures"] = failures

    if args.out:
        with open(args.out, "w", encoding="utf-8") as f:
            json.dump(report, f, indent=1, sort_keys=True)
    print(json.dumps({k: v for k, v in report.items()
                      if k not in ("checkpoint_dir", "replica_logs")},
                     indent=1, sort_keys=True))
    kw = report["phases"]["kill"]["kill_window"]
    print(f"\nrouter chaos: availability "
          f"{kw['availability']} during the kill window, "
          f"{hedge_phase['hedged']['hedges_fired']:.0f} hedges fired "
          f"({hedge_phase['hedged']['hedges_won']:.0f} won), "
          f"{report['phases']['saturate']['router_all_saturated_sheds']:.0f}"
          f" router sheds, launches {report['phases']['rolling']['launches']}")
    rows = [("kill", report["phases"]["kill"]["load"]),
            ("unhedged", hedge_phase["unhedged"]["load"]),
            ("hedged", hedge_phase["hedged"]["load"]),
            ("saturate", report["phases"]["saturate"]["load"]),
            ("rolling", report["phases"]["rolling"]["load"])]
    print(f"{'phase':<9} {'ok':>5} {'shed':>5} {'rejec':>5} {'inval':>5} "
          f"{'crash':>5} {'p50ms':>8} {'p99ms':>8}")
    for name, ld in rows:
        c, lat = ld["counts"], ld["latency_ms"]
        print(f"{name:<9} {c['ok']:>5} {c['shed']:>5} {c['rejected']:>5} "
              f"{c['invalid']:>5} {c['crashed']:>5} "
              f"{str(lat['p50']):>8} {str(lat['p99']):>8}")
    for msg in failures:
        print(f"ROUTER FAILURE: {msg}")
    return 0 if not failures else 1


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    sub = p.add_subparsers(dest="cmd", required=True)
    sm = sub.add_parser("smoke", help="CI SLO gate under an active fault plan")
    sm.add_argument("--out", default=None)
    sm.add_argument("--fault-plan", default=DEFAULT_PLAN,
                    help="plan JSON path, or 'none' to disable injection")
    sm.add_argument("--qps", type=float, default=120.0)
    sm.add_argument("--duration", type=float, default=4.0)
    kn = sub.add_parser("knee", help="latency-vs-load sweep across knobs")
    kn.add_argument("--out", default=None)
    kn.add_argument("--qps", dest="qps_list", default="50,100,200,400")
    kn.add_argument("--knobs", default="1:0.5,8:2,32:5",
                    help="comma list of max_batch:max_delay_ms settings")
    kn.add_argument("--duration", type=float, default=3.0)
    kn.add_argument("--rows", type=int, default=1)
    lc = sub.add_parser("lifecycle",
                        help="hot-swap campaign gate under a 503 storm")
    lc.add_argument("--out", default=None)
    lc.add_argument("--fault-plan", default=LIFECYCLE_PLAN,
                    help="plan JSON path, or 'none' to disable injection")
    lc.add_argument("--swaps", type=int, default=3,
                    help="checkpoint versions published during the load "
                         "(one validation is killed by the default plan)")
    lc.add_argument("--qps", type=float, default=80.0)
    lc.add_argument("--duration", type=float, default=5.0)
    lc.add_argument("--swap-interval", type=float, default=1.2,
                    help="seconds between published versions")
    ct = sub.add_parser("continuous",
                        help="whole-ring trainer-daemon chaos drill")
    ct.add_argument("--out", default=None)
    ct.add_argument("--fault-plan", default=CONTINUOUS_PLAN,
                    help="plan JSON path, or 'none' to disable injection")
    ct.add_argument("--files", type=int, default=14,
                    help="spool files written (label rate shifts across "
                         "them; one is poisoned)")
    ct.add_argument("--qps", type=float, default=40.0)
    ct.add_argument("--duration", type=float, default=75.0)
    rt = sub.add_parser("router",
                        help="multi-replica chaos drill: kill / hedge / "
                             "saturate / rolling restart")
    rt.add_argument("--out", default=None)
    rt.add_argument("--fault-plan", default=ROUTER_PLAN,
                    help="plan JSON path, or 'none' to disable injection")
    rt.add_argument("--replicas", type=int, default=3)
    rt.add_argument("--qps", type=float, default=50.0)
    rt.add_argument("--duration", type=float, default=12.0,
                    help="kill-phase seconds (hedge/saturate phases scale "
                         "from it)")
    rt.add_argument("--roll-duration", type=float, default=30.0,
                    help="rolling-restart phase seconds (must cover 3 "
                         "drain+relaunch+warmup cycles)")
    ck = sub.add_parser("c10k",
                        help="10k concurrent keep-alive connections "
                             "against a real server subprocess")
    ck.add_argument("--out", default=None)
    ck.add_argument("--transport", default="evloop",
                    choices=["threaded", "evloop"])
    ck.add_argument("--connections", type=int, default=10000)
    ck.add_argument("--active", type=int, default=32,
                    help="keep-alive workers scoring continuously while "
                         "the idle army holds")
    ck.add_argument("--churn-per-s", type=float, default=50.0,
                    help="idle connections closed+reopened per second")
    ck.add_argument("--duration", type=float, default=10.0)
    ab = sub.add_parser("evloop-ab",
                        help="threaded vs evloop p99 at matched load, "
                             "with per-stage tail attribution")
    ab.add_argument("--out", default=None)
    ab.add_argument("--qps", type=float, default=150.0)
    ab.add_argument("--duration", type=float, default=6.0)
    ab.add_argument("--rows", type=int, default=2)
    args = p.parse_args(argv)
    # every drill here is a CPU *correctness* drill: a parent plus server /
    # replica / trainer children that would each need a device of their
    # own.  Pinned for this process before it touches jax, inherited by
    # every child, and stated in each report's host block.
    os.environ["JAX_PLATFORMS"] = "cpu"
    print("bench_serving: CPU correctness drill — platform: cpu "
          "(JAX_PLATFORMS=cpu pinned for this process and its children)")
    if args.cmd == "c10k":
        return run_c10k(args)
    if args.cmd == "evloop-ab":
        return run_evloop_ab(args)
    if args.cmd == "smoke":
        return run_smoke(args)
    if args.cmd == "lifecycle":
        return run_lifecycle(args)
    if args.cmd == "continuous":
        return run_continuous(args)
    if args.cmd == "router":
        return run_router(args)
    return run_knee(args)


if __name__ == "__main__":
    sys.exit(main())
