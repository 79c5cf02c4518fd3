#!/usr/bin/env python
"""The chip benchmark's command.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

One process.  It loads the cell and its configuration by name, asks
``dmlc_core_tpu.device.init_device()`` what JAX found, and REFUSES to
measure — non-zero exit, no result line — unless the platform is ``tpu``
and there are at least the cell's ``chips`` devices (it then uses exactly
that many).  There is no CPU path here.  It then runs the cell through
``harness.run_cell`` (set-up, measured window, check) and prints the
result as one JSON object on the last line of stdout: with ``--trace 0``
the cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics,
the device's busy seconds and the breakdown; either way ``compared`` comes
last, every check's line with its numbers beside their limits, and the
same lines close stderr.
"""

import time

_T_START = time.perf_counter()   # set-up is counted from here

import argparse  # noqa: E402
import faulthandler  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

EXIT_NO_DEVICE = 3


def say(msg):
    print(f"[chip +{time.perf_counter() - _T_START:7.2f}s] {msg}",
          flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)

    from benchmarks.chip import harness

    manifest = harness.load_manifest(ROOT)
    cell, config = harness.load_cell(manifest, args.workload, ROOT)

    import jax

    from dmlc_core_tpu.device import init_device

    info = init_device()
    say(f"device: platform={info.platform} kind={info.device_kind} "
        f"count={info.count}; host cpus {os.cpu_count()}; compile cache "
        f"{info.cache_dir}")
    if info.platform != "tpu" or info.count < cell["chips"]:
        print(f"refusing to measure {args.workload}: it needs "
              f"{cell['chips']} tpu chip(s), JAX reports {info.count} "
              f"device(s) of platform {info.platform!r}", file=sys.stderr)
        return EXIT_NO_DEVICE
    # a wedged phase ends the run, with every thread's stack, inside the
    # 1200 s a first (compiling) run is allowed, rather than hold the chip
    faulthandler.dump_traceback_later(1100, exit=True)
    # every program of a run goes to the persistent cache, however quickly
    # it compiled: a later run of the cell then compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    cache_dir = os.path.join(ROOT, ".bench_cache")
    os.makedirs(cache_dir, exist_ok=True)
    # this run's scratch (the trace, the generator's samples) under TMPDIR
    with tempfile.TemporaryDirectory(prefix="chipbench_") as work_dir:
        ctx = harness.Context(cell=cell, config=config, seed=args.seed,
                              seconds=args.seconds, trace=bool(args.trace),
                              devices=jax.devices()[:cell["chips"]],
                              cache_dir=cache_dir, work_dir=work_dir, say=say)
        result = harness.run_cell(ctx, manifest, _T_START)
    faulthandler.cancel_dump_traceback_later()
    say(f"total {time.perf_counter() - _T_START:.2f} s")
    # every number compared beside its limit: the last lines of stderr, and
    # the last key of the result's line
    print("\n".join(result["compared"]), file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
