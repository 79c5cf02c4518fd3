"""The harness: one cell, set-up -> measured window -> check -> metrics.

Driven by data.  A cell is an entry of ``BENCHMARK.json``'s ``workloads``
plus ``workloads/<name>.json`` (its traffic parameters, naming a traffic
``kind``); a configuration is ``configs/<name>.json``; a traffic kind is
``traffic/<kind>.py`` (``setup`` / ``window`` / ``check`` /
``end_to_end``); a per-layer metric is ``layer_metrics/<metric>.py``
(``NAME``, ``UNIT``, ``LAYER``, ``MOVES``, ``KINDS``, ``reduce``); what a
fit configuration's ``objective`` means is ``objectives/<objective>.py``
(labels, further per-row arrays, the reference's gradient, the compared
loss).  All are found by name, so a later PR adds files and manifest
entries and edits nothing here.  ``run.py`` is the command (it refuses to
measure without the TPU and the cell's chips); the tests call
:func:`run_cell` with tiny rehearsal configurations on the CPU.
"""

from __future__ import annotations

import importlib
import json
import os
import pkgutil
import threading
import time
from dataclasses import dataclass
from typing import Callable, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_manifest(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def load_cell(manifest: dict, name: str, root: str = ROOT):
    """``(cell, config)`` of a manifest workload: the cell's own file
    (``<root>/benchmarks/chip/workloads/<name>.json``) merged over its
    manifest entry, and the configuration's file.  The two sources must
    agree on what they both state."""
    entries = {w["name"]: w for w in manifest["workloads"]}
    if name not in entries:
        raise KeyError(f"workload {name!r} is not in BENCHMARK.json "
                       f"(has: {sorted(entries)})")
    entry = entries[name]
    cell = load_json(os.path.join(root, os.path.relpath(HERE, ROOT),
                                  "workloads", f"{name}.json"))
    for key in ("config", "chips"):
        if cell.get(key, entry[key]) != entry[key]:
            raise ValueError(f"workloads/{name}.json says {key}="
                             f"{cell[key]!r}, BENCHMARK.json {entry[key]!r}")
    cell = {**entry, **cell}
    configs = {c["name"]: c for c in manifest["configs"]}
    config = load_json(os.path.join(root, configs[cell["config"]]["file"]))
    return cell, config


def load_kind(kind: str):
    return importlib.import_module(f"benchmarks.chip.traffic.{kind}")


def layer_metric_modules() -> list:
    """Every ``layer_metrics/<metric>.py``, by file name."""
    pkg = importlib.import_module("benchmarks.chip.layer_metrics")
    return [importlib.import_module(f"{pkg.__name__}.{m.name}")
            for m in sorted(pkgutil.iter_modules(pkg.__path__),
                            key=lambda m: m.name)]


def cell_metrics(manifest: dict, name: str, group: str) -> List[dict]:
    """The manifest's ``end_to_end`` / ``per_layer`` entries that list this
    cell (an entry without ``workloads`` is reported by every cell)."""
    return [m for m in manifest[group]
            if name in m.get("workloads", [name])]


@dataclass
class Context:
    """What a traffic kind is handed."""

    cell: dict
    config: dict
    seed: int
    seconds: float
    trace: bool
    devices: list                  # exactly the chips the cell asks for
    cache_dir: str                 # fixed, inside the checkout, git-ignored
    work_dir: str                  # this run's scratch; removed at the end
    say: Callable[[str], None] = print


class CompileCounter:
    """Counts lowerings and backend compiles through ``jax.monitoring``; a
    window in which the count moved compiled something.  One listener per
    process (JAX offers no way to take one away again)."""

    EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")
    _instance: Optional["CompileCounter"] = None

    def __init__(self):
        self.count = 0

    @classmethod
    def get(cls) -> "CompileCounter":
        if cls._instance is None:
            import jax.monitoring

            cls._instance = cls()
            jax.monitoring.register_event_duration_secs_listener(
                cls._instance._on)
        return cls._instance

    def _on(self, event, duration, **_):
        if event in self.EVENTS:
            self.count += 1


class TraceWindow:
    """Records a ``jax.profiler`` trace of ``cell["trace_seconds"]`` steady
    seconds, starting ``cell["trace_after_s"]`` into the window, from a
    timer thread (the window's own thread keeps offering load)."""

    def __init__(self, ctx: Context):
        self.dir = os.path.join(ctx.work_dir, "trace")
        self.after = float(ctx.cell.get("trace_after_s", 2.0))
        self.length = float(ctx.cell.get("trace_seconds", 5.0))
        self.error: Optional[BaseException] = None
        self._thread = threading.Thread(target=self._run, name="trace",
                                        daemon=True)

    def start(self):
        self._thread.start()

    def _run(self):
        import jax

        try:
            time.sleep(self.after)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0     # device ops, not Python
            options.host_tracer_level = 1
            jax.profiler.start_trace(self.dir, profiler_options=options)
            time.sleep(self.length)
            jax.profiler.stop_trace()
        except BaseException as exc:  # noqa: BLE001 — re-raised by join()
            self.error = exc

    def join(self) -> str:
        self._thread.join()
        if self.error is not None:
            raise self.error
        return self.dir


def peak_memory_bytes(devices) -> int:
    """Peak bytes held on the fullest chip: the buffers in use at their
    peak plus the scratch the runtime reserved for compiled programs at its
    peak.  The v5e runtime keeps the two apart — a fit whose program needs
    8.8 GB of temporaries shows 0.58 GB ``peak_bytes_in_use`` and 8.8 GB
    ``peak_bytes_reserved`` (my chip run, PR 22) — and a chip holds both.
    0 where the backend keeps no statistics, as the CPU does."""
    def peak(d):
        stats = d.memory_stats() or {}
        return int(stats.get("peak_bytes_in_use", 0)
                   + stats.get("peak_bytes_reserved", 0))

    return max(peak(d) for d in devices)


def run_cell(ctx: Context, manifest: dict, t_start: float) -> dict:
    """Measure one cell; returns the contract's result object.

    ``t_start`` is the process's start on ``time.perf_counter``: ``setup_s``
    runs from it to the first instant of the measured window.
    """
    from dmlc_core_tpu import telemetry

    kind = load_kind(ctx.cell["kind"])
    compiles = CompileCounter.get()
    state = kind.setup(ctx)
    if ctx.trace:
        telemetry.reset()
        telemetry.enable()
        tracer = TraceWindow(ctx)
        tracer.start()
    compiled_before = compiles.count
    window = kind.window(ctx, state, t_start)
    compiled_in_window = compiles.count - compiled_before
    trace_dir = None
    if ctx.trace:
        trace_dir = tracer.join()
        telemetry.disable()
    # what the timed path held, read before the check: its reference and
    # its eager kernel call hold more than a fit ever does (on epsilon 12
    # GB against the fit's 5.2), and a process's peak never falls again
    device = {"platform": ctx.devices[0].platform,
              "kind": ctx.devices[0].device_kind,
              "count": len(ctx.devices),
              "memory_peak_bytes": peak_memory_bytes(ctx.devices)}
    checks = list(kind.check(ctx, state, window))
    checks.append((compiled_in_window == 0,
                   f"nothing compiled inside the window "
                   f"({compiled_in_window} lowerings/compiles)"))
    compared = [f"{'ok' if ok else 'FAILED'}: {what}" for ok, what in checks]
    for line in compared:
        ctx.say(line)
    result = {"correct": all(ok for ok, _ in checks),
              "attempted": int(window["attempted"]),
              "failed": int(window["failed"]), "metrics": {},
              "device": device}
    if ctx.trace:
        _read_layers(ctx, manifest, trace_dir, state, window, result)
    else:
        values = kind.end_to_end(ctx, state, window)
        for m in cell_metrics(manifest, ctx.cell["name"], "end_to_end"):
            result["metrics"][m["name"]] = {"value": values[m["name"]],
                                            "unit": m["unit"]}
    # every number compared beside its limit, last in the line
    result["compared"] = compared
    return result


def _read_layers(ctx, manifest, trace_dir, state, window, result):
    """A traced run's per-layer metrics, busy seconds and breakdown, into
    ``result``."""
    from benchmarks.chip import tracereduce
    from dmlc_core_tpu import telemetry

    device = result["device"]
    trace = tracereduce.load(trace_dir, len(ctx.devices))
    device["busy_s"] = trace.busy_s
    device["window_s"] = trace.window_s
    result["breakdown"] = trace.breakdown()
    dropped = telemetry.get_tracer().dropped
    evidence = {"trace": trace, "window": window, "state": state,
                "cell": ctx.cell, "config": ctx.config,
                "device_kind": device["kind"], "say": ctx.say,
                # a truncated span buffer gives no span-derived metric
                "spans": None if dropped else telemetry.get_tracer().events(),
                "counters": telemetry.snapshot()["metrics"]}
    if dropped:
        ctx.say(f"span buffer dropped {dropped} spans: no span-derived "
                f"metric is reported")
    wanted = {m["name"]: m for m in
              cell_metrics(manifest, ctx.cell["name"], "per_layer")}
    for mod in layer_metric_modules():
        if mod.NAME not in wanted or ctx.cell["kind"] not in mod.KINDS:
            continue
        value = mod.reduce(evidence)
        if value is None:
            ctx.say(f"per-layer {mod.NAME}: nothing to read, left out")
            continue
        result["metrics"][mod.NAME] = {"value": value, "unit": mod.UNIT}
