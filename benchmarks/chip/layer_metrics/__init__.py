"""One file per per-layer metric: ``NAME``, ``UNIT``, ``LAYER``, ``MOVES``
(the end-to-end metric it should move), ``KINDS`` (the traffic kinds whose
evidence it can read) and ``reduce(evidence)``, which returns the value or
``None`` when there is nothing to read.  ``evidence`` holds ``trace`` (a
``tracereduce.Trace``), ``spans`` (the telemetry span buffer, ``None`` if
it overflowed), ``counters`` (``telemetry.snapshot()["metrics"]``),
``window`` (what the traffic kind's window returned), ``state``, ``cell``,
``config``, ``device_kind`` and ``say`` (the run's log: a reader with
several ways to find nothing says there which one it took)."""


def counter_total(counters, name):
    """Sum of a telemetry counter's samples over all label sets, or None."""
    family = counters.get(name)
    if not family or not family.get("samples"):
        return None
    return sum(s["value"] for s in family["samples"])


def span_seconds(spans, name):
    """Durations in seconds of every complete span called ``name``."""
    return [e["dur"] * 1e-6 for e in spans
            if e["name"] == name and e.get("ph") == "X"]


def kernel_seconds(chip):
    """Durations of the chip's Mosaic (histogram kernel) calls."""
    return [o.dur_s for o in chip.ops if o.is_mosaic]


def rounds_traced(evidence, chip):
    """Boosting rounds the traced window holds on one chip, counted from
    the histogram kernel's calls (one per tree level)."""
    return len(kernel_seconds(chip)) / evidence["config"]["max_depth"]
