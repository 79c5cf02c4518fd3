"""Share of the worst chip's idle time, between its first and last op,
during which the program had a span open on the host: telemetry spans are
``jax.profiler.TraceAnnotation``s too, so they lie in the trace beside the
device ops.  The host plane's clock runs ahead of the device's by more
than a gap between fits lasts; ``scopes.host_clock_lead`` bounds the lead
from the runtime's own launch and done events, and the share reported is
the smaller of the two the bounds give: what is attributed whatever the
lead."""

from benchmarks.chip import scopes

NAME = "idle_attributed_share"
UNIT = "%"
LAYER = "host: what the program was doing while the chip idled"
MOVES = "train_rows_per_s"
KINDS = ("fit",)


def reduce(evidence):
    path = scopes.find_xplane(evidence)
    if path is None or not evidence["spans"]:
        return None
    names = {e["name"] for e in evidence["spans"]}
    chip = evidence["trace"].worst
    events = scopes.host_annotations(
        path, names | {scopes.LAUNCH, scopes.DONE})
    leads = scopes.host_clock_lead(chip, events)
    if leads is None:
        return None
    spans = [a for a in events if a[0] in names]
    shares = [scopes.idle_attributed(chip, spans, lead) for lead in leads]
    return None if None in shares else 100.0 * min(shares)
