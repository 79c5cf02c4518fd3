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
    say = evidence.get("say") or (lambda msg: None)
    path = scopes.find_xplane(evidence)
    if path is None or not evidence["spans"]:
        say(f"{NAME}: " + ("no trace file" if path is None
                           else "no span in the buffer"))
        return None
    names = {e["name"] for e in evidence["spans"]}
    chip = evidence["trace"].worst
    events = scopes.host_annotations(
        path, names | {scopes.LAUNCH, scopes.DONE})
    leads = scopes.host_clock_lead(chip, events)
    if leads is None:
        say(f"{NAME}: the runtime's launch and done events do not bound "
            f"the host clock's lead")
        return None
    spans = [a for a in events if a[0] in names]
    shares = [scopes.idle_attributed(chip, spans, lead) for lead in leads]
    if None in shares:
        say(f"{NAME}: the chip never idled between its first and last op")
        return None
    return 100.0 * min(shares)
