"""Share of the traced span in which no operation ran on the device."""
from benchmark import trace

NAMES = ("device_idle_pct", "device_idle_pct.closed")


def read(ctx):
    events = ctx.get("events")
    if not events:
        return None
    lo, hi = trace.span(events)
    return 100.0 * (1.0 - trace.busy_seconds(events) / (hi - lo))
