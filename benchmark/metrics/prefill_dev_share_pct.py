"""Prefill programs' share of the device's busy time in the trace."""
from benchmark import trace

NAMES = ("prefill_dev_share_pct", "prefill_dev_share_pct.closed")


def read(ctx):
    events = ctx.get("events")
    if not events:
        return None
    busy = trace.busy_seconds(events)
    needle = ctx["cell"].load["programs"]["prefill"]
    return 100.0 * trace.program_seconds(events, needle) / busy
