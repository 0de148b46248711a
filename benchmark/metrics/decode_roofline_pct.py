"""Least time the chip could take for the decode steps in the trace, by
the count of the cell's own block (``references/<block>.py``'s
``decode_step``: for the block there is, bf16 weights once a step and
the live keys and values once, bound by HBM bandwidth at these batch
sizes), over the time they took."""
from benchmark import roofline, trace

NAMES = ("decode_roofline_pct", "decode_roofline_pct.closed")


def read(ctx):
    work = trace.decode_work(ctx)
    if not work or not work["steps"] or not work["seconds"]:
        return None
    cell = ctx["cell"]
    step = cell.reference.decode_step(cell.config["model"], work["rows"],
                                      work["live_tokens"])
    least = roofline.least_seconds(step, ctx["peak"], cell.chips)
    return 100.0 * least * work["steps"] / work["seconds"]
