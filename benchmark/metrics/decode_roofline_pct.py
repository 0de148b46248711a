"""Least time the chip could take for the decode steps in the trace
(bf16 weights once a step, the live keys and values once: bound by HBM
bandwidth at these batch sizes) over the time they took."""
from benchmark import roofline, trace

NAMES = ("decode_roofline_pct", "decode_roofline_pct.closed")


def read(ctx):
    work = trace.decode_work(ctx)
    if not work or not work["steps"] or not work["seconds"]:
        return None
    step = roofline.decode_step(ctx["cell"].config["model"],
                                work["rows"], work["live_tokens"])
    least = roofline.least_seconds(step, ctx["peak"], ctx["cell"].chips)
    return 100.0 * least * work["steps"] / work["seconds"]
