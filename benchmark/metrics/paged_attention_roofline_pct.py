"""Least time the chip could take for the bytes the paged-attention
kernel must read, over the time its calls took in the trace. The bytes:
the server's own page-steps of both pools between the window's
snapshots (``pages_live_steps_total``: pages holding the live rows'
contexts, which the full layers read; ``window_pages_live_steps_total``:
pages in their window tables, which the window layers read), a step's
mean, times the steps of the decode programs the capture holds whole,
times a page's bytes a layer and the layers of each kind (the cell's
own block: ``references/<block>.py``'s ``page_bytes`` and
``attention_layers``), at the peak's HBM bandwidth. The time: the events
named ``paged_attention`` inside those programs."""
from benchmark import trace
from benchmark.metrics import _counters

NAMES = ("paged_attention_roofline_pct.closed",)
KERNEL = "paged_attention"


def read(ctx):
    work = trace.decode_work(ctx)
    steps = _counters.delta(ctx, "decode_steps_total")
    windowed = _counters.delta(ctx, "window_pages_live_steps_total")
    spanned = _counters.delta(ctx, "pages_live_steps_total")
    block = ctx["cell"].reference
    if (not work or not work["steps"] or not steps or windowed is None
            or spanned is None or not hasattr(block, "page_bytes")):
        return None
    cell = ctx["cell"]
    needle = cell.load["programs"]["decode"]
    took = sum(op["dur"]
               for program in trace.whole_programs(ctx["events"], needle)
               for op in trace.ops_inside(ctx["events"], program)
               if op["name"].startswith(KERNEL))
    if not took:
        return None
    model = cell.config["model"]
    full, window = block.attention_layers(model)
    page = block.page_bytes(model, cell.config["payload"]["serving_page_size"])
    nbytes = (work["steps"] / steps) * page * (full * spanned
                                               + window * windowed)
    return 100.0 * nbytes / ctx["peak"]["hbm_bytes_per_s"] / took
