"""``paged_attention_roofline_pct``'s reading in a cell whose full
layers run the paged-attention kernel's blocked form: the least time at
the peak's HBM bandwidth for the pages the decode programs' attention
calls must read (the server's page-steps of both pools times a page's
bytes a layer, as that reader reckons them) over those calls' time in
the trace, both forms' events together (``paged_attention_blocked`` on
the full layers' table, ``paged_attention`` on the window layers': that
reader takes every event whose name starts with ``paged_attention``).
A program with no blocked call reads nothing."""
from benchmark import trace
from benchmark.metrics import paged_attention_roofline_pct as both_forms

NAMES = ("blocked_attention_roofline_pct.closed",)
BLOCKED = "paged_attention_blocked"


def read(ctx):
    if not trace.decode_work(ctx):
        return None
    needle = ctx["cell"].load["programs"]["decode"]
    if not any(op["name"].startswith(BLOCKED)
               for program in trace.whole_programs(ctx["events"], needle)
               for op in trace.ops_inside(ctx["events"], program)):
        return None
    return both_forms.read(ctx)
