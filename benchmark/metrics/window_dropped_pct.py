"""Of the pages a live row's context spans, the share its window layers
no longer hold: 100 x (1 - the growth of ``window_pages_live_steps_total``
(pages of the window layers' pool in live rows' tables x steps) over the
growth of ``pages_live_steps_total`` (pages holding the rows' whole
contexts, the full layers' pool, x steps)). 0 where no context passes the
window; what a single table for every layer would have kept."""
from benchmark.metrics import _counters

NAMES = ("window_dropped_pct.closed",)


def read(ctx):
    held = _counters.delta(ctx, "window_pages_live_steps_total")
    spanned = _counters.delta(ctx, "pages_live_steps_total")
    if held is None or not spanned:
        return None
    return 100.0 * (1.0 - held / spanned)
