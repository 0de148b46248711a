"""Pages of the window layers' pool in live rows' tables over the pages
of that pool, averaged over the decode steps of the harvested windows:
``pool_live_pct`` for the second pool."""
from benchmark.metrics import _counters

NAMES = ("window_pool_live_pct.closed",)


def read(ctx):
    steps = _counters.delta(ctx, "decode_steps_total")
    pages = ctx["stats_end"].get("window_pages_total")
    if steps is None or not pages:
        return None
    return _counters.ratio(
        _counters.delta(ctx, "window_pages_live_steps_total"),
        steps * pages, 100.0)
