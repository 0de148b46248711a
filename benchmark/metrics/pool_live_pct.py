"""Pages holding tokens of live rows over pages in the pool, averaged
over the decode steps of the harvested windows: pages in use, against
the pages reserved that ``pool_used_pct`` reads."""
from benchmark.metrics import _counters

NAMES = ("pool_live_pct", "pool_live_pct.closed")


def read(ctx):
    steps = _counters.delta(ctx, "decode_steps_total")
    pages = ctx["stats_end"].get("pages_total")
    if steps is None or not pages:
        return None
    return _counters.ratio(_counters.delta(ctx, "pages_live_steps_total"),
                           steps * pages, 100.0)
