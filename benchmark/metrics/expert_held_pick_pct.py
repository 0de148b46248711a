"""Of the routed experts' picks the decode windows counted, the share
that fell on an expert this chip holds: 100 x the growth of
``expert_picks_held_total`` over that of ``expert_picks_total`` (50
under even routing where half the experts are held)."""
from benchmark.metrics import _counters

NAMES = ("expert_held_pick_pct.closed",)


def read(ctx):
    return _counters.ratio(_counters.delta(ctx, "expert_picks_held_total"),
                           _counters.delta(ctx, "expert_picks_total"), 100.0)
