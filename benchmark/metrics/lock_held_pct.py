"""Share of the time in which anyone at all held the server's work
lock, by the lock's own count of its acquires and releases (a thread
parked in ``Condition.wait`` holds nothing)."""
from benchmark.metrics import _counters

NAMES = ("lock_held_pct", "lock_held_pct.closed")


def read(ctx):
    return _counters.ratio(_counters.delta(ctx, "lock_held_ms_total"),
                           _counters.seconds(ctx), 0.1)
