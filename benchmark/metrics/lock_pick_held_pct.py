"""Share of the time in which a handler held the work lock for the pick
of its request's first token: lock held to logits read back, which is
a wait for the device with the lock in hand."""
from benchmark.metrics import _counters, _ledger

NAMES = ("lock_pick_held_pct", "lock_pick_held_pct.closed")


def read(ctx):
    return _counters.ratio(
        _ledger.gained_ms(ctx, "lock_held_ms", ("admit/first_pick",)),
        _counters.seconds(ctx), 0.1)
