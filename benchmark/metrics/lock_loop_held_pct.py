"""Share of the time in which the decode-loop thread held the server's
work lock (its waits inside ``Condition.wait`` taken out): what is left
is all that prefill chunks, cancels and ``stats()`` can have."""
from benchmark.metrics import _counters

NAMES = ("lock_loop_held_pct", "lock_loop_held_pct.closed")


def read(ctx):
    return _counters.ratio(_counters.delta(ctx, "loop_lock_held_ms_total"),
                           _counters.seconds(ctx), 0.1)
