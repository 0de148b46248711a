"""Mean time a prefill chunk waited for the server's work lock, which
the decode loop holds for a window (phase ``admit/lock_wait``)."""
from benchmark.metrics import _counters

NAMES = ("prefill_lock_wait_ms", "prefill_lock_wait_ms.closed")


def read(ctx):
    return _counters.hist_mean(ctx, "prefill_lock_wait_ms")
