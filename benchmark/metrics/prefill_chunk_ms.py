"""Mean time a prefill chunk held the server's work lock: the chunk
dispatched and whatever that blocked on (phase ``admit/prefill_chunk``)."""
from benchmark.metrics import _counters

NAMES = ("prefill_chunk_ms", "prefill_chunk_ms.closed")


def read(ctx):
    return _counters.hist_mean(ctx, "prefill_chunk_ms")
