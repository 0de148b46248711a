"""Of the time the requests that finished in the window held a slot and
their pages (admission to the last token), the share in which they were
decoding: the rest is the admission block, the prefill's chunks and
their waits for the lock, the pick, and the wait for the first window
that carries the row."""
from benchmark.metrics import _counters, _ledger

NAMES = ("slot_decode_pct", "slot_decode_pct.closed")
HOLDING = ("admit", "prefill_wait", "prefill", "pick", "join_wait",
           "decode")


def read(ctx):
    return _counters.ratio(
        _ledger.gained_ms(ctx, "request_ms", ("decode",)),
        _ledger.gained_ms(ctx, "request_ms", HOLDING), 100.0)
