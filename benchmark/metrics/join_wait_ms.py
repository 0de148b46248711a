"""Mean time from the pick of a request's first token to the dispatch
of the first decode window that carries its row, over the requests
that finished in the window."""
from benchmark.metrics import _counters, _ledger

NAMES = ("join_wait_ms", "join_wait_ms.closed")


def read(ctx):
    got = _ledger.gained(ctx, "request_ms", "join_wait")
    return None if got is None else _counters.ratio(got[1], got[0])
