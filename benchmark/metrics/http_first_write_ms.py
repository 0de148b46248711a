"""Mean time from the put of a request's first token on its stream to
the return of the handler's flush of that token's line: the handler
thread's wake-up among its kind, and the socket write."""
from benchmark.metrics import _counters, _ledger

NAMES = ("http_first_write_ms", "http_first_write_ms.closed")


def read(ctx):
    got = _ledger.gained(ctx, "request_ms", "first_write")
    return None if got is None else _counters.ratio(got[1], got[0])
