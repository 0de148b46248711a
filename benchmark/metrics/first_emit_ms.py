"""Mean time from the pick of a request's first token to its put on the
request's stream: the server's own share of ``http_over_ms`` (the first
emission rides the next harvested window)."""
from benchmark.metrics import _counters

NAMES = ("first_emit_ms", "first_emit_ms.closed")


def read(ctx):
    return _counters.hist_mean(ctx, "first_emit_ms")
