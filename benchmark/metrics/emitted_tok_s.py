"""Tokens the server recorded into its requests per second of its own
clock: ``delivered_tok_s`` from inside, before the streams and HTTP."""
from benchmark.metrics import _counters

NAMES = ("emitted_tok_s", "emitted_tok_s.closed")


def read(ctx):
    return _counters.ratio(_counters.delta(ctx, "tokens_emitted_total"),
                           _counters.seconds(ctx))
