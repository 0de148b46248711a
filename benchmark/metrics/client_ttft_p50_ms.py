"""Median client time to first token, from the due time."""
from benchmark import reduce

NAMES = ("client_ttft_p50_ms", "client_ttft_p50_ms.closed")


def read(ctx):
    xs = [reduce.ttft_ms(r) for r in ctx["window"]]
    return reduce.percentile(xs, 50) if xs else None
