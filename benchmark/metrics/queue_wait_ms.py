"""Mean wait for admission (the server's ``queue_ms``) in the window."""
from benchmark.metrics import hist_mean

NAMES = ("queue_wait_ms", "queue_wait_ms.closed")


def read(ctx):
    return hist_mean(ctx, "queue_ms")
