"""Row-steps that produced a token over row-steps the device computed
(bucket rows times window length), summed over the harvested windows: a
count, exact on any backend."""
from benchmark.metrics import _counters

NAMES = ("decode_bucket_fill_pct", "decode_bucket_fill_pct.closed")


def read(ctx):
    return _counters.ratio(_counters.delta(ctx, "decode_row_steps_total"),
                           _counters.delta(ctx, "decode_bucket_steps_total"),
                           100.0)
