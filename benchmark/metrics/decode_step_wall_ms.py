"""Wall time one decode step costs: the server's clock over the steps of
the windows it harvested (prefill and every host stall included), beside
the device time ``decode_step_dev_ms`` reads from the trace."""
from benchmark.metrics import _counters

NAMES = ("decode_step_wall_ms", "decode_step_wall_ms.closed")


def read(ctx):
    return _counters.ratio(_counters.seconds(ctx),
                           _counters.delta(ctx, "decode_steps_total"), 1e3)
