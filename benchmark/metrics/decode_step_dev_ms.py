"""Device time of one decode step: the decode-window programs' time in
the trace over the steps they ran."""
from benchmark import trace

NAMES = ("decode_step_dev_ms", "decode_step_dev_ms.closed")


def read(ctx):
    work = trace.decode_work(ctx)
    if not work or not work["steps"]:
        return None
    return 1e3 * work["seconds"] / work["steps"]
