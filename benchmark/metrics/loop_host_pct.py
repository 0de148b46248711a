"""Share of the time the decode-loop thread spent in its own host work:
boundary sweeps, building and enqueueing windows, and the bookkeeping
after a harvest (phases ``loop/boundary``, ``loop/dispatch``,
``loop/emit``), the rest being waits for the device, the lock or work."""
from benchmark.metrics import _counters

NAMES = ("loop_host_pct", "loop_host_pct.closed")


def read(ctx):
    host = _counters.phase_ms(
        ctx, ("loop/boundary", "loop/dispatch", "loop/emit"))
    return _counters.ratio(host, _counters.seconds(ctx), 0.1)
