"""Mean time the server spent giving window pages back, lock held: the
phases ``loop/window_release`` (once a harvested decode window) and
``admit/window_release`` (once a prefill chunk), their milliseconds over
their count."""
from benchmark.metrics import _counters

NAMES = ("window_release_ms.closed",)
PHASES = ("loop/window_release", "admit/window_release")


def read(ctx):
    a = ctx["stats_start"].get("phase_ms") or {}
    b = ctx["stats_end"].get("phase_ms") or {}
    if any(p not in a or p not in b for p in PHASES):
        return None
    return _counters.ratio(sum(b[p][1] - a[p][1] for p in PHASES),
                           sum(b[p][0] - a[p][0] for p in PHASES))
