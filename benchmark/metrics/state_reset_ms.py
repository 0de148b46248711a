"""Mean time an admission spent zeroing its slot's recurrent state
(phase ``admit/state_reset``: the small program's dispatch and whatever
that waited for), over the window's admissions."""
from benchmark.metrics import _counters

NAMES = ("state_reset_ms.closed",)
PHASE = "admit/state_reset"


def read(ctx):
    a = (ctx["stats_start"].get("phase_ms") or {}).get(PHASE)
    b = (ctx["stats_end"].get("phase_ms") or {}).get(PHASE)
    if a is None or b is None:
        return None
    return _counters.ratio(b[1] - a[1], b[0] - a[0])
