"""Share of the time the work lock was held that no holder's name
accounts for: the lock's own total less the named holds. A site that
takes the lock without saying who it is shows here."""
from benchmark.metrics import _counters, _ledger

NAMES = ("lock_unnamed_pct", "lock_unnamed_pct.closed")


def read(ctx):
    total = _counters.delta(ctx, "lock_held_ms_total")
    holders = _ledger.names(ctx, "lock_held_ms")
    if total is None or not holders:
        return None
    named = _ledger.gained_ms(ctx, "lock_held_ms", holders)
    return _counters.ratio(total - named, total, 100.0)
