"""What the readers of the server's own counters share (the leading
underscore keeps this file out of ``readers()``). Every reading is a
difference between the two snapshots of ``stats()`` at the window's
edges; a program that lacks a key, as one from before these counters
does, gives ``None`` and the metric is left out of the line."""

from benchmark import metrics


def delta(ctx: dict, key: str):
    """``stats_end[key] - stats_start[key]`` of a plain counter."""
    a, b = ctx["stats_start"].get(key), ctx["stats_end"].get(key)
    return None if a is None or b is None else b - a


def seconds(ctx: dict):
    """The time between the two snapshots by the server's own clock
    (``clock_s`` is stamped inside the lock hold that copies the
    counters: each snapshot waits for that lock, up to a window)."""
    d = delta(ctx, "clock_s")
    return d if d else None


def hist_mean(ctx: dict, key: str):
    """Mean of a server histogram's observations between the snapshots."""
    if key not in ctx["stats_start"] or key not in ctx["stats_end"]:
        return None
    return metrics.hist_mean(ctx, key)


def phase_ms(ctx: dict, names: tuple):
    """Milliseconds the named phases took between the snapshots
    (``phase_ms``: name -> [count, total ms])."""
    a, b = ctx["stats_start"].get("phase_ms"), ctx["stats_end"].get("phase_ms")
    if a is None or b is None or any(n not in a or n not in b for n in names):
        return None
    return sum(b[n][1] - a[n][1] for n in names)


def ratio(num, den, scale: float = 1.0):
    """``scale * num / den``, or None where either is missing or there
    is nothing to divide by."""
    return None if num is None or not den else scale * num / den
