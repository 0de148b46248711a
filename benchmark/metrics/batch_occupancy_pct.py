"""Live rows over slots, mean of the in-window samples."""
NAMES = ("batch_occupancy_pct", "batch_occupancy_pct.closed")


def read(ctx):
    rows = [s["in_flight"] / s["slots_total"] for s in ctx["samples"]
            if s["slots_total"]]
    return 100.0 * sum(rows) / len(rows) if rows else None
