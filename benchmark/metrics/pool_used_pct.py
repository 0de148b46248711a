"""Pages in use over pages in the pool, peak of the in-window samples
(prefix-cache entries hold pages too, until they are evicted)."""
NAMES = ("pool_used_pct", "pool_used_pct.closed")


def read(ctx):
    used = [1.0 - s["free_pages"] / s["pages_total"] for s in ctx["samples"]
            if s["pages_total"]]
    return 100.0 * max(used) if used else None
