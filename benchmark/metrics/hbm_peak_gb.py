"""Peak device memory on the fullest chip, from ``memory_stats()``."""
NAMES = ("hbm_peak_gb", "hbm_peak_gb.closed")


def read(ctx):
    peak = ctx["memory"].get("memory_peak_bytes")
    return peak / 1e9 if peak else None
