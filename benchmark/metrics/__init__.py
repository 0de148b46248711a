"""Per-layer metrics, one reader to a file, found by listing this
directory. A file gives ``NAMES`` (the metric and, where closed-loop
cells report the same reading under a name of their own, its ``.closed``
twin) and ``read(ctx)``, which returns the value or ``None`` when there
is nothing to read (the metric is then left out of the line). ``ctx`` is
the run: ``records``/``window`` (the load generator's), ``stats_start``
and ``stats_end`` (the server's counters at the window's edges),
``samples`` (its gauges every quarter second), ``events`` (the trace,
traced runs only), ``setup``, ``cell``, ``plan``, ``peak``.
"""

from __future__ import annotations

import os

from benchmark import cellspec

HERE = os.path.dirname(os.path.abspath(__file__))


def readers(directory: str = HERE) -> dict:
    """name -> read, over every ``*.py`` of the directory."""
    found = {}
    for file in sorted(os.listdir(directory)):
        if not file.endswith(".py") or file.startswith("_"):
            continue
        module = cellspec.load_module("benchmark_metric_" + file[:-3],
                                      os.path.join(directory, file))
        for name in module.NAMES:
            if name in found:
                raise ValueError(f"two readers for metric {name!r}")
            found[name] = module.read
    return found


def hist_mean(ctx: dict, key: str):
    """Mean of a server histogram's observations inside the window."""
    a, b = ctx["stats_start"][key], ctx["stats_end"][key]
    n = b["count"] - a["count"]
    return (b["sum"] - a["sum"]) / n if n > 0 else None
