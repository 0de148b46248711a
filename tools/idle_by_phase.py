#!/usr/bin/env python3
"""What the host was doing while the device stood empty.

Usage:
    python tools/idle_by_phase.py <capture>.xplane.pb [--json] [--top N]

A capture taken while a paged server runs (``POST /profile``, or
``jax.profiler.start_trace`` around the traffic) holds the device's
operations and, on the profiler's own clock in the same file, the
server's phases as ``kvedge/...`` annotations on their host threads'
lines (runtime/tracing.py): the decode loop's ``kvedge/loop/<phase>``
and, for every hold of the work lock, ``kvedge/lock/<holder>``. This
tool takes the device's idle gaps (``benchmark.trace.idle_gaps``: the
stretches with no operation on the first device) and puts each down to
the holder of the lock and the phase of the loop at the instant the
gap starts, summed by that pair: "the device went empty while a
handler held the lock for its pick and the loop waited for the lock"
reads ``admit/first_pick | loop/lock_wait``.

:func:`idle_by_phase` is the one function, on plain event lists, so a
benchmark PR can move it beside ``idle_gaps`` (ROADMAP S4b).
"""

from __future__ import annotations

import argparse
import bisect
import json
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from benchmark import trace  # noqa: E402

LOCK = "kvedge/lock/"
LOOP = "kvedge/loop/"


def read(path: str) -> tuple[list, list]:
    """(device events as ``benchmark.trace.read_xplane`` gives them,
    the host planes' ``kvedge/...`` events as ``{"line", "name",
    "start", "dur"}``), seconds from the first device event."""
    from jax.profiler import ProfileData

    device, host = [], []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name in (trace.OPS_LINE, trace.MODULES_LINE):
                    device += [{"device": plane.name, "line": line.name,
                                "name": ev.name,
                                "start": ev.start_ns * 1e-9,
                                "dur": ev.duration_ns * 1e-9}
                               for ev in line.events]
        elif plane.name.startswith("/host:"):
            for index, line in enumerate(plane.lines):
                host += [{"line": f"{line.name or index}", "name": ev.name,
                          "start": ev.start_ns * 1e-9,
                          "dur": ev.duration_ns * 1e-9}
                         for ev in line.events
                         if ev.name.startswith("kvedge/")]
    zero = min((e["start"] for e in device), default=0.0)
    for e in device + host:
        e["start"] -= zero
    return device, host


def _covering(host: list, prefix: str):
    """at(t) -> the name (less ``prefix``) of the event of that family
    under way at ``t``, or None. Holds of one lock never overlap and
    one thread's phases follow each other, so the last to start before
    ``t`` is the only candidate."""
    family = sorted((e for e in host if e["name"].startswith(prefix)),
                    key=lambda e: e["start"])
    starts = [e["start"] for e in family]

    def at(t: float):
        i = bisect.bisect_right(starts, t) - 1
        if i >= 0 and t < family[i]["start"] + family[i]["dur"]:
            return family[i]["name"][len(prefix):]
        return None

    return at


def idle_by_phase(events: list, host: list, device: str | None = None,
                  top: int | None = None) -> list[list]:
    """``[lock holder, loop phase, idle seconds, gaps]`` rows, most idle
    first: every idle gap of the device, put down to who held the work
    lock and which phase the decode loop was in when the gap started
    (``"(free)"``: nobody held it; ``"(none)"``: between two phases'
    annotations, or no loop thread in the capture)."""
    holder, phase = _covering(host, LOCK), _covering(host, LOOP)
    total: dict = {}
    for lo, hi in trace.idle_gaps(events, device):
        key = (holder(lo) or "(free)", "loop/" + (phase(lo) or "(none)"))
        seconds, gaps = total.get(key, (0.0, 0))
        total[key] = (seconds + hi - lo, gaps + 1)
    rows = [[who, what, seconds, gaps]
            for (who, what), (seconds, gaps) in total.items()]
    rows.sort(key=lambda row: -row[2])
    return rows[:top]


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("xplane", help="a capture's .xplane.pb (or the "
                        "directory jax.profiler wrote it under)")
    parser.add_argument("--json", action="store_true")
    parser.add_argument("--top", type=int, default=12)
    args = parser.parse_args(argv)
    path = args.xplane
    if not path.endswith(".pb"):
        path = trace.find_xplane(path)
    events, host = read(path)
    if not events:
        print("no device operations in the capture", file=sys.stderr)
        return 1
    rows = idle_by_phase(events, host, top=args.top)
    lo, hi = trace.span(events)
    idle = sum(g[1] - g[0] for g in trace.idle_gaps(events))
    if args.json:
        print(json.dumps({"window_s": hi - lo, "idle_s": idle,
                          "rows": rows}))
        return 0
    print(f"window {hi - lo:.3f} s, idle {idle:.3f} s "
          f"({100 * idle / (hi - lo):.1f}%)")
    print(f"{'lock holder':<22}{'loop phase':<22}{'idle s':>9}"
          f"{'% of idle':>11}{'gaps':>8}")
    for who, what, seconds, gaps in rows:
        print(f"{who:<22}{what:<22}{seconds:>9.4f}"
              f"{100 * seconds / idle if idle else 0.0:>11.1f}{gaps:>8}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
