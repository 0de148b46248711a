"""From the profiler's trace to device numbers.

``jax.profiler`` writes an ``.xplane.pb``; :func:`read_xplane` turns it
into plain events ``{"device", "line", "name", "start", "dur"}`` (seconds
from the trace's first event) and every reducer below works on that
list, so the reducers are tested on a small recorded trace kept as JSON
beside the tests. On a TPU each device plane has a line of XLA programs
(``XLA Modules``: one event per execution of a jitted program) and a
line of the operations inside them (``XLA Ops``).
"""

from __future__ import annotations

import bisect
import glob
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def read_xplane(path: str) -> list[dict]:
    """Device events of the trace, times in seconds."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    events = []
    for plane in data.planes:
        if not plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            if line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                events.append({
                    "device": plane.name, "line": line.name,
                    "name": (short_name(ev.name) if line.name == OPS_LINE
                             else ev.name),
                    "start": ev.start_ns * 1e-9,
                    "dur": ev.duration_ns * 1e-9})
    if events:
        zero = min(e["start"] for e in events)
        for e in events:
            e["start"] -= zero
    return events


_RESULT = re.compile(r"^%?(\S+) = (\w+)\[([\d,]*)\]")


def short_name(name: str) -> str:
    """An operation's event name is its whole HLO instruction; keep the
    instruction's name and its result's type and shape."""
    m = _RESULT.match(name)
    if m:
        return f"{m.group(1)} {m.group(2)}[{m.group(3)}]"
    return name.split(" = ")[0].lstrip("%")[:96]


def _union(intervals: list) -> float:
    """Seconds covered by a set of (start, end) intervals."""
    total, edge = 0.0, -1.0
    for start, end in sorted(intervals):
        if end <= edge:
            continue
        total += end - max(start, edge)
        edge = end
    return total


def devices(events: list) -> list[str]:
    return sorted({e["device"] for e in events})


def span(events: list) -> tuple[float, float]:
    """The traced span: first operation's start to the last one's end."""
    ops = [e for e in events if e["line"] == OPS_LINE] or events
    return (min(e["start"] for e in ops),
            max(e["start"] + e["dur"] for e in ops))


def busy_seconds(events: list) -> float:
    """Seconds in which an operation ran, averaged over the devices."""
    per = []
    for dev in devices(events):
        per.append(_union([(e["start"], e["start"] + e["dur"])
                           for e in events
                           if e["device"] == dev and e["line"] == OPS_LINE]))
    return sum(per) / len(per) if per else 0.0


def idle_gaps(events: list, device: str | None = None) -> list[tuple]:
    """(start, end) of every stretch with no operation on one device
    (the first, by default), longest first."""
    device = device or devices(events)[0]
    ivs = sorted((e["start"], e["start"] + e["dur"]) for e in events
                 if e["device"] == device and e["line"] == OPS_LINE)
    gaps, edge = [], ivs[0][0] if ivs else 0.0
    for start, end in ivs:
        if start > edge:
            gaps.append((edge, start))
        edge = max(edge, end)
    return sorted(gaps, key=lambda g: g[0] - g[1])


def program_events(events: list, needle: str,
                   device: str | None = None) -> list[dict]:
    """Executions of the programs whose name holds ``needle``."""
    device = device or devices(events)[0]
    return [e for e in events if e["line"] == MODULES_LINE
            and e["device"] == device and needle in e["name"]]


def program_seconds(events: list, needle: str) -> float:
    return sum(e["dur"] for e in program_events(events, needle))


def ops_inside(events: list, program: dict) -> list[dict]:
    """The operations that ran inside one execution of a program."""
    ops, starts = _sorted_ops(events, program["device"])
    lo, hi = program["start"], program["start"] + program["dur"] + 1e-9
    i = bisect.bisect_left(starts, lo)
    out = []
    while i < len(ops) and ops[i]["start"] < hi:
        if ops[i]["start"] + ops[i]["dur"] <= hi:
            out.append(ops[i])
        i += 1
    return out


_INDEX: dict = {}


def _sorted_ops(events: list, device: str):
    key = (id(events), device)
    if key not in _INDEX:
        _INDEX.clear()  # one trace at a time
        ops = sorted((e for e in events if e["line"] == OPS_LINE
                      and e["device"] == device), key=lambda e: e["start"])
        _INDEX[key] = (ops, [e["start"] for e in ops])
    return _INDEX[key]


EDGE_S = 1e-3


def whole_programs(events: list, needle: str,
                   device: str | None = None) -> list[dict]:
    """The named programs' executions that lie wholly inside the
    capture. One under way when the profiler started or stopped is in
    the trace cut short, from the capture's first instant or to its
    last, with part of its steps: it is left out."""
    programs = program_events(events, needle, device)
    if not programs:
        return []
    lo, hi = span([e for e in events
                   if e["device"] == programs[0]["device"]])
    return [p for p in programs if p["start"] > lo + EDGE_S
            and p["start"] + p["dur"] < hi - EDGE_S]


def steps_per_program(ctx: dict) -> float | None:
    """Decode steps one dispatched decode program ran, by the server's
    own counts between the window's two snapshots: ``decode_steps_total``
    adds each harvested program's steps, and the loop enters its phase
    ``loop/harvest_wait`` once for each program it harvests, whichever
    path dispatched it. Nothing here knows what a step is made of."""
    def counted(snap: dict) -> tuple:
        harvests = (snap.get("phase_ms") or {}).get("loop/harvest_wait")
        return snap.get("decode_steps_total"), harvests and harvests[0]

    steps_a, programs_a = counted(ctx["stats_start"])
    steps_b, programs_b = counted(ctx["stats_end"])
    if None in (steps_a, programs_a, steps_b, programs_b):
        return None  # a program from before these counters
    if steps_b <= steps_a or programs_b <= programs_a:
        return None
    return (steps_b - steps_a) / (programs_b - programs_a)


def live_rows_and_tokens(records: list, lo: float, hi: float,
                         points: int = 40) -> tuple[float, float]:
    """Mean sequences decoding, and mean cached positions they hold
    between them, over [lo, hi] (window seconds), from the clients'
    records: a request decodes from its first token to its last."""
    rows = tokens = 0.0
    for k in range(points):
        t = lo + (hi - lo) * (k + 0.5) / points
        for r in records:
            if r["first"] is None or not r["first"] <= t <= r["last"]:
                continue
            rows += 1
            done = (t - r["first"]) / max(r["last"] - r["first"], 1e-9)
            tokens += r["prompt"] + done * len(r["tokens"])
    return rows / points, tokens / points


def decode_work(ctx: dict) -> dict | None:
    """The decode programs' seconds and steps in the trace, and the mean
    batch they ran on: the seconds of the executions the capture holds
    whole, and for each of them the steps the server counted to a
    program over the window (its every program runs the same number of
    steps under steady load; where they differ this is their mean)."""
    events = ctx.get("events")
    if not events:
        return None
    if "decode_work" not in ctx:
        needle = ctx["cell"].load["programs"]["decode"]
        programs = whole_programs(events, needle)
        each = steps_per_program(ctx)
        rows, live = live_rows_and_tokens(ctx["records"], *ctx["trace_span"])
        ctx["decode_work"] = {
            "seconds": sum(p["dur"] for p in programs),
            "steps": each * len(programs) if each else 0,
            "programs": len(programs), "rows": rows, "live_tokens": live}
    return ctx["decode_work"]


def top_ops(events: list, n: int = 10) -> list[list]:
    """The operations that took most device time, on the first device
    (a ``while`` is its body's operations over again: left out)."""
    device = devices(events)[0]
    total: dict = {}
    for e in events:
        if (e["device"] == device and e["line"] == OPS_LINE
                and not e["name"].startswith(("while", "conditional"))):
            total[e["name"]] = total.get(e["name"], 0.0) + e["dur"]
    return [[k, v] for k, v in
            sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def program_table(events: list, n_ops: int = 25) -> dict:
    """Per program: executions, device seconds, its largest operations
    with their counts. Written to the run's output file."""
    device = devices(events)[0]
    table: dict = {}
    for prog in [e for e in events if e["line"] == MODULES_LINE
                 and e["device"] == device]:
        row = table.setdefault(prog["name"], {"runs": 0, "seconds": 0.0,
                                              "ops": {}})
        row["runs"] += 1
        row["seconds"] += prog["dur"]
        for e in ops_inside(events, prog):
            op = row["ops"].setdefault(e["name"], [0, 0.0])
            op[0] += 1
            op[1] += e["dur"]
    for row in table.values():
        row["ops"] = dict(sorted(row["ops"].items(),
                                 key=lambda kv: -kv[1][1])[:n_ops])
    return table
