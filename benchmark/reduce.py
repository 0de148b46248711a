"""From the load generator's records to the numbers a user would see."""

from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """Linear interpolation between closest ranks (numpy's default)."""
    xs = sorted(values)
    if not xs:
        return math.nan
    k = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def window_requests(records: list, seconds: float) -> list:
    """Requests due inside the window: the ones every latency is of."""
    return [r for r in records if 0.0 <= r["due"] < seconds]


def live_in_window(records: list, seconds: float,
                   loop: str = "closed") -> list:
    """The requests a run attempted. Open loop: the ones due inside the
    window. Closed loop: the ones under way at some time inside it (its
    requests are sent before the window opens or when a client's last
    one has just ended)."""
    if loop == "open":
        return window_requests(records, seconds)
    return [r for r in records if r["due"] < seconds
            and (r["last"] is None or r["last"] >= 0.0)]


def clients_dry(requests: list, records: list, seconds: float) -> dict:
    """Closed-loop clients whose chain of planned ``requests`` ended
    inside the window, and the second the first of them did: from then
    on that client's row stood empty, and ``out_tok_s`` reads less than
    the server could have given. A cell's chains are sized so that none
    does (PERF.md, section 4)."""
    planned: dict = {}
    for r in requests:
        if r["client"] >= 0:
            planned[r["client"]] = planned.get(r["client"], 0) + 1
    ended: dict = {}
    for r in finished(records):
        if r["client"] in planned:
            ended.setdefault(r["client"], []).append(r["last"])
    dry = sorted(max(lasts) for client, lasts in ended.items()
                 if len(lasts) == planned[client] and max(lasts) < seconds)
    return {"count": len(dry), "first_s": dry[0] if dry else None}


def failed(records: list) -> list:
    return [r for r in records if r["error"] and not r.get("cut")]


def ttft_ms(r: dict) -> float:
    return (r["first"] - r["due"]) * 1e3


def tpot_ms(r: dict) -> float:
    return (r["last"] - r["first"]) * 1e3 / (len(r["tokens"]) - 1)


def finished(records: list) -> list:
    return [r for r in records if not r["error"]]


PULSE_GAP_S = 0.25


def deliveries(record: dict) -> list[tuple[float, int]]:
    """One stream's deliveries as (time of the last line, tokens). The
    server hands a row's tokens over once per harvested decode window,
    up to 64 lines within a few milliseconds; lines less than a quarter
    second apart are one delivery."""
    out: list = []
    last = None
    for t, n in record.get("bursts", ()):
        if last is not None and t - last <= PULSE_GAP_S:
            out[-1] = (t, out[-1][1] + n)
        else:
            out.append((t, n))
        last = t
    return out


def tokens_in_window(records: list, seconds: float) -> float:
    """Output tokens produced for the clients inside the window. The
    server hands a row's tokens over once per harvested decode window,
    64 at a time and every row at the same instant, so a delivery's
    tokens are credited evenly over the time since that stream's
    previous delivery: they were produced over that time. A stream's
    first delivery has none before it and is credited whole, when it
    arrives. One that straddles an edge of the window is credited by the
    part inside (the drain reads on until each stream's next delivery
    after the close). Counted whole, a 48 s window holds 20 to 32
    deliveries per stream, all rows in step, and the rate moves by one
    part in that many with where the window's edges fall between two of
    them; the whole count stands beside this as ``delivered_tok_s``."""
    total = 0.0
    for r in records:
        prev = None
        for t, n in deliveries(r):
            if prev is None:
                total += n if 0.0 <= t < seconds else 0
            else:
                lo, hi = max(prev, 0.0), min(t, seconds)
                if hi > lo:
                    total += n * (hi - lo) / (t - prev)
            prev = t
    return total


def tokens_delivered_whole(records: list, seconds: float) -> int:
    """Output tokens whose line reached the client inside the window."""
    return sum(n for r in records for t, n in r.get("bursts", ())
               if 0.0 <= t < seconds)


def end_to_end(name: str, records: list, seconds: float) -> float:
    """The value of one end-to-end metric (``setup_s`` is the harness's
    own). A tail is the tail of all requests due in the window, a rate
    is taken over all the window's tokens and seconds."""
    due = finished(window_requests(records, seconds))
    if name == "ttft_p90_ms":
        return percentile([ttft_ms(r) for r in due], 90)
    if name == "tpot_p50_ms":
        return percentile([tpot_ms(r) for r in due
                           if len(r["tokens"]) >= 16], 50)
    if name == "out_tok_s":
        return tokens_in_window(records, seconds) / seconds
    raise KeyError(f"no reduction for end-to-end metric {name!r}")
