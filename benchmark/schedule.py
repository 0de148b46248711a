"""The request schedule of one run: same work for every seed.

A traffic mix (``traffic/<name>.json``) fixes the shape of the work: a
grid of prompt and output lengths, the quantiles of its distributions,
and how they pair. A cell (``cells/<cell>.json``) fixes the load: an
arrival rate for an open loop, a client count for a closed one. From
those and the window's seconds the number of requests and the multiset
of (prompt, output) lengths follow, the same for every seed. The seed
chooses only the order the grid is offered in (open loop: within blocks
that each carry the same prompt work, with the jitter of each arrival
inside its slot of a fixed grid; closed loop: which client sends which
fixed chain of requests), and the token ids. Nothing here imports JAX: the load generator's process
reads this module too.
"""

from __future__ import annotations

import hashlib
import json
import math
import os
import statistics

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
_NORMAL = statistics.NormalDist()


def load_json(kind: str, name: str, root: str = HERE) -> dict:
    """``<root>/<kind>/<name>.json``: a mix, a cell's load, the peaks."""
    path = os.path.join(root, kind, name + ".json")
    with open(path) as fh:
        return json.load(fh)


def _quantile(dist: dict, u: float) -> float:
    kind = dist["dist"]
    if kind == "lognormal":
        return dist["median"] * math.exp(dist["sigma"] * _NORMAL.inv_cdf(u))
    if kind == "uniform":
        return dist["min"] + u * (dist["max"] - dist["min"])
    raise ValueError(f"unknown length distribution {kind!r}")


def length_grid(dist: dict, n: int) -> list[int]:
    """``n`` lengths at the distribution's mid-quantiles, clipped to its
    range and rounded to its ``multiple`` (a prefill chunk's tail is a
    compiled program of its own: the mix bounds how many it reaches)."""
    multiple = int(dist.get("multiple", 1))
    out = []
    for i in range(n):
        x = _quantile(dist, (i + 0.5) / n)
        x = min(max(x, dist["min"]), dist["max"])
        out.append(max(multiple, int(round(x / multiple)) * multiple))
    return out


def paired_grid(traffic: dict, n: int) -> list[tuple[int, int]]:
    """The mix's ``n`` (prompt, output) pairs. Prompt and output lengths
    are independent in the mixes here, so the pairing is a fixed shuffle
    (``pairing_seed``, part of the mix, not of the run)."""
    prompts = length_grid(traffic["prompt"], n)
    outputs = length_grid(traffic["output"], n)
    rng = np.random.default_rng(int(traffic.get("pairing_seed", 0)))
    outputs = [outputs[i] for i in rng.permutation(n)]
    return list(zip(prompts, outputs))


def prompt_tokens(seed: int, index: int, n: int, vocab: int) -> list[int]:
    """Request ``index``'s prompt: the same for the load generator,
    which sends it, and the check, which replays it. No two requests of
    a run start with the same token (warm-up requests have negative
    indices): the server's prefix cache shares down to one token, and a
    chance hit would leave a prefill piece of a length no other request
    has, a program of its own compiled inside the window. A mix that
    means to share prefixes will say so in its file."""
    rng = np.random.default_rng([int(seed), int(index) + (1 << 20)])
    tokens = rng.integers(0, vocab, size=n)
    base = int(np.random.default_rng([int(seed), 0xF1]).integers(0, vocab))
    tokens[0] = (base + int(index)) % vocab
    return tokens.tolist()


def _stratified_order(pairs: list, block: int, rng) -> list:
    """The grid in the order one seed offers it. With ``block`` > 1 the
    pairs are ranked by prompt length and cut into strata; every block
    of ``block`` consecutive requests draws one pair from each stratum,
    so each stretch of the run carries about the same prompt work
    whatever the seed (a few 2,048-token prompts are a tenth of all
    prompt tokens: left to chance, a quarter of one window got 5,900
    prompt tokens and a quarter of another 16,700). Which pair of a
    stratum lands in which block, and the order inside a block, are the
    seed's. ``block`` <= 1 is a plain shuffle."""
    n = len(pairs)
    if block <= 1 or n <= block:
        return [pairs[i] for i in rng.permutation(n)]
    n_blocks = -(-n // block)
    ranked = sorted(pairs)
    blocks: list = [[] for _ in range(n_blocks)]
    for lo in range(0, n, n_blocks):
        stratum = ranked[lo:lo + n_blocks]
        for j, k in enumerate(rng.permutation(len(stratum))):
            blocks[j].append(stratum[k])
    out = []
    for j in rng.permutation(n_blocks):
        out += [blocks[j][k] for k in rng.permutation(len(blocks[j]))]
    return out


def _open_loop(traffic, cell, seconds, rng) -> list[dict]:
    """Request ``k`` of a phase is due at ``(k + u) / rate``, ``u`` drawn
    from the seed in [0, 1): a jittered grid, the same count in every
    stretch for every seed (a steady mix; bursts are a mix of their
    own)."""
    rate = float(cell["rate_rps"])
    ramp = float(cell["ramp_s"])
    block = int(cell.get("order_block", 1))
    phases = []  # (first due, count)
    if ramp > 0:
        phases.append((-ramp, int(round(rate * ramp))))
    phases.append((0.0, int(round(rate * seconds))))
    requests = []
    for start, n in phases:
        pairs = _stratified_order(paired_grid(traffic, n), block, rng)
        dues = start + (np.arange(n) + rng.uniform(0, 1, size=n)) / rate
        for due, (prompt, n_new) in zip(np.sort(dues), pairs):
            requests.append({"due": float(due), "prompt": prompt,
                             "n_new": n_new, "client": -1})
    return requests


def _closed_loop(traffic, cell, seconds, rng) -> list[dict]:
    """Each client gets a chain of requests and sends the next when the
    last one ends. The chains are the mix's, not the seed's: chain ``j``
    takes its ``k``-th request from a fixed shuffle of every
    ``per_client``-th pair of the grid (so each turn spans the lengths),
    sends its first staggered over the ramp's first half and cuts it to
    the share ``(j + 0.5) / clients`` of its output (the residual life
    of a request already under way), so completions do not come in step
    and the window opens on a batch in its steady state. The seed deals
    the chains out among the clients and draws the token ids: which
    requests start, prefill and end inside the window is then the same
    for every seed. (Dealt anew at every turn, the prompt tokens
    admitted inside a window ran from 10,336 to 12,896 over six seeds
    and the rate followed them by 3%; PERF.md.)"""
    clients = int(cell["clients"])
    ramp = float(cell["ramp_s"])
    per_client = int(cell["requests_per_client"])
    pairs = paired_grid(traffic, clients * per_client)
    floor = max(1, int(traffic["output"]["min"]) // 4)
    fixed = np.random.default_rng(int(traffic.get("pairing_seed", 0)) + 1)
    turns = [[pairs[k::per_client][i] for i in fixed.permutation(clients)]
             for k in range(per_client)]
    requests = []
    for c, j in enumerate(rng.permutation(clients)):
        share = (j + 0.5) / clients
        for k, turn in enumerate(turns):
            prompt, n_new = turn[j]
            due = None
            if k == 0:
                due = -ramp + 0.5 * ramp * share
                n_new = max(floor, int(round(n_new * share)))
            requests.append({"due": due, "prompt": prompt,
                             "n_new": n_new, "client": c})
    return requests


def build(traffic: dict, cell: dict, seed: int, seconds: float,
          vocab: int) -> dict:
    """The plan one run offers: requests in sending order with their due
    time (seconds from the window's opening; negative in the ramp), and
    a digest of the work that is the same for every seed."""
    rng = np.random.default_rng([int(seed), 0x5EED])
    loop = cell["loop"]
    make = {"open": _open_loop, "closed": _closed_loop}[loop]
    requests = make(traffic, cell, seconds, rng)
    for i, r in enumerate(requests):
        r["index"] = i
    lengths = sorted((r["prompt"], r["n_new"]) for r in requests)
    digest = hashlib.sha256(json.dumps(lengths).encode()).hexdigest()[:16]
    return {
        "loop": loop, "seed": int(seed), "seconds": float(seconds),
        "vocab": int(vocab), "ramp_s": float(cell["ramp_s"]),
        "drain_s": float(cell["drain_s"]), "requests": requests,
        "work": {"requests": len(requests),
                 "prompt_tokens": sum(p for p, _ in lengths),
                 "output_tokens": sum(o for _, o in lengths),
                 "lengths_sha256_16": digest},
    }


def warmup_requests(traffic: dict, cell: dict) -> list[dict]:
    """Requests that reach the programs the mix's lengths can, before
    the ramp: alone, one per prefill tail and long enough to walk the
    decode windows from the cap down to one step on the device's carry;
    alone and short, so that each smaller window is also dispatched
    first from the host's tokens; then a few at once. (The server lowers
    a program anew for each window length, for host or carried tokens
    and for page tables fresh from the host or not; what is common the
    ramp reaches too.)"""
    multiple = int(traffic["prompt"].get("multiple", 1))
    chunk = int(cell.get("prefill_chunk", 64))
    window = int(cell.get("decode_window", 64))
    tails = sorted({(t % chunk) or chunk
                    for t in range(multiple, chunk + 1, multiple)})
    short = min(tails)
    singles = [{"prompt": 2 * chunk + short, "n_new": 2 * window + 2},
               {"prompt": chunk, "n_new": 2 * window}]
    w = window // 2
    while w >= 1:
        singles.append({"prompt": short, "n_new": w + 1})
        w //= 2
    for s in singles:
        s["at"] = None
    burst = [{"prompt": chunk + tails[i % len(tails)], "n_new": 24 + 8 * i,
              "at": 0.15 * i} for i in range(6)]
    return singles + burst
