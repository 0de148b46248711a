"""How ``correct`` is decided: served tokens against the plain reference.

Once the window has closed and the server's arrays are freed, a sample
of the requests it finished (drawn from the seed, the longest always in
it) is run through the reference of the cell's own block
(``references/<block>.py``), prompt and served tokens together.
At each served position the reference's best logit is compared with its
logit of the token the server chose: 0 where they agree, the gap where
the server's arithmetic (bf16, its kernels, its cache) tipped a near
tie. Two numbers are held to limits of their own, kept in the cell's
file with the readings they were set from (``check.set_from``; PERF.md
section 6): the mean gap over all checked tokens, which is steady and
grows with the square of the arithmetic's error, so its limit lies
between the program's largest reading and the int8 control's smallest
and tells the precisions apart; and the widest gap, the extreme of
thousands of tokens with a long tail, whose limit is a guard against
gross faults (another row's pages, a broken kernel's token) at twice the
largest reading on record or more, and which the control need not fail.
Decoding is greedy and output lengths are forced, so every served token
can be checked.
"""

from __future__ import annotations

import numpy as np

from benchmark import reduce, schedule

# Sequences are padded up to a multiple of this: the reference is a program
# per padded length, and three lengths (to the configuration's 3,072
# positions) are all in the compile cache after a run or two. With 256 a run
# whose sample had lengths no earlier run had spent 104 s compiling them.
PAD = 1024


def sample(records: list, seed: int, seconds: float, n: int,
           loop: str = "closed") -> list:
    """``n`` requests of the window, the longest among them: finished
    ones, or, where the window's own requests outlast it (a closed loop
    cut at its end), the tokens each was served until then."""
    due = reduce.live_in_window(records, seconds, loop)
    pool = reduce.finished(due) or [r for r in due
                                    if r.get("cut") and r["tokens"]]
    if not pool:
        return []
    pool.sort(key=lambda r: r["index"])
    longest = max(pool, key=lambda r: (r["prompt"] + len(r["tokens"]),
                                       -r["index"]))
    rest = [r for r in pool if r is not longest]
    rng = np.random.default_rng([int(seed), 0xC0DE])
    take = rng.permutation(len(rest))[:max(0, n - 1)]
    return [longest] + [rest[i] for i in sorted(take)]


def _sequences(chosen: list, seed: int, vocab: int):
    """Each request's prompt with its served tokens, and the position
    whose logits predict the first of them."""
    sequences, first = [], []
    for r in chosen:
        prompt = schedule.prompt_tokens(seed, r["index"], r["prompt"], vocab)
        seq = prompt + list(r["tokens"])
        seq += [0] * (-len(seq) % PAD)  # causal: the tail changes nothing
        sequences.append(seq)
        first.append(r["prompt"] - 1)
    return sequences, first


def token_gaps(model: dict, weights: dict, chosen: list, seed: int,
               vocab: int, reference) -> dict:
    """Per checked token, how far the served token's reference logit
    lies below the reference's best."""
    sequences, first = _sequences(chosen, seed, vocab)
    gaps = []
    for r, rows in zip(chosen, reference.logits(model, weights, sequences,
                                                first)):
        served = np.asarray(r["tokens"])
        rows = rows[:len(served)]
        gaps.append(rows.max(axis=-1) - rows[np.arange(len(served)), served])
    gaps = np.concatenate(gaps) if gaps else np.zeros((0,))
    return {"tokens": int(gaps.size), "requests": len(chosen),
            "differ": int((gaps > 0).sum()),
            "token_gap_max": float(gaps.max()) if gaps.size else 0.0,
            "token_gap_mean": float(gaps.mean()) if gaps.size else 0.0}


def control_gaps(model: dict, weights: dict, chosen: list, seed: int,
                 vocab: int, reference, quant: str = "int8") -> dict:
    """The control's reading of the same two numbers: the reference put
    in the program's place and computed in int8, the precision below the
    one the configuration serves in. It need not decode: at each
    position of the same prompts and served tokens, the token the lower
    precision puts first, and how far the float32 reference's logit of
    it lies below the reference's best."""
    sequences, first = _sequences(chosen, seed, vocab)
    exact = reference.logits(model, weights, sequences, first)
    rough = reference.logits(model, weights, sequences, first, quant=quant)
    gaps = []
    for r, rows, low in zip(chosen, exact, rough):
        n = len(r["tokens"])
        rows, picked = rows[:n], low[:n].argmax(axis=-1)
        gaps.append(rows.max(axis=-1) - rows[np.arange(n), picked])
    gaps = np.concatenate(gaps)
    return {"tokens": int(gaps.size), "requests": len(chosen),
            "differ": int((gaps > 0).sum()),
            "token_gap_max": float(gaps.max()),
            "token_gap_mean": float(gaps.mean())}


def verdict(numbers: dict, limits: dict, say=print) -> bool:
    """Each number beside its limit; true only if every one holds."""
    ok = numbers["tokens"] > 0
    say(f"[check] {numbers['requests']} requests, {numbers['tokens']} "
        f"served tokens against the float32 reference, "
        f"{numbers['differ']} differ from its choice")
    for name, limit in limits.items():
        value = numbers[name]
        held = value <= limit
        ok = ok and held
        say(f"[check] {name} = {value:.6g}  limit {limit:.6g}  "
            f"{'ok' if held else 'FAILED'}")
    return ok
