"""The numbers ``correct`` compares in a cell, request by request and by
position, for the program and for the int8 control.

    chiprun --timeout 1800 -- python tools/window_gap_readings.py \\
        --workload smallthinker-21ba3b.longmix --seeds 41001,41002,41003

``benchmark.control`` gives a sample's two numbers; this gives what they
are made of, to tell arithmetic from a fault that grows with the
context: one window a seed on one server (``control.windows``), the
cell's own sample of ``check.requests`` requests (``check.sample``),
and for each request its prompt and final length beside the mean and
largest gap of its served tokens, for the program's tokens and for the
tokens the reference computed in int8 would have put first
(``check.control_gaps``' reading), each also split at ``--split``
positions (the window of a block with window layers: a served token
below it was computed with every key in sight in every layer, one
above it with the window layers' oldest pages given back). A fault in
the bound, the trim or the table's first position would show as the
program's gap past the split standing apart from its gap before it
while the control's does not. Lines go to stdout and, whole, to
``chiprun_out/window_gap_readings.json``. Not part of a run.
"""

from __future__ import annotations

import argparse
import json
import os
import sys


def _parts(gaps, at, split):
    """Mean and largest gap, and the means of the served positions
    below and from ``split`` on (``at``: each gap's position)."""
    below, past = gaps[at < split], gaps[at >= split]
    return {"mean": float(gaps.mean()), "max": float(gaps.max()),
            "differ": int((gaps > 0).sum()),
            "tokens_below": int(below.size), "tokens_past": int(past.size),
            "mean_below": float(below.mean()) if below.size else None,
            "mean_past": float(past.mean()) if past.size else None,
            "max_below": float(below.max()) if below.size else None,
            "max_past": float(past.max()) if past.size else None}


def _by_1k(gaps, at):
    """[first position, tokens, mean gap] of each 1,024 positions."""
    return [[int(lo), int((at // 1024 == lo // 1024).sum()),
             float(gaps[at // 1024 == lo // 1024].mean())]
            for lo in sorted(set((at // 1024 * 1024).tolist()))]


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--seconds", type=float, default=48.0)
    parser.add_argument("--split", type=int, default=4096)
    parser.add_argument("--out", default=os.path.join(
        "chiprun_out", "window_gap_readings.json"))
    args = parser.parse_args()
    sys.path.insert(0, os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))

    import numpy as np

    from benchmark import cellspec, check, control, run

    def say(text):
        print(text, flush=True)

    cell = cellspec.load_cell(args.workload)
    if cell.chips == 1:
        run.one_chip_only()
    device = run.find_chip(cell)
    seeds = [int(s) for s in args.seeds.split(",")]
    by_seed = control.windows(cell, device, seeds, args.seconds, None, say)
    model, reference = cell.config["model"], cell.reference
    n = int(cell.load["check"]["requests"])
    weights = reference.make_weights(model)
    rows, samples = [], []
    for seed, records in by_seed.items():
        chosen = check.sample(records, seed, args.seconds, n,
                              cell.load["loop"])
        sequences, first = check._sequences(chosen, seed, model["vocab"])
        exact = reference.logits(model, weights, sequences, first)
        rough = reference.logits(model, weights, sequences, first,
                                 quant="int8")
        sums = {"program": [], "reference-int8": []}
        places = []
        for r, full, low in zip(chosen, exact, rough):
            served = np.asarray(r["tokens"])
            k = len(served)
            full, at = full[:k], r["prompt"] + np.arange(k)
            best = full.max(axis=-1)
            sides = {
                "program": best - full[np.arange(k), served],
                "reference-int8":
                    best - full[np.arange(k), low[:k].argmax(axis=-1)]}
            row = {"seed": seed, "index": r["index"], "prompt": r["prompt"],
                   "served": k, "final": r["prompt"] + k}
            places.append(at)
            for side, gaps in sides.items():
                row[side] = _parts(gaps, at, args.split)
                sums[side].append(gaps)
            say("[gaps] " + json.dumps(row, default=lambda x: x).replace(
                '"reference-int8"', '"int8"'))
            rows.append(row)
        sample = {"seed": seed, "requests": len(chosen)}
        for side, parts in sums.items():
            gaps = np.concatenate(parts)
            sample[side] = {"tokens": int(gaps.size),
                            "token_gap_mean": float(gaps.mean()),
                            "token_gap_max": float(gaps.max()),
                            "by_1k": _by_1k(gaps, np.concatenate(places))}
        say("[sample] " + json.dumps(sample))
        samples.append(sample)
    for key, limit in cell.load["check"]["limits"].items():
        for side in ("program", "reference-int8"):
            values = [s[side][key] for s in samples]
            say(f"[sample] {side} {key}: smallest {min(values):.6g}, "
                f"largest {max(values):.6g}, limit now {limit:.6g}")
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as fh:
        json.dump({"workload": args.workload, "split": args.split,
                   "device": device, "requests": rows, "samples": samples},
                  fh)
    return 0


if __name__ == "__main__":
    sys.exit(main())
