"""Read the numbers ``correct`` compares, for the program or its control.

    python3 -m benchmark.control --workload starcoder2-3b.batchgen \\
        --seeds 7001,7002,7003 --tag program
    python3 -m benchmark.control --workload starcoder2-3b.batchgen \\
        --seeds 7001,7002,7003 --tag int8kv \\
        --override serving_kv_dtype=int8,serving_pages=640

One process. Windows at the cell's own load, one per seed, on one
server: the program as configured, or, with ``--override``, the program
with an option of its own changed (``serving_kv_dtype = "int8"`` stores
keys and values in int8). Then, with the server gone, the float32
reference over each window's sample, as a run's check does, and, for the
program as configured, the control's reading of the same sample: the
reference put in the program's place and computed in int8, the precision
below the bf16 the configuration serves in. A cell's limits are set
between the program's largest reading and the control's smallest
(PERF.md has both). Not part of a run: the driver never calls this.
"""

from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402


def windows(cell, device, seeds, seconds, overrides, say):
    """Records of one window per seed, one server for all."""
    from benchmark import reduce, schedule
    from benchmark.harness import Harness

    harness = Harness(cell, t_process=T_PROCESS, overrides=overrides)
    out = {}
    try:
        harness.start(device["platform"])
        for i, seed in enumerate(seeds):
            plan = schedule.build(cell.traffic, cell.load, seed, seconds,
                                  cell.config["model"]["vocab"])
            got = harness.run_window(plan, warm=(i == 0), trace=False,
                                     tag=f"-{seed}")
            records = got["records"]
            out[seed] = records
            say("[control] " + json.dumps({
                "seed": seed, "requests": len(records),
                "out_tok_s": reduce.tokens_in_window(records, seconds)
                / seconds,
                "failed": len(reduce.failed(records)),
                "lowered_in_window": got["window_lowered"]}))
            while harness.stats()["in_flight"]:
                time.sleep(0.5)
    finally:
        harness.stop()
    return out


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--seconds", type=float, default=48.0)
    parser.add_argument("--override", default="",
                        help="key=value for [payload], e.g. "
                             "serving_kv_dtype=int8,serving_pages=640")
    parser.add_argument("--tag", default="program",
                        help="names the output file")
    args = parser.parse_args(argv)

    from benchmark import cellspec, check, run

    def say(text):
        print(text, flush=True)

    cell = cellspec.load_cell(args.workload)
    if cell.chips == 1:
        run.one_chip_only()
    device = run.find_chip(cell)
    seeds = [int(s) for s in args.seeds.split(",")]
    overrides = {}
    for kv in filter(None, args.override.split(",")):
        key, value = kv.split("=", 1)
        overrides[key] = int(value) if value.isdigit() else value
    by_seed = windows(cell, device, seeds, args.seconds, overrides or None,
                      say)
    model = cell.config["model"]
    n = int(cell.load["check"]["requests"])
    reference = cell.reference
    weights = reference.make_weights(model)
    rows = []
    for seed, records in by_seed.items():
        chosen = check.sample(records, seed, args.seconds, n,
                              cell.load["loop"])
        numbers = check.token_gaps(model, weights, chosen, seed,
                                   model["vocab"], reference)
        numbers.update(side=args.tag, seed=seed)
        say("[control] " + json.dumps(numbers))
        rows.append(numbers)
        if not overrides:
            numbers = check.control_gaps(model, weights, chosen, seed,
                                         model["vocab"], reference)
            numbers.update(side="reference-int8", seed=seed)
            say("[control] " + json.dumps(numbers))
            rows.append(numbers)
    for key, limit in cell.load["check"]["limits"].items():
        for side in sorted({r["side"] for r in rows}):
            values = [r[key] for r in rows if r["side"] == side]
            say(f"[control] {side} {key}: smallest {min(values):.6g}, "
                f"largest {max(values):.6g}, limit now {limit:.6g}")
    path = os.path.join(cellspec.REPO, "chiprun_out", "benchmark", cell.name)
    os.makedirs(path, exist_ok=True)
    with open(os.path.join(path, f"control-{args.tag}.json"), "w") as fh:
        json.dump({"seconds": args.seconds, "device": device,
                   "override": overrides, "rows": rows}, fh)
    return 0


if __name__ == "__main__":
    sys.exit(main())
