"""Run one cell of ``BENCHMARK.json`` once and print its one JSON line.

    python3 -m benchmark.run --workload starcoder2-3b.batchgen --seed 7 \\
        --seconds 48 --trace 0

A new process: it finds the chip (no chip, or fewer chips than the cell
asks for, is an error and never a CPU run), starts the serve payload
through ``start_runtime``, has the load generator warm it up, offers the
ramp, measures the window, lets the window's requests finish, shuts the
server down, frees its arrays and checks a sample of the served tokens
against the float32 reference. What the last line cannot carry (the
schedule's digest, per-request records, the set-up split, the traced
programs) goes to ``<out>/<cell>/seed<seed>-trace<t>.json``.
"""

from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402


def _say(text: str) -> None:
    print(text, flush=True)


def one_chip_only() -> None:
    """Before JAX starts: a one-chip cell opens one chip, whatever the
    host holds. An operator's own setting of any of these wins."""
    os.environ.setdefault("TPU_VISIBLE_CHIPS", "0")
    os.environ.setdefault("TPU_CHIPS_PER_PROCESS_BOUNDS", "1,1,1")
    os.environ.setdefault("TPU_PROCESS_BOUNDS", "1,1,1")


def find_chip(cell, allow_platform: str = "tpu") -> dict:
    """The device as JAX reports it, or SystemExit when it is not the
    accelerator this cell was defined on."""
    import jax

    # Every program goes to the persistent cache, however fast it
    # compiled: the second run of a cell in a checkout compiles nothing.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if device["platform"] != allow_platform:
        raise SystemExit(
            f"benchmark: JAX found {device}, not a {allow_platform}; a "
            "cell is measured on the chip or not at all")
    if device["count"] != cell.chips:
        raise SystemExit(
            f"benchmark: cell {cell.name} needs {cell.chips} chip(s), "
            f"JAX sees {device['count']}")
    return device


def measure(cell, seed: int, seconds: float, trace_on: bool, device: dict,
            *, overrides: dict | None = None, t_process: float = T_PROCESS,
            out_dir: str | None = None, layers: bool | None = None,
            say=_say) -> dict:
    """Everything after the chip is found; returns the result line as a
    dict. Tests call this on the CPU at a probe size (``layers``: report
    the per-layer metrics although nothing is traced)."""
    from benchmark import check, metrics, reduce, roofline, schedule, trace
    from benchmark.harness import Harness

    model = cell.config["model"]
    plan = schedule.build(cell.traffic, cell.load, seed, seconds,
                          model["vocab"])
    say(f"[bench] {cell.name} seed {seed}: {plan['work']} "
        f"({plan['loop']} loop, {seconds:g} s window)")
    harness = Harness(cell, t_process=t_process, overrides=overrides)
    try:
        harness.start(device["platform"])
        run = harness.run_window(plan, warm=True, trace=trace_on)
        events = None
        if trace_on:
            events = trace.read_xplane(trace.find_xplane(run["trace_dir"]))
    finally:
        harness.stop()
    records = run["records"]
    window = reduce.finished(reduce.window_requests(records, seconds))
    live = reduce.live_in_window(records, seconds, plan["loop"])
    failed = reduce.failed(live)
    setup = dict(harness.split)
    setup["ramp_s"] = plan["ramp_s"]
    setup["compile_s"] = harness.meter.seconds
    setup["setup_s"] = run["t0"] - t_process
    ctx = {
        "cell": cell, "plan": plan, "seconds": seconds, "records": records,
        "window": window, "stats_start": run["stats_start"],
        "stats_end": run["stats_end"],
        "samples": [s for s in run["samples"] if 0 <= s["t"] < seconds],
        "events": events, "setup": setup, "memory": run["memory"],
        "window_compiles": len(run["window_lowered"]),
        "peak": roofline.peaks(device["kind"]) if trace_on else None,
    }
    result_metrics: dict = {}
    breakdown = None
    device_out = {**device, **run["memory"]}
    if trace_on:
        lo, hi = trace.span(events)
        ctx["trace_span"] = (run["trace_at"] + lo, run["trace_at"] + hi)
        device_out["busy_s"] = trace.busy_seconds(events)
        device_out["window_s"] = hi - lo
        breakdown = {"device_ops": trace.top_ops(events, 10),
                     "idle_gaps": _label_gaps(events, run, 10)}
    if trace_on if layers is None else layers:
        readers = metrics.readers(os.path.join(cell.root, "metrics"))
        for m in cell.per_layer:
            value = readers[m["name"]](ctx)
            if value is not None:
                result_metrics[m["name"]] = {"value": value,
                                             "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            value = (setup["setup_s"] if m["name"] == "setup_s" else
                     reduce.end_to_end(m["name"], records, seconds))
            result_metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    t_check = time.monotonic()
    chosen = check.sample(records, seed, seconds,
                          int(cell.load["check"]["requests"]), plan["loop"])
    weights = cell.reference.make_weights(model)
    numbers = check.token_gaps(model, weights, chosen, seed, model["vocab"],
                               cell.reference)
    del weights
    say(f"[check] the reference took {time.monotonic() - t_check:.1f} s")
    limits = cell.load["check"]["limits"]
    correct = check.verdict(numbers, limits, say)
    if failed:
        say(f"[check] {len(failed)} request(s) of the window failed, the "
            f"first: {failed[0]['error']}  FAILED")
    lowered = run["window_lowered"]
    say(f"[check] window_compiles = {len(lowered)}  limit 0  "
        f"{'ok' if not lowered else 'FAILED: ' + ', '.join(lowered)}")
    dry = reduce.clients_dry(plan["requests"], records, seconds)
    say(f"[check] clients_dry = {dry['count']}  " + (
        "ok" if not dry["count"] else
        f"the first at {dry['first_s']:.1f} s: their rows stood empty from "
        "then on and out_tok_s reads less than the server could give (not "
        "part of correct)"))
    correct = correct and not failed and not lowered and bool(live)
    line = {"correct": bool(correct), "attempted": len(live),
            "failed": len(failed), "metrics": result_metrics,
            "device": device_out}
    if breakdown is not None:
        line["breakdown"] = breakdown
    # every number compared, beside its limit: last in the line
    line["check"] = {
        **{name: {"value": numbers[name], "limit": limit}
           for name, limit in limits.items()},
        "failed": {"value": len(failed), "limit": 0},
        "window_compiles": {"value": len(lowered), "limit": 0}}
    if out_dir:
        _write_report(out_dir, cell, seed, trace_on, line, plan, records,
                      setup, numbers, run, events, harness.meter,
                      {"clients_dry": dry,
                       "decode_work": ctx.get("decode_work")})
    return line


def _label_gaps(events: list, run: dict, n: int) -> list:
    """The longest idle gaps, each named by what the server's counters
    said nearest to it, and summed by that name."""
    from benchmark import trace

    samples = run["samples"]
    total: dict = {}
    for lo, hi in trace.idle_gaps(events)[:200]:
        at = run["trace_at"] + lo
        s = min(samples, key=lambda s: abs(s["t"] - at), default=None)
        label = "no_sample" if s is None else (
            f"in_flight_{s['in_flight']}_queued_"
            f"{s['sched_queue_depth_interactive'] + s['sched_queue_depth_batch']}"
            f"_window_in_flight_{s['overlap_inflight_depth']}")
        total[label] = total.get(label, 0.0) + (hi - lo)
    return [[k, v] for k, v in
            sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def _write_report(out_dir, cell, seed, trace_on, line, plan, records,
                  setup, numbers, run, events, meter, more) -> None:
    from benchmark import trace

    path = os.path.join(out_dir, cell.name)
    os.makedirs(path, exist_ok=True)
    report = {
        "line": line, "work": plan["work"], "setup": setup,
        "check": numbers, "samples": run["samples"],
        "gc_pauses": run["gc_pauses"],
        "loadgen_stall_max_s": run["loadgen_stall_max_s"],
        "lowered": [[name, at - run["t0"]] for name, at in meter.programs],
        "records": records, **more,
    }
    name = f"seed{seed}-trace{int(trace_on)}"
    if events:
        report["programs"] = trace.program_table(events)
        with gzip.open(os.path.join(path, name + ".events.json.gz"),
                       "wt") as fh:
            json.dump(events, fh)
    with open(os.path.join(path, name + ".json"), "w") as fh:
        json.dump(report, fh)


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--out", default=None,
                        help="directory for the run's report "
                             "(default: <checkout>/chiprun_out/benchmark)")
    args = parser.parse_args(argv)

    from benchmark import cellspec

    cell = cellspec.load_cell(args.workload)
    if cell.chips == 1:
        one_chip_only()
    device = find_chip(cell)
    out_dir = args.out or os.path.join(cellspec.REPO, "chiprun_out",
                                       "benchmark")
    line = measure(cell, args.seed, args.seconds, bool(args.trace), device,
                   out_dir=out_dir)
    for name, held in line["check"].items():  # the record keeps stderr's end
        print(f"[check] {name} = {held['value']:.6g}  limit "
              f"{held['limit']:.6g}", file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
