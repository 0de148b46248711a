"""Scoped serving-stack bench: the paged/scheduler legs of bench.py.

``bench.py`` is the full-evidence run — train throughput, MFU, the
209M speculative crossover, long-context kernels — sized for the
chip. On a CPU-only box the train and big-model legs are multi-hour
non-starters, but the SERVING
legs (paged decode windows, spec windows, the mixed sampled co-tenant,
scheduler overload, open-loop arrivals) are exactly the surface the
device-resident-endgame work changes and they run in minutes at the
flagship-GQA shape. This driver re-uses bench.py's own measurement
functions verbatim (one methodology, two entry points) and emits one
JSON document tagged with the platform so a serving snapshot is never
mistaken for a full-evidence TPU round.

Usage::

    python tools/bench_serving.py            # all serving legs
    python tools/bench_serving.py --skip-openloop   # quick subset

Prints ONE JSON object to stdout (progress notes go to stderr).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time

REPO_NOTE = (
    "serving-stack legs only (bench.py measurement functions, "
    "unchanged); train/209M/long-context legs need the chip and "
    "are not re-run here"
)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--skip-openloop", action="store_true",
                    help="skip the (slowest) open-loop arrivals leg")
    ap.add_argument("--skip-overload", action="store_true",
                    help="skip the closed-loop scheduler overload leg")
    ap.add_argument("--skip-prefix", action="store_true",
                    help="skip the shared-prefix open-loop leg")
    args = ap.parse_args()

    import pathlib
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

    import jax

    import bench

    gqa = dataclasses.replace(bench.FLAGSHIP, n_kv_heads=2)
    out: dict = {
        "metric": "serving_bench",
        "platform": jax.devices()[0].platform,
        "devices": len(jax.devices()),
        "note": REPO_NOTE,
    }

    def leg(name, fn):
        t0 = time.perf_counter()
        print(f"[bench_serving] {name} ...", file=sys.stderr, flush=True)
        result = fn()
        print(f"[bench_serving] {name} done in "
              f"{time.perf_counter() - t0:.0f}s", file=sys.stderr,
              flush=True)
        return result

    out["host_round_trip_ms"] = round(
        leg("host_round_trip", bench.measure_host_round_trip), 2)

    (paged_tps, paged_sps, paged_host_sps, paged_overlap_tps,
     paged_overlap_speedup) = leg("paged_decode", lambda: (
        bench.measure_paged_decode(
            gqa, bench.PAGED_SLOTS, bench.DECODE_PROMPT, bench.DECODE_NEW,
            bench.PAGED_PAGE_SIZE)))
    out.update({
        "paged_decode_tokens_per_sec": round(paged_tps, 1),
        "paged_decode_steps_per_sec": round(paged_sps, 1),
        "paged_decode_hostloop_steps_per_sec": round(paged_host_sps, 1),
        "paged_decode_overlap_tokens_per_sec": round(paged_overlap_tps, 1),
        "paged_decode_overlap_speedup": round(paged_overlap_speedup, 3),
        "paged_decode_slots": bench.PAGED_SLOTS,
        "paged_decode_window": bench.PAGED_WINDOW,
    })

    out["paged_mixed_tokens_per_sec"] = round(leg("paged_mixed", lambda: (
        bench.measure_paged_mixed(
            gqa, bench.PAGED_SLOTS, bench.DECODE_PROMPT, bench.DECODE_NEW,
            bench.PAGED_PAGE_SIZE))), 1)

    spec_tps, spec_epp = leg("paged_spec", lambda: bench.measure_paged_spec(
        gqa, bench.PAGED_SLOTS, bench.DECODE_PROMPT, bench.DECODE_NEW,
        bench.PAGED_PAGE_SIZE, bench.SPEC_DRAFT_LEN))
    out["paged_spec_tokens_per_sec"] = round(spec_tps, 1)
    out["paged_spec_emitted_per_pass"] = round(spec_epp, 2)

    specw_tps, specw_epw = leg("paged_spec_window", lambda: (
        bench.measure_paged_spec_window(
            gqa, bench.PAGED_SLOTS, bench.DECODE_PROMPT, bench.DECODE_NEW,
            bench.PAGED_PAGE_SIZE, bench.SPEC_DRAFT_LEN,
            bench.SPEC_WINDOW_PASSES)))
    out.update({
        "paged_spec_window_passes": bench.SPEC_WINDOW_PASSES,
        "paged_spec_window_tokens_per_sec": round(specw_tps, 1),
        "paged_spec_window_emitted_per_window": round(specw_epw, 2),
        "paged_spec_window_speedup": round(specw_tps / spec_tps, 3),
    })

    if not args.skip_overload:
        sched_fifo, sched_strict = leg("sched_overload", lambda: (
            bench.measure_sched_overload(
                gqa, bench.PAGED_SLOTS, bench.DECODE_PROMPT,
                bench.SCHED_OVERLOAD_N_NEW, bench.PAGED_PAGE_SIZE)))
        out.update({
            "sched_overload_goodput_tokens_per_sec": round(
                sched_strict["goodput_tokens_per_sec"], 1),
            "sched_overload_fifo_goodput_tokens_per_sec": round(
                sched_fifo["goodput_tokens_per_sec"], 1),
            "sched_overload_interactive_wait_p99_ms":
                sched_strict["interactive_wait_p99_ms"],
            "sched_overload_fifo_interactive_wait_p99_ms":
                sched_fifo["interactive_wait_p99_ms"],
            # Exact client-side first-token latencies alongside the
            # bucket-edge histogram numbers above — disagreement
            # between the two is quantization artifact (SERVING.md
            # rung 26 strict-vs-fifo verdict), not scheduling.
            "sched_overload_interactive_ttft_p50_ms": round(
                sched_strict["interactive_ttft_p50_ms"], 1),
            "sched_overload_interactive_ttft_p99_ms": round(
                sched_strict["interactive_ttft_p99_ms"], 1),
            "sched_overload_fifo_interactive_ttft_p50_ms": round(
                sched_fifo["interactive_ttft_p50_ms"], 1),
            "sched_overload_fifo_interactive_ttft_p99_ms": round(
                sched_fifo["interactive_ttft_p99_ms"], 1),
            "sched_overload_batch_ttft_p99_ms": round(
                sched_strict["batch_ttft_p99_ms"], 1),
            "sched_overload_fifo_batch_ttft_p99_ms": round(
                sched_fifo["batch_ttft_p99_ms"], 1),
            "sched_overload_preemptions": sched_strict["preemptions"],
        })

    if not args.skip_openloop:
        openloop = leg("openloop", lambda: bench.measure_openloop(
            gqa, bench.DECODE_PROMPT, bench.PAGED_PAGE_SIZE))
        out.update({
            "sched_openloop_capacities": list(bench.OPENLOOP_CAPACITIES),
            "sched_openloop_rate_low_req_per_sec": round(
                openloop["rates"]["low"], 2),
            "sched_openloop_rate_high_req_per_sec": round(
                openloop["rates"]["high"], 2),
            **{
                f"sched_openloop_{mode}_{rate}_goodput"
                f"_tokens_per_sec_c{cap}": round(
                    lg["goodput_tokens_per_sec"], 1)
                for (cap, mode, rate), lg in openloop["legs"].items()
            },
            **{
                f"sched_openloop_{mode}_{rate}_wait_p99_ms_c{cap}":
                    lg["wait_p99_ms"]
                for (cap, mode, rate), lg in openloop["legs"].items()
            },
        })

    if not args.skip_prefix:
        prefix_ol = leg("prefix_openloop", lambda: (
            bench.measure_prefix_openloop(gqa, bench.PAGED_PAGE_SIZE)))
        out.update({
            "prefix_openloop_requests": prefix_ol["requests"],
            "prefix_openloop_rate_req_per_sec": round(
                prefix_ol["rate_req_per_sec"], 2),
            "prefix_openloop_bit_identical":
                prefix_ol["bit_identical"],
            "prefix_openloop_prefill_tokens_saved":
                prefix_ol["on"]["prefill_tokens_saved"],
            "prefix_openloop_prefill_saved_frac": round(
                prefix_ol["saved_frac"], 3),
            "prefix_openloop_cow_copies": prefix_ol["on"]["cow_copies"],
            "prefix_openloop_goodput_tokens_per_sec": round(
                prefix_ol["on"]["goodput_tokens_per_sec"], 1),
            "prefix_openloop_off_goodput_tokens_per_sec": round(
                prefix_ol["off"]["goodput_tokens_per_sec"], 1),
            "prefix_openloop_ttft_p50_ms": prefix_ol["on"]["ttft_p50_ms"],
            "prefix_openloop_off_ttft_p50_ms":
                prefix_ol["off"]["ttft_p50_ms"],
            "prefix_openloop_ttft_p99_ms": prefix_ol["on"]["ttft_p99_ms"],
            "prefix_openloop_off_ttft_p99_ms":
                prefix_ol["off"]["ttft_p99_ms"],
        })

    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
