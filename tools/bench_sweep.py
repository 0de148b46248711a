"""Throughput sweep over flagship train-step variants on the visible devices.

Measures tokens/s for combinations of attention kind, remat, and per-device
batch so bench.py's defaults are chosen from data rather than guesses:

    python tools/bench_sweep.py [--steps 6] [--seq 512]

Uses bench.measure() so the sweep's numbers are directly comparable to the
headline benchmark. Each variant compiles fresh (expect ~20-40s/compile on
TPU the first time). ``--json PATH`` writes the full per-variant record
(the committed SWEEP_r{N}.json artifact — VERDICT r2 #2: the ceiling
claim must be machine-checkable, so every variant's number ships).
"""

from __future__ import annotations

import argparse
import dataclasses
import itertools
import json
import os
import platform
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from bench import measure  # noqa: E402
from __graft_entry__ import FLAGSHIP  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=6)
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--attention", nargs="*", default=["naive", "flash"])
    ap.add_argument("--batch", nargs="*", type=int, default=[32, 64, 128])
    # remat modes: "off", "full", "dots" (off = no checkpointing at all).
    ap.add_argument("--remat", nargs="*", default=["off", "full", "dots"])
    ap.add_argument("--fused-xent", action="store_true",
                    help="also sweep fused_xent=True for each variant")
    ap.add_argument("--json", help="write the per-variant record here")
    args = ap.parse_args()

    import jax

    # Resume: variants already recorded in --json are skipped, so a sweep
    # interrupted by a wall-clock cap continues instead of restarting —
    # the artifact is written ATOMICALLY after every variant.
    records = []
    extra = {}  # non-sweep keys (e.g. bench_breakdown.py's "breakdown")
    if args.json and os.path.exists(args.json):
        with open(args.json, encoding="utf-8") as fh:
            existing = json.load(fh)
        records = existing.get("variants", [])
        extra = {
            k: v for k, v in existing.items()
            if k not in ("platform", "device_kind", "n_devices",
                         "timestamp", "host", "methodology", "variants")
        }

    def variant_key(r):
        # seq/steps are part of the identity: resuming with different
        # measurement parameters must re-measure, not silently keep the
        # old numbers under a rewritten header.
        return (r["attention"], r["remat"], r["batch_per_device"],
                r["fused_xent"], r["seq"], r["steps"])

    # Only SUCCESSFUL records pin their variant; failures are retried on
    # every resume (a transient runtime error must not ship as a
    # permanent "fails to compile" in the recorded artifact) — the retry outcome
    # REPLACES the failed record either way.
    done = {variant_key(r) for r in records if r.get("tokens_per_sec")}

    def record_outcome(record):
        records[:] = [r for r in records
                      if variant_key(r) != variant_key(record)]
        records.append(record)
        flush_json()

    def flush_json():
        if not args.json:
            return
        tmp = args.json + ".tmp"
        with open(tmp, "w", encoding="utf-8") as fh:
            json.dump({
                "platform": jax.devices()[0].platform,
                "device_kind": jax.devices()[0].device_kind,
                "n_devices": jax.device_count(),
                "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
                "host": platform.node(),
                "methodology": (
                    "bench.measure(): steps scanned inside one jit, "
                    "double warmup, best-of-2 timed runs, scalar-fetch "
                    "sync (see bench.py docstring)"
                ),
                "variants": records,
                **extra,
            }, fh, indent=1)
        os.replace(tmp, args.json)

    xent_modes = [False, True] if args.fused_xent else [False]
    for attn, remat, bpd, fx in itertools.product(
        args.attention, args.remat, args.batch, xent_modes
    ):
        record = {"attention": attn, "remat": remat,
                  "batch_per_device": bpd, "fused_xent": fx,
                  "seq": args.seq, "steps": args.steps}
        # Membership through variant_key(record) — the SAME key function
        # that indexed the loaded records, so the two can never drift
        # (a 4-field literal here once silently re-measured everything).
        if variant_key(record) in done:
            continue
        cfg = dataclasses.replace(
            FLAGSHIP, attention=attn, remat=remat != "off",
            remat_policy=remat if remat != "off" else "full",
            fused_xent=fx,
        )
        label = (f"attn={attn:5s} remat={remat:4s} bpd={bpd:3d} "
                 f"fused_xent={int(fx)}")
        try:
            tps, loss, _ = measure(cfg, bpd, args.seq, args.steps)
        except Exception as e:  # OOM etc — report and keep sweeping
            print(f"{label}  FAILED: "
                  f"{type(e).__name__}: {str(e)[:120]}", flush=True)
            record_outcome({**record, "tokens_per_sec": None,
                            "error": f"{type(e).__name__}: {str(e)[:200]}"})
            continue
        record_outcome({**record, "tokens_per_sec": round(tps, 1),
                        "final_loss": round(loss, 4)})
        print(f"{label}  {tps:10.0f} tok/s  loss={loss:.3f}", flush=True)

    scored = [r for r in records if r.get("tokens_per_sec")]
    if scored:
        best = max(scored, key=lambda r: r["tokens_per_sec"])
        print(f"\nbest: attn={best['attention']} remat={best['remat']} "
              f"batch_per_device={best['batch_per_device']} "
              f"fused_xent={int(best['fused_xent'])}  "
              f"{best['tokens_per_sec']:.0f} tok/s")
    if args.json:
        print(f"wrote {args.json}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
