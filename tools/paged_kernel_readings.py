"""The paged-attention decode kernel alone on the chip: three readings.

    chiprun -- python tools/paged_kernel_readings.py [--tree DIR]
                                                     [--dead-position N]

One jitted program calls ``paged_decode_attention`` in a ``fori_loop``
over the layers of a pool at the benchmark cell's shapes (64 rows, 24
query / 2 KV heads of 128, 768 pages of 128 tokens, 24 pages a row, 16
layers, bf16) and is timed on the host's clock, best of several after
a warm-up, and once more under the profiler, where the kernel's own
events are read (PERF.md section 5 quotes both):

  (a) every row at position 0: what a program costs before it streams;
  (b) the cell's mix: 36 rows at 128 to 3,072 tokens (mean about 1,100,
      330 pages) among 28 dead ones;
  (c) every row at position 3,071: the streaming rate;
  and, where dead rows do nothing, all 64 dead: the grid's own cost.

``--dead-position`` is what the call site hands the kernel for a dead
row: -1 since PR 31 (the row does nothing), 0 before it (an empty
slot's length, which cost a page). ``--tree`` runs another checkout's
``kvedge_tpu`` (the parent's, unpacked beside this one). Nothing here
runs on the CPU: a timing taken there is not a reading.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

ROWS, HEADS, KV, DH, PAGE, MAX_PAGES, POOL_PAGES, LAYERS = (
    64, 24, 2, 128, 128, 24, 768, 16)
REPEATS = 8  # passes over the layers inside one timed program


def _readings(dead_position: int) -> dict:
    """name -> (positions [ROWS], tables [ROWS, MAX_PAGES]) as numpy."""
    import numpy as np

    rng = np.random.default_rng(31)

    def tables_for(pages_of_row):
        ids = rng.permutation(ROWS * MAX_PAGES) % POOL_PAGES
        tables = np.zeros((ROWS, MAX_PAGES), np.int32)
        at = 0
        for row, n in enumerate(pages_of_row):
            tables[row, :n] = ids[at:at + n]
            at += n
        return tables

    # (b): lengths 128 + 2,944 u^2 over 36 evenly spaced u, dealt out
    # among the 64 rows by a seeded permutation.
    u = (np.arange(36) + 0.5) / 36
    lengths = np.zeros(ROWS, np.int64)
    lengths[rng.permutation(ROWS)[:36]] = (128 + 2944 * u ** 2).astype(int)
    mix_pos = np.where(lengths > 0, lengths - 1, dead_position)
    mix_pages = np.where(lengths > 0, (lengths - 1) // PAGE + 1, 0)
    full = np.full(ROWS, MAX_PAGES * PAGE - 1)
    readings = {
        "a_position_0": (np.zeros(ROWS, np.int64),
                         tables_for(np.ones(ROWS, int))),
        "b_cell_mix": (mix_pos, tables_for(mix_pages)),
        "c_position_3071": (full, tables_for(np.full(ROWS, MAX_PAGES))),
    }
    if dead_position < 0:  # what 64 programs cost that do nothing
        readings["all_dead"] = (np.full(ROWS, dead_position),
                                tables_for(np.zeros(ROWS, int)))
    return readings


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--tree", default=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    parser.add_argument("--dead-position", type=int, default=-1)
    parser.add_argument("--set", action="append", default=[],
                        metavar="NAME=INT",
                        help="measure with another value of one of the "
                             "kernel module's constants")
    parser.add_argument("--out", default="")
    args = parser.parse_args()
    sys.path.insert(0, os.path.abspath(args.tree))

    import jax
    import jax.numpy as jnp
    import numpy as np

    from kvedge_tpu.ops import paged_attention
    from kvedge_tpu.ops.paged_attention import paged_decode_attention

    for name, value in (item.split("=") for item in args.set):
        assert hasattr(paged_attention, name), name
        setattr(paged_attention, name, int(value))
    device = jax.devices()[0]
    if device.platform != "tpu":
        print(json.dumps({"ok": False, "why": "no TPU: nothing was timed"}))
        return 1

    keys = jax.random.split(jax.random.PRNGKey(31), 3)
    shape = (LAYERS, POOL_PAGES, PAGE, KV * DH)
    pool_k = jax.random.normal(keys[0], shape, jnp.bfloat16)
    pool_v = jax.random.normal(keys[1], shape, jnp.bfloat16)
    q = jax.random.normal(keys[2], (ROWS, HEADS, DH), jnp.bfloat16)

    @jax.jit
    def passes(q, pool_k, pool_v, tables, positions):
        def layer(i, q):
            out = paged_decode_attention(
                q, pool_k, pool_v, tables, positions, i % LAYERS)
            # The next layer's queries depend on this layer's output, as
            # in the model; a dead row's output may be anything.
            return jnp.where(positions[:, None, None] >= 0,
                             q + out * 0.001, q).astype(q.dtype)
        return jax.lax.fori_loop(0, LAYERS * REPEATS, layer, q)

    report = {"device": device.device_kind, "tree": args.tree,
              "dead_position": args.dead_position,
              "constants": {k: v for k, v in vars(paged_attention).items()
                            if k.startswith("_") and isinstance(v, int)},
              "readings": {}}
    out = args.out or os.path.join("chiprun_out", "paged_kernel_readings.json")
    os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
    outputs = {}
    for name, (positions, tables) in _readings(args.dead_position).items():
        operands = (q, pool_k, pool_v, jnp.asarray(tables, jnp.int32),
                    jnp.asarray(positions, jnp.int32))
        passes(*operands).block_until_ready()
        # One layer's output, kept so that two trees' bits can be compared.
        outputs[name] = np.asarray(jax.jit(paged_decode_attention)(
            *operands, 3).astype(jnp.float32))
        walls = []
        for _ in range(7):
            t0 = time.perf_counter()
            passes(*operands).block_until_ready()
            walls.append(time.perf_counter() - t0)
        calls = LAYERS * REPEATS
        with tempfile.TemporaryDirectory() as trace_dir:
            with jax.profiler.trace(trace_dir):
                passes(*operands).block_until_ready()
            kernel = _kernel_events(trace_dir)
        live_pages = int(np.sum(np.maximum(positions, -1) // PAGE + 1))
        row = {
            "live_rows": int(np.sum(positions >= 0)),
            "live_pages": live_pages,
            "wall_us_a_layer": min(walls) / calls * 1e6,
            "kernel": kernel,
        }
        if kernel:
            us = kernel["us_each"]
            row["kernel_gb_s"] = live_pages * 2 * PAGE * KV * DH * 2 / us / 1e3
        report["readings"][name] = row
        print(name, json.dumps(row), flush=True)
    with open(out, "w") as f:
        json.dump(report, f, indent=1)
    np.savez_compressed(os.path.splitext(out)[0] + ".npz", **outputs)
    return 0


def _kernel_events(trace_dir: str) -> dict | None:
    """The costliest operation of the traced program that is not a
    fusion: the Mosaic kernel's custom call, by whatever name."""
    from benchmark import trace

    events = [e for e in trace.read_xplane(trace.find_xplane(trace_dir))
              if e["line"] == trace.OPS_LINE]
    by_name: dict = {}
    for e in events:
        by_name.setdefault(e["name"], []).append(e["dur"])
    calls = {n: d for n, d in by_name.items()
             if "fusion" not in n and "while" not in n}
    if not calls:
        return None
    name = max(calls, key=lambda n: sum(calls[n]))
    durs = calls[name]
    return {"name": name, "runs": len(durs),
            "us_each": sum(durs) / len(durs) * 1e6,
            "others": sorted(((n, len(d), sum(d) / len(d) * 1e6)
                              for n, d in by_name.items() if n != name),
                             key=lambda t: -t[1] * t[2])[:5]}


if __name__ == "__main__":
    sys.exit(main())
