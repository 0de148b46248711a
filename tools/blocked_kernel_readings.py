"""The paged-attention decode kernel's two forms and the gather, alone
on the chip, at two full layers' shapes.

    chiprun -- python tools/blocked_kernel_readings.py

For each shape (``k-exaone-236b-a23b``'s full layer: 64 rows, 64 query /
8 KV heads of 128, a table of 64 pages of 128 positions, where the
whole form's V image does not fit the scratch; ``smallthinker-21ba3b``'s:
28 / 4 heads, the same table, where both forms fit) and each of the
forms that runs there (``whole``, ``blocked``, and the gather as
models/kvcache.py writes it), one jitted program makes the call in a
``fori_loop`` over the layers of a pool and is timed on the host's
clock, best of several after a warm-up, and once under the profiler,
where the call's own events are read. The rows are longmix's: 52 live
rows at contexts spread evenly from 1,536 to 8,128 positions, one of
them ending on a block's last position and one on a block's first,
among 12 dead ones. One layer's outputs of each form are compared with
the gather's: how many bf16 outputs differ, and by how much. Nothing
here runs on the CPU: a timing taken there is not a reading.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

ROWS, DH, PAGE, MAX_PAGES, POOL_PAGES, LAYERS = 64, 128, 128, 64, 2816, 2
REPEATS = 8  # passes over the layers inside one timed program
SHAPES = {"k-exaone full layer": (64, 8), "smallthinker full layer": (28, 4)}


def _rows():
    """(positions [ROWS], tables [ROWS, MAX_PAGES]) of the mix."""
    import numpy as np

    rng = np.random.default_rng(43)
    lengths = np.zeros(ROWS, np.int64)
    live = rng.permutation(ROWS)[:52]
    lengths[live] = np.linspace(1536, 8128, 52).astype(int)
    lengths[live[0]], lengths[live[1]] = 4 * 512, 4 * 512 + 1
    positions = np.where(lengths > 0, lengths - 1, -1)
    pages = np.where(lengths > 0, (lengths - 1) // PAGE + 1, 0)
    ids = rng.permutation(POOL_PAGES)
    tables = np.zeros((ROWS, MAX_PAGES), np.int32)
    at = 0
    for row, n in enumerate(pages):
        tables[row, :n] = ids[at:at + n]
        at += n
    return positions, tables, int(pages.sum())


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import trace
    from kvedge_tpu.models import kvcache
    from kvedge_tpu.ops.paged_attention import (
        decode_scratch_form, paged_decode_attention, visible)

    device = jax.devices()[0]
    if device.platform != "tpu":
        print(json.dumps({"ok": False, "why": "no TPU: nothing was timed"}))
        return 1
    positions, tables, live_pages = _rows()
    tables_d = jnp.asarray(tables)
    positions_d = jnp.asarray(positions, jnp.int32)
    report = {"device": device.device_kind, "live_pages": live_pages,
              "live_rows": int((positions >= 0).sum()), "shapes": {}}

    def gather(q, pool_k, pool_v, tables, positions, layer):
        """kvcache._paged_attention's gather branch on a decode step."""
        rows, h, dh = q.shape
        kv = pool_k.shape[-1] // dh
        gk, gv = kvcache._gathered((pool_k, pool_v, None, None), layer,
                                   tables, kv, q.dtype)
        qg = q.reshape(rows, 1, kv, h // kv, dh)
        scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, gk) / (dh ** 0.5)
        allowed = visible(jnp.arange(gk.shape[1])[None, None, :],
                          positions[:, None, None])
        scores = jnp.where(allowed[:, None, None], scores,
                           jnp.finfo(q.dtype).min)
        weights = jax.nn.softmax(scores.astype(jnp.float32),
                                 axis=-1).astype(q.dtype)
        return jnp.einsum("bkgqs,bskd->bqkgd", weights, gv).reshape(
            rows, h, dh)

    for name, (heads, kv) in SHAPES.items():
        keys = jax.random.split(jax.random.PRNGKey(43), 3)
        shape = (LAYERS, POOL_PAGES, PAGE, kv * DH)
        pool_k = jax.random.normal(keys[0], shape, jnp.bfloat16)
        pool_v = jax.random.normal(keys[1], shape, jnp.bfloat16)
        q = jax.random.normal(keys[2], (ROWS, heads, DH), jnp.bfloat16)
        forms = {"gather": gather}
        fits = decode_scratch_form(MAX_PAGES, PAGE, kv * DH, heads)
        if fits == "whole":
            forms["whole"] = paged_decode_attention
        forms["blocked"] = lambda *a: paged_decode_attention(*a, blocked=True)
        row = {"auto_takes": fits, "forms": {}}
        outputs = {}
        for form, call in forms.items():
            @jax.jit
            def passes(q, pool_k, pool_v, call=call):
                def layer(i, q):
                    out = call(q, pool_k, pool_v, tables_d, positions_d,
                               i % LAYERS)
                    return jnp.where(positions_d[:, None, None] >= 0,
                                     q + out * 0.001, q).astype(q.dtype)
                return jax.lax.fori_loop(0, LAYERS * REPEATS, layer, q)

            passes(q, pool_k, pool_v).block_until_ready()
            outputs[form] = np.asarray(jax.jit(call)(
                q, pool_k, pool_v, tables_d, positions_d, 1))
            walls = []
            for _ in range(5):
                t0 = time.perf_counter()
                passes(q, pool_k, pool_v).block_until_ready()
                walls.append(time.perf_counter() - t0)
            with tempfile.TemporaryDirectory() as trace_dir:
                with jax.profiler.trace(trace_dir):
                    passes(q, pool_k, pool_v).block_until_ready()
                events = [e for e in trace.read_xplane(
                    trace.find_xplane(trace_dir))
                    if e["line"] == trace.OPS_LINE
                    and e["name"].startswith("paged_attention")]
            reading = {"wall_us_a_call": min(walls) / (LAYERS * REPEATS)
                       * 1e6}
            if events:
                us = sum(e["dur"] for e in events) / len(events) * 1e6
                reading.update(
                    event=events[0]["name"].split(".")[0], events=len(events),
                    kernel_us_a_call=us,
                    kernel_gb_s=live_pages * 2 * PAGE * kv * DH * 2 / us
                    / 1e3)
            row["forms"][form] = reading
        live = positions >= 0
        want = outputs["gather"][live]
        for form in forms:
            got = outputs[form]
            if form == "gather":
                continue
            bits = got[live].view(np.uint16) != want.view(np.uint16)
            row["forms"][form].update(
                outputs=int(bits.size), differ=int(bits.sum()),
                largest_gap=float(np.abs(
                    got[live].astype(np.float32)
                    - want.astype(np.float32)).max()),
                scale=float(np.abs(want.astype(np.float32)).max()),
                dead_rows_nonzero=int((got[~live] != 0).sum()))
        report["shapes"][name] = row
        print(name, json.dumps(row), flush=True)
        del pool_k, pool_v
    out = os.path.join("chiprun_out", "blocked_kernel_readings.json")
    os.makedirs("chiprun_out", exist_ok=True)
    with open(out, "w") as f:
        json.dump(report, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
