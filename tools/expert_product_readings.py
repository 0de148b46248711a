"""The held experts' feed-forward alone on the chip, in the ways its
products can be written.

    chiprun -- python tools/expert_product_readings.py [--out FILE]
        [--cells a,b] [--tile-mb 2,4,8]

``models.moe.held_experts_ffn`` as the served programs run it: a scan
over the layers' stacked bf16 leaves, one call a layer on ``N`` tokens,
at the shapes of the benchmark's four patterned cells (held experts,
``d_model``, gated width, top-k), with a decode batch's 64 tokens and
with a prefill chunk's (64, or 256 in the cells whose chunks are). The
first product is ``"nd,edf->enf"`` either with the tokens shared by
every expert (*one*) or with the tokens stated once an expert,
``"end,edf->enf"`` over a broadcast (*batched*); the chip's compiler
makes different convolutions of the two. The third form, *walk*, is
ops/expert_walk.py: the kernel that reads only the experts a token
picked, handed the stacked leaves whole and the layer's index, at up
to 64 tokens and at three touched shares: *all* (every held expert on
the list, whatever the routing: the kernel's rate against the one
product's at the same bytes), *own* (the picks of this tool's seeded
router, which routes evenly) and *two_thirds* (so many held experts'
router columns zeroed that about two thirds of them still get a pick:
the delta cell's measured share). A walk's row has the share of the
held matrices it read, the GB/s over those bytes and the largest
difference of its outputs from the one product's on the same picks;
``--tile-mb`` times it at other sizes of the kernel's fetched tile than
the module's own.

Each row is one jitted program, timed on the host's clock, best and
median of several after a warm-up, with what the compiled program
needs beside its arguments
(``memory_analysis().temp_size_in_bytes``: a copy of the stacked leaf
in another layout shows there). Nothing here runs on the CPU: a timing
taken there is not a reading.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

# cell -> routed layers, held experts, all experts, d_model, expert
# width, top-k, the gate, the token counts its programs run at
SHAPES = {
    "granite-4.0-h-small": (10, 36, 72, 4096, 768, 10, "silu", (64,)),
    "solar-open2-250b": (4, 40, 320, 4096, 1280, 8, "silu", (64, 32)),
    "smallthinker-21ba3b": (8, 64, 64, 2560, 768, 6, "relu", (64, 256)),
    "k-exaone-236b-a23b": (4, 16, 128, 6144, 2048, 8, "silu", (64, 256)),
}
FORMS = {"one": 1 << 30, "batched": -1}  # moe._ONE_PRODUCT_TOKENS
SHARES = ("all", "own", "two_thirds")
CALLS = 20


def _timed(compiled, *args):
    import jax

    jax.block_until_ready(compiled(*args))
    times = []
    for _ in range(CALLS):
        t0 = time.perf_counter()
        out = jax.block_until_ready(compiled(*args))
        times.append((time.perf_counter() - t0) * 1e3)
    return out, times


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--out", default=os.path.join(
        "chiprun_out", "expert_product_readings.json"))
    parser.add_argument("--cells", default=",".join(SHAPES))
    parser.add_argument("--tile-mb", default="",
                        help="sizes of the walk's fetched tile to time "
                             "beside expert_walk._TILE_BYTES")
    args = parser.parse_args()
    sys.path.insert(0, os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))

    import jax
    import jax.numpy as jnp
    import numpy as np

    from kvedge_tpu.models import moe
    from kvedge_tpu.ops import expert_walk

    if jax.devices()[0].platform != "tpu":
        print(json.dumps({"ok": False, "why": "no TPU: nothing was timed"}))
        return 1
    own_tile = expert_walk._TILE_BYTES
    tile_sizes = [own_tile] + [int(float(mb) * (1 << 20))
                               for mb in args.tile_mb.split(",") if mb]
    walk_kernel = expert_walk.expert_walk
    rows = []

    def note(row):
        print("[experts] " + json.dumps(row), flush=True)
        rows.append(row)

    for cell in args.cells.split(","):
        layers, held, experts, d, f, top_k, gate, counts = SHAPES[cell]
        keys = jax.random.split(jax.random.PRNGKey(0), 4)
        router = jax.random.normal(keys[0], (layers, d, experts)) * 0.02
        leaves = (jax.random.normal(keys[1], (layers, held, d, 2 * f),
                                    jnp.bfloat16) * 0.02,
                  jax.random.normal(keys[2], (layers, held, f, d),
                                    jnp.bfloat16) * 0.02)
        matrix_bytes = 3 * d * f * 2  # one expert's w_in and w_out

        def scanned(x, router, leaves):
            def one_layer(x, w):
                out, picks = moe.held_experts_ffn(
                    x, *w, top_k=top_k, gated=True, activation=gate)
                return x + out, (out, picks)
            return jax.lax.scan(one_layer, x, (router, *leaves))

        def walked(x, router, leaves):
            def one_layer(x, w):
                out, picks = moe.held_experts_ffn(
                    x, w[0], *leaves, top_k=top_k, gated=True,
                    activation=gate, layer=w[1])
                return x + out, (out, picks)
            return jax.lax.scan(
                one_layer, x, (router, jnp.arange(layers, dtype=jnp.int32)))

        for n in counts:
            x = jax.random.normal(keys[3], (n, d), jnp.bfloat16)
            for form, threshold in FORMS.items():
                moe._ONE_PRODUCT_TOKENS = threshold
                row = {"cell": cell, "tokens": n, "form": form}
                try:
                    compiled = jax.jit(scanned).lower(
                        x, router, leaves).compile()
                    row["temp_gb"] = (
                        compiled.memory_analysis().temp_size_in_bytes / 1e9)
                    _, times = _timed(compiled, x, router, leaves)
                    row["ms_best"] = min(times)
                    row["ms_median"] = statistics.median(times)
                    row["ms_a_layer"] = min(times) / layers
                    row["gb_s"] = (held * matrix_bytes
                                   / (min(times) / layers * 1e-3) / 1e9)
                    del compiled
                except Exception as e:  # the compiler's refusal is a reading
                    row["refused"] = repr(e)[:400]
                note(row)
            if not expert_walk.tiles(n, d, f) or n > 64:
                continue
            moe._ONE_PRODUCT_TOKENS = FORMS["one"]
            # So many held experts silenced that two thirds of them are
            # still touched where an expert goes untouched as often as
            # even routing leaves it.
            reached = 1 - (1 - top_k / experts) ** n
            silenced = max(held - round(2 / 3 * held / reached), 0)
            for share in SHARES:
                routed = router
                if share == "two_thirds":
                    routed = router.at[:, :, :silenced].set(0.0)
                _, (reference, _) = jax.jit(scanned)(x, routed, leaves)
                for tile_bytes in tile_sizes:
                    expert_walk._TILE_BYTES = tile_bytes
                    moe.expert_walk.expert_walk = (
                        walk_kernel if share != "all" else
                        lambda x, g, w_in, w_out, layer, touched, **kw:
                        walk_kernel(x, g, w_in, w_out, layer,
                                    jnp.ones_like(touched), **kw))
                    row = {"cell": cell, "tokens": n, "form": "walk",
                           "share": share,
                           "tile": expert_walk.width_tile(d, f),
                           "tile_mb": tile_bytes / (1 << 20)}
                    try:
                        jax.clear_caches()  # the kernel is jitted by shape
                        compiled = jax.jit(walked).lower(
                            x, routed, leaves).compile()
                        row["temp_gb"] = (compiled.memory_analysis()
                                          .temp_size_in_bytes / 1e9)
                        (_, (out, picks)), times = _timed(
                            compiled, x, routed, leaves)
                        touched = int(np.asarray(picks)[:, -1].sum())
                        read = layers * held if share == "all" else touched
                        per_layer = min(times) / layers
                        row.update(
                            touched_pct=100 * touched / (layers * held),
                            read_pct=100 * read / (layers * held),
                            ms_best=min(times),
                            ms_median=statistics.median(times),
                            ms_a_layer=per_layer,
                            gb_s=(read / layers * matrix_bytes
                                  / (per_layer * 1e-3) / 1e9),
                            # of the first layer's, whose tokens are
                            # the same to the bit in both forms
                            max_diff=float(jnp.max(jnp.abs(
                                out[0].astype(jnp.float32)
                                - reference[0].astype(jnp.float32)))),
                            out_max=float(jnp.max(jnp.abs(
                                reference[0].astype(jnp.float32)))))
                        del compiled
                    except Exception as e:
                        row["refused"] = repr(e)[:400]
                    note(row)
            expert_walk._TILE_BYTES = own_tile
            moe.expert_walk.expert_walk = walk_kernel
        del leaves
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as fh:
        json.dump({"device": str(jax.devices()[0]), "rows": rows}, fh)
    print(json.dumps({"ok": True}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
