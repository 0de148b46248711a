"""The held experts' feed-forward alone on the chip, in the two ways its
first product can be written.

    chiprun -- python tools/expert_product_readings.py [--out FILE]

``models.moe.held_experts_ffn`` as the served programs run it: a scan
over the layers' stacked bf16 leaves, one call a layer on ``N`` tokens,
at the shapes of the benchmark's three patterned cells (held experts,
``d_model``, gated width, top-k), with a decode batch's 64 tokens and
with a prefill chunk's (64, or 256 in the cell whose chunks are). The
first product is ``"nd,edf->enf"`` either with the tokens shared by
every expert (*one*) or with the tokens stated once an expert,
``"end,edf->enf"`` over a broadcast (*batched*); the chip's compiler
makes different convolutions of the two. Each is one jitted program,
timed on the host's clock, best and median of several after a warm-up,
with what the compiled program needs beside its arguments
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

# cell -> layers, held experts, all experts, d_model, expert width,
# top-k, the gate, the token counts its programs run at
SHAPES = {
    "granite-4.0-h-small": (10, 36, 72, 4096, 768, 10, "silu", (64,)),
    "solar-open2-250b": (4, 40, 320, 4096, 1280, 8, "silu", (64,)),
    "smallthinker-21ba3b": (8, 64, 64, 2560, 768, 6, "relu", (64, 256)),
}
FORMS = {"one": 1 << 30, "batched": -1}  # moe._ONE_PRODUCT_TOKENS
CALLS = 20


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--out", default=os.path.join(
        "chiprun_out", "expert_product_readings.json"))
    args = parser.parse_args()
    sys.path.insert(0, os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))

    import jax
    import jax.numpy as jnp

    from kvedge_tpu.models import moe

    if jax.devices()[0].platform != "tpu":
        print(json.dumps({"ok": False, "why": "no TPU: nothing was timed"}))
        return 1
    rows = []
    for cell, (layers, held, experts, d, f, top_k, gate, counts) in \
            SHAPES.items():
        keys = jax.random.split(jax.random.PRNGKey(0), 4)
        weights = (
            jax.random.normal(keys[0], (layers, d, experts)) * 0.02,
            jax.random.normal(keys[1], (layers, held, d, 2 * f),
                              jnp.bfloat16) * 0.02,
            jax.random.normal(keys[2], (layers, held, f, d),
                              jnp.bfloat16) * 0.02)
        for n in counts:
            x = jax.random.normal(keys[3], (n, d), jnp.bfloat16)
            for form, threshold in FORMS.items():
                moe._ONE_PRODUCT_TOKENS = threshold

                def run(x, weights):
                    def one_layer(x, w):
                        out, picks = moe.held_experts_ffn(
                            x, *w, top_k=top_k, gated=True,
                            activation=gate)
                        return x + out, picks
                    return jax.lax.scan(one_layer, x, weights)

                row = {"cell": cell, "tokens": n, "form": form}
                try:
                    compiled = jax.jit(run).lower(x, weights).compile()
                    row["temp_gb"] = (
                        compiled.memory_analysis().temp_size_in_bytes / 1e9)
                    jax.block_until_ready(compiled(x, weights))
                    times = []
                    for _ in range(CALLS):
                        t0 = time.perf_counter()
                        jax.block_until_ready(compiled(x, weights))
                        times.append((time.perf_counter() - t0) * 1e3)
                    row["ms_best"] = min(times)
                    row["ms_median"] = statistics.median(times)
                    row["ms_a_layer"] = min(times) / layers
                    del compiled
                except Exception as e:  # the compiler's refusal is a reading
                    row["refused"] = repr(e)[:400]
                print("[experts] " + json.dumps(row), flush=True)
                rows.append(row)
        del weights
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as fh:
        json.dump({"device": str(jax.devices()[0]), "rows": rows}, fh)
    print(json.dumps({"ok": True}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
