"""The one-pass delta-rule step kernel alone on the chip, beside what it
replaced and beside the forms that were tried.

    chiprun -- python tools/delta_kernel_readings.py [--out FILE]

One jitted program carries the stacked recurrent state of the
benchmark's delta cell (3 delta layers, 64 slots, 64 heads of 128 key by
128 value channels: float32 [3, 64, 64, 128, 128], 805 MB; a layer's 64
rows are 268 MB) through a ``fori_loop`` of one-token steps, layer after
layer, each step's ``v`` depending on the last step's ``o`` as in the
model, and is timed on the host's clock, best of several after a
warm-up, and once more under the profiler, where the operations' own
events are read (PERF.md section 5 quotes both), us a layer, with all 64
rows live and with 58 of 64 (the cell's bucket fill):

  kernel            ``ops.delta_step.delta_step``, the form kept: the
                    three ``dk``-vectors of a head turned on the XLU
                    into columns, both reductions as sums down the
                    sublanes, rows that are not live skipped;
  copied_through    the same body, a row that is not live fetched and
                    written back as it came (what skipping saves);
  block_8, block_32 the same at 8 and 32 heads a block (16 is kept);
  packed            a block's 48 vectors turned once, as one [128, 128]
                    tile, and each column spread across the lanes;
  mxu               both reductions as one product with the head's tile
                    on the MXU at ``Precision.HIGHEST``, the decay
                    folded into ``k`` and ``q``;
  stream            a kernel that only copies the blocks through VMEM;
  two_passes        what a decode step ran before the kernel:
                    ``state[layer]`` taken, ``models.delta._one_token``,
                    the rows that are not live put back, the layer
                    written in place into the carried state (XLA makes
                    of it a pass for the two reductions and an in-place
                    update).

Then the kernel's ``S'`` and ``o`` are held against ``_one_token``'s on
the chip over 64 steps from one random state, and the skipping form's
bits against the copying form's. Nothing here is timed on the CPU: a
timing taken there is not a reading (``--rehearse`` runs the whole
script at a tiny size in the Pallas interpreter, and times nothing).
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import tempfile
import time

LAYERS, ROWS, HEADS, DK, DV = 3, 64, 64, 128, 128
DEAD = (3, 17, 29, 41, 50, 63)  # 58 of 64 live
REPEATS = 36  # passes over the layers inside one timed program
STEPS = 64    # of the comparison with _one_token


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--out", default=os.path.join(
        "chiprun_out", "delta_kernel_readings.json"))
    parser.add_argument("--rehearse", action="store_true")
    args = parser.parse_args()
    sys.path.insert(0, os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))

    import jax
    import jax.numpy as jnp
    import numpy as np

    from kvedge_tpu.models.delta import _l2norm

    device = jax.devices()[0]
    on_chip = device.platform == "tpu"
    if not on_chip and not args.rehearse:
        print(json.dumps({"ok": False, "why": "no TPU: nothing was timed"}))
        return 1
    layers, rows, heads, dk, dv = LAYERS, ROWS, HEADS, DK, DV
    dead, repeats, steps = DEAD, REPEATS, STEPS
    if args.rehearse:
        rows, heads, dead, repeats, steps = 4, 32, (2,), 1, 3
    interpret = not on_chip
    f32 = jnp.float32

    forms = tried_forms(heads, dk, dv, interpret)
    two_passes, the_kernel = forms["two_passes"], forms["kernel"]

    # ---- operands: the mixer's own ranges (models/delta.py)
    keys = jax.random.split(jax.random.PRNGKey(39), 6)
    shape = (rows, heads)
    q = _l2norm(jax.random.normal(keys[1], (*shape, dk))) * dk ** -0.5
    k = _l2norm(jax.random.normal(keys[2], (*shape, dk)))
    v0 = jax.random.normal(keys[3], (*shape, dv))
    g = -jnp.exp(jax.random.uniform(keys[4], (*shape, dk), f32, -12.0, 2.0))
    beta = 2.0 * jax.nn.sigmoid(2.0 * jax.random.normal(keys[5], shape))

    def fresh():
        return jax.random.normal(keys[0], (layers, rows, heads, dk, dv), f32)

    def passes(step):
        @functools.partial(jax.jit, donate_argnums=(0,))
        def run(state, live):
            def one(i, carry):
                state, o = carry
                o, state = step(state, i % layers, q, k, v0 + 1e-3 * o, g,
                                beta, live)
                return state, o
            return jax.lax.fori_loop(0, layers * repeats, one,
                                     (state, jnp.zeros_like(v0)))
        return run

    all_live = jnp.ones((rows,), jnp.bool_)
    some_dead = all_live.at[jnp.asarray(dead)].set(False)
    report = {"device": device.device_kind, "readings": {},
              "shapes": [layers, rows, heads, dk, dv],
              "live_of": [rows - len(dead), rows]}
    layer_bytes = 2 * rows * heads * dk * dv * 4  # once in, once out
    for name, step in forms.items():
        run = passes(step)
        for label, live in (("all_live", all_live), ("some_dead", some_dead)):
            state, o = run(fresh(), live)
            jax.block_until_ready(o)
            row = {}
            if on_chip:
                walls = []
                for _ in range(4):
                    t0 = time.perf_counter()
                    state, o = run(state, live)
                    jax.block_until_ready(o)
                    walls.append(time.perf_counter() - t0)
                with tempfile.TemporaryDirectory() as trace_dir:
                    with jax.profiler.trace(trace_dir):
                        state, o = run(state, live)
                        jax.block_until_ready(o)
                    ops = _costliest(trace_dir)
                us = min(walls) / (layers * repeats) * 1e6
                row = {"wall_us_a_layer": us,
                       "gb_s_of_once_in_once_out": layer_bytes / us / 1e3,
                       "costliest_ops": ops}
            del state
            report["readings"][f"{name}.{label}"] = row
            print(name, label, json.dumps(row), flush=True)

    # ---- the kernel against _one_token over ``steps`` steps, layer 1
    layer = 1
    step_keys = jax.random.split(jax.random.PRNGKey(3939), steps)

    def drawn(key):
        ks = jax.random.split(key, 5)
        return (_l2norm(jax.random.normal(ks[0], (*shape, dk))) * dk ** -0.5,
                _l2norm(jax.random.normal(ks[1], (*shape, dk))),
                jax.random.normal(ks[2], (*shape, dv)),
                -jnp.exp(jax.random.uniform(ks[3], (*shape, dk), f32,
                                            -12.0, 2.0)),
                2.0 * jax.nn.sigmoid(2.0 * jax.random.normal(ks[4], shape)))

    def stepped(step):
        @functools.partial(jax.jit, donate_argnums=(0,))
        def run(state, live):
            def one(state, key):
                o, state = step(state, layer, *drawn(key), live)
                return state, o[:, :2]  # two heads' o of every step
            return jax.lax.scan(one, state, step_keys)
        return run

    def plain(state, layer, q, k, v, g, beta, live):
        o, state = two_passes(state, layer, q, k, v, g, beta, live)
        return jnp.where(live[:, None, None], o, 0.0), state

    start = np.asarray(fresh())
    got, got_o = map(np.asarray, stepped(the_kernel)(fresh(), some_dead))
    want, want_o = map(np.asarray, stepped(plain)(fresh(), some_dead))
    copied, copied_o = map(np.asarray, stepped(forms["copied_through"])(
        fresh(), some_dead))
    live = np.asarray(some_dead)
    others = [i for i in range(layers) if i != layer]

    def bits(a):
        return a.view(np.uint32)

    held = {
        "steps": steps,
        "state_gap_of_scale": float(
            np.abs(got[layer][live] - want[layer][live]).max()
            / np.abs(want[layer][live]).max()),
        "state_elements_differing": int(
            (bits(got[layer][live]) != bits(want[layer][live])).sum()),
        "state_elements": int(want[layer][live].size),
        "o_gap_of_scale": float(
            np.abs(got_o[:, live] - want_o[:, live]).max()
            / np.abs(want_o[:, live]).max()),
        "o_elements_differing": int(
            (bits(got_o[:, live]) != bits(want_o[:, live])).sum()),
        "o_elements": int(want_o[:, live].size),
        "dead_rows_bit_for_bit": bool(
            (bits(got[layer][~live]) == bits(start[layer][~live])).all()),
        "dead_rows_o_zero": bool((got_o[:, ~live] == 0).all()),
        "other_layers_bit_for_bit": bool(
            (bits(got[others]) == bits(start[others])).all()),
        "skipping_equals_copying_bit_for_bit": bool(
            (bits(got) == bits(copied)).all()
            and (bits(got_o) == bits(copied_o)).all()),
    }
    report["against_one_token"] = held
    print("against_one_token", json.dumps(held), flush=True)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1)
    return 0


def tried_forms(heads: int, dk: int, dv: int, interpret: bool) -> dict:
    """name -> step(state, layer, q, k, v, g, beta, live) -> (o, state)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from kvedge_tpu.models.delta import _one_token
    from kvedge_tpu.ops import delta_step as kept

    f32 = jnp.float32
    highest = jax.lax.Precision.HIGHEST

    # the forms as bodies over one block of heads
    def packed(eg_ref, k_ref, q_ref, v_ref, beta_ref, s_ref, o_ref, out_ref):
        n = s_ref.shape[2]
        k, q = k_ref[0, 0], q_ref[0, 0]
        k_dot_q = jnp.sum(k * q, axis=-1, keepdims=True)
        turned = jnp.concatenate(
            [eg_ref[0, 0], k, q, jnp.zeros((128 - 3 * n, dk), f32)]).T

        def column(i):
            return jnp.broadcast_to(turned[:, i:i + 1], (dk, 128))

        for h in range(n):
            eg_col, k_col, q_col = column(h), column(n + h), column(2 * n + h)
            decayed = eg_col * s_ref[0, 0, h]
            at_k = jnp.sum(decayed * k_col, axis=0, keepdims=True)
            at_q = jnp.sum(decayed * q_col, axis=0, keepdims=True)
            u = beta_ref[0, 0, h:h + 1] * (v_ref[0, 0, h:h + 1] - at_k)
            out_ref[0, 0, h] = decayed + k_col * u
            o_ref[0, 0, h:h + 1] = at_q + k_dot_q[h:h + 1] * u

    def mxu(eg_ref, k_ref, q_ref, v_ref, beta_ref, s_ref, o_ref, out_ref):
        n = s_ref.shape[2]
        eg, k, q = eg_ref[0, 0], k_ref[0, 0], q_ref[0, 0]
        k_dot_q = jnp.sum(k * q, axis=-1, keepdims=True)
        folded = jnp.concatenate([eg * k, eg * q])           # [2 n, dk]
        for h in range(n):
            s = s_ref[0, 0, h]
            both = jnp.dot(folded, s, precision=highest,
                           preferred_element_type=f32)       # [2 n, dv]
            u = beta_ref[0, 0, h:h + 1] * (
                v_ref[0, 0, h:h + 1] - both[h:h + 1])
            out_ref[0, 0, h] = (kept._column(eg[h:h + 1]) * s
                                + kept._column(k[h:h + 1]) * u)
            o_ref[0, 0, h:h + 1] = (both[n + h:n + h + 1]
                                    + k_dot_q[h:h + 1] * u)

    def stream(eg_ref, k_ref, q_ref, v_ref, beta_ref, s_ref, o_ref, out_ref):
        out_ref[...] = s_ref[...]
        o_ref[...] = v_ref[...]

    def copying(body, block):
        """``body`` over a grid of every row, a row that is not live
        copied through: ops.delta_step.delta_step's call but for the
        compacted list of rows."""
        def kernel(layer_ref, live_ref, *refs):
            del layer_ref
            s_ref, o_ref, out_ref = refs[-3:]
            is_live = live_ref[pl.program_id(0)] != 0

            @pl.when(is_live)
            def _():
                body(*refs)

            @pl.when(jnp.logical_not(is_live))
            def _():
                out_ref[...] = s_ref[...]
                o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

        def step(state, layer, q, k, v, g, beta, live):
            n = k.shape[0]
            blocks = heads // block

            def lines(a, width):
                return a.astype(f32).reshape(n, blocks, block, width)

            def per_head(width):
                return pl.BlockSpec((1, 1, block, width),
                                    lambda r, j, *_: (r, j, 0, 0))

            in_state = pl.BlockSpec(
                (1, 1, block, dk, dv),
                lambda r, j, layer, live: (layer[0], r, j, 0, 0))
            o, state = pl.pallas_call(
                kernel,
                grid_spec=pltpu.PrefetchScalarGridSpec(
                    num_scalar_prefetch=2, grid=(n, blocks),
                    in_specs=[per_head(dk)] * 3 + [per_head(dv)] * 2
                    + [in_state],
                    out_specs=[per_head(dv), in_state]),
                out_shape=[jax.ShapeDtypeStruct((n, blocks, block, dv), f32),
                           jax.ShapeDtypeStruct(state.shape, state.dtype)],
                input_output_aliases={7: 1},
                compiler_params=pltpu.CompilerParams(
                    dimension_semantics=("arbitrary", "arbitrary")),
                interpret=interpret, name="delta_step_tried",
            )(jnp.asarray(layer, jnp.int32).reshape(1),
              live.astype(jnp.int32), lines(jnp.exp(g), dk), lines(k, dk),
              lines(q, dk), lines(v, dv),
              lines(jnp.broadcast_to(beta[..., None], v.shape), dv), state)
            return o.reshape(n, heads, dv), state
        return step

    def two_passes(state, layer, q, k, v, g, beta, live):
        held = state[layer]
        o, new = _one_token(held, q, k, v, g, beta)
        new = jnp.where(live[:, None, None, None], new, held)
        return o, state.at[layer].set(new)

    def the_kernel(*a):
        return kept.delta_step(*a, interpret=interpret)

    return {
        "kernel": the_kernel,
        "copied_through": copying(kept._heads, 16),
        "block_8": copying(kept._heads, 8),
        "block_32": copying(kept._heads, 32),
        "packed": copying(packed, 16),
        "mxu": copying(mxu, 16),
        "stream": copying(stream, 16),
        "two_passes": two_passes,
    }


def _costliest(trace_dir: str, count: int = 4) -> list:
    """The traced program's costliest device operations: [name, runs,
    us each], the loops that hold the others left out."""
    from benchmark import trace

    by_name: dict = {}
    for e in trace.read_xplane(trace.find_xplane(trace_dir)):
        if e["line"] == trace.OPS_LINE and "while" not in e["name"]:
            by_name.setdefault(e["name"], []).append(e["dur"])
    return sorted(([n, len(d), sum(d) / len(d) * 1e6]
                   for n, d in by_name.items()),
                  key=lambda t: -t[1] * t[2])[:count]


if __name__ == "__main__":
    sys.exit(main())
