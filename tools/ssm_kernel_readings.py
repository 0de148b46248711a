"""The one-pass SSM step kernel alone on the chip, beside what it replaced.

    chiprun -- python tools/ssm_kernel_readings.py [--out FILE]

One jitted program carries the stacked recurrent state of the
benchmark's patterned cell (9 mamba layers, 64 slots, 128 heads of 64,
state 128: float32 [9, 64, 8192, 128], 2.4 GB; a layer's 64 rows are 268
MB) through a ``fori_loop`` of one-token steps, layer after layer, each
step's ``x`` depending on the last step's ``y`` as in the model, and is
timed on the host's clock, best of several after a warm-up, and once
more under the profiler, where the operations' own events are read
(PERF.md section 5 quotes both), us a layer:

  kernel, all live      ``ops.ssm_step.ssm_step`` on all 64 rows;
  kernel, 63 of 64      one row not decoding: it is copied through;
  two fusions           what a decode step ran before the kernel:
                        ``state[layer]`` taken, ``models.ssm._one_token``,
                        the rows that are not live put back, the layer
                        written in place into the carried state (XLA
                        makes of it an in-place update and a second
                        pass for ``y``).

and the kernel's ``S'`` and ``y`` are held against ``_one_token``'s on
the chip. Nothing here runs on the CPU: a timing taken there is not a
reading.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import tempfile
import time

LAYERS, ROWS, HEADS, P, N = 9, 64, 128, 64, 128
REPEATS = 12  # passes over the layers inside one timed program


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--out", default=os.path.join(
        "chiprun_out", "ssm_kernel_readings.json"))
    args = parser.parse_args()
    sys.path.insert(0, os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))

    import jax
    import jax.numpy as jnp
    import numpy as np

    from kvedge_tpu.models.ssm import _one_token
    from kvedge_tpu.ops.ssm_step import ssm_step

    device = jax.devices()[0]
    if device.platform != "tpu":
        print(json.dumps({"ok": False, "why": "no TPU: nothing was timed"}))
        return 1

    keys = jax.random.split(jax.random.PRNGKey(34), 6)
    x0 = jax.random.normal(keys[1], (ROWS, HEADS, P), jnp.float32)
    b = jax.random.normal(keys[2], (ROWS, N), jnp.float32)
    c = jax.random.normal(keys[3], (ROWS, N), jnp.float32)
    dt = jax.random.uniform(keys[4], (ROWS, HEADS), jnp.float32, 1e-3, 1e-1)
    a = -jax.random.uniform(keys[5], (HEADS,), jnp.float32, 1.0, 16.0)

    def fresh():
        return jax.random.normal(
            keys[0], (LAYERS, ROWS, HEADS * P, N), jnp.float32)

    def two_fusions(state, layer, x, b, c, dt, a, live):
        rows = state[layer]
        y, new = _one_token(rows, x, b, c, dt, a)
        new = jnp.where(live[:, None, None], new, rows)
        return y, state.at[layer].set(new)

    def passes(step):
        @functools.partial(jax.jit, donate_argnums=(0,))
        def run(state, live):
            def one(i, carry):
                state, y = carry
                y, state = step(state, i % LAYERS, x0 + 1e-3 * y, b, c, dt,
                                a, live)
                return state, y
            return jax.lax.fori_loop(0, LAYERS * REPEATS, one,
                                     (state, jnp.zeros_like(x0)))
        return run

    all_live = jnp.ones((ROWS,), jnp.bool_)
    one_dead = all_live.at[17].set(False)
    report = {"device": device.device_kind, "readings": {}}
    for name, step, live in (
            ("kernel_all_live", ssm_step, all_live),
            ("kernel_63_of_64", ssm_step, one_dead),
            ("two_fusions_all_live", two_fusions, all_live),
            ("two_fusions_63_of_64", two_fusions, one_dead)):
        run = passes(step)
        state, y = run(fresh(), live)
        jax.block_until_ready(y)
        walls = []
        for _ in range(5):
            t0 = time.perf_counter()
            state, y = run(state, live)
            jax.block_until_ready(y)
            walls.append(time.perf_counter() - t0)
        with tempfile.TemporaryDirectory() as trace_dir:
            with jax.profiler.trace(trace_dir):
                state, y = run(state, live)
                jax.block_until_ready(y)
            ops = _costliest(trace_dir)
        del state
        layer_bytes = 2 * ROWS * HEADS * P * N * 4  # once in, once out
        us = min(walls) / (LAYERS * REPEATS) * 1e6
        row = {"wall_us_a_layer": us,
               "gb_s_of_once_in_once_out": layer_bytes / us / 1e3,
               "costliest_ops": ops}
        report["readings"][name] = row
        print(name, json.dumps(row), flush=True)

    # The kernel against _one_token on the chip, one layer of one state.
    state = fresh()
    want_y, want = jax.jit(_one_token)(state[3], x0, b, c, dt, a)
    got_y, got = jax.jit(ssm_step)(state, 3, x0, b, c, dt, a, one_dead)
    live = np.asarray(one_dead)
    same = np.asarray(got[3]) == np.asarray(want)
    held = {
        "state_max_abs_diff_live": float(
            np.abs(np.asarray(got[3]) - np.asarray(want))[live].max()),
        "state_elements_differing_live": int((~same[live]).sum()),
        "y_max_abs_diff_live": float(
            np.abs(np.asarray(got_y) - np.asarray(want_y))[live].max()),
        "y_max_abs": float(np.abs(np.asarray(want_y)).max()),
        "dead_row_bit_for_bit": bool(
            (np.asarray(got[3, 17]) == np.asarray(state[3, 17])).all()),
        "other_layers_bit_for_bit": bool(
            (np.asarray(got[:3]) == np.asarray(state[:3])).all()
            and (np.asarray(got[4:]) == np.asarray(state[4:])).all()),
    }
    report["against_one_token"] = held
    print("against_one_token", json.dumps(held), flush=True)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1)
    return 0


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
