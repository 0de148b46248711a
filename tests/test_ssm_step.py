"""The one-pass SSM step kernel (ops/ssm_step.py) against ``_one_token``.

On the CPU the kernel runs in the Pallas interpreter, the same body the
chip compiles (tests/test_chip_compile.py compiles it for a described
v5e at the benchmark cell's shapes; chip_smoke.py runs it on one). The
product takes the kernel on a TPU backend only and has no switch for
it, so a test that wants it on the served path patches the backend test
(``ssm._on_tpu``); ``kvedge_tpu.ops.pallas_interpret`` still answers
for the CPU.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kvedge_tpu.models import hybrid, kvcache, ssm
from kvedge_tpu.ops import ssm_step
from tests.test_hybrid_block import config_of, prompt_of, server_of

SLOTS, LAYERS, STATE = 8, 3, 128


def operands(rows: int, heads: int, p: int = 64, seed: int = 0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    return {
        "state": jax.random.normal(
            keys[0], (LAYERS, SLOTS, heads * p, STATE), jnp.float32),
        "x": jax.random.normal(keys[1], (rows, heads, p), jnp.float32),
        "B": jax.random.normal(keys[2], (rows, STATE), jnp.float32),
        "C": jax.random.normal(keys[3], (rows, STATE), jnp.float32),
        "dt": jax.random.uniform(keys[4], (rows, heads), jnp.float32,
                                 1e-3, 1e-1),
        "A": -jax.random.uniform(keys[5], (heads,), jnp.float32, 1.0, 16.0),
    }


def bits(a) -> np.ndarray:
    return np.asarray(a).view(np.uint32)


@pytest.mark.parametrize("layer", [0, LAYERS - 1])
@pytest.mark.parametrize("rows,heads", [(1, 2), (3, 4), (8, 4), (3, 34)])
def test_the_kernel_is_one_token_in_place(rows, heads, layer):
    """``S'`` and ``y`` of the live rows within float32 rounding of
    ``_one_token``'s; every other layer, every row that is not live and
    every slot past the batch's rows unchanged bit for bit. 34 heads of
    64 are 17 chunks: a block of one chunk, 17 blocks a row."""
    o = operands(rows, heads, seed=rows + layer)
    live = np.arange(rows) % 3 != 1            # rows 1, 4, 7 are not live
    got_y, got = jax.jit(
        lambda *a: ssm_step.ssm_step(*a, interpret=True)
    )(o["state"], jnp.asarray(layer, jnp.int32), o["x"], o["B"], o["C"],
      o["dt"], o["A"], jnp.asarray(live))
    want_y, want = ssm._one_token(o["state"][layer, :rows], o["x"], o["B"],
                                  o["C"], o["dt"], o["A"])
    assert got.dtype == jnp.float32 and got.shape == o["state"].shape
    np.testing.assert_allclose(got[layer, :rows][live], want[live],
                               rtol=2e-6, atol=1e-6)
    # y is a sum of 128 terms of size |S'| |C|
    scale = float(jnp.abs(want_y).max())
    np.testing.assert_allclose(np.asarray(got_y)[live],
                               np.asarray(want_y)[live],
                               rtol=0, atol=2e-6 * scale)
    before = bits(o["state"])
    after = bits(got)
    others = [i for i in range(LAYERS) if i != layer]
    assert (after[others] == before[others]).all()
    assert (after[layer, :rows][~live] == before[layer, :rows][~live]).all()
    assert (after[layer, rows:] == before[layer, rows:]).all()


def test_block_rows_and_what_tiles():
    assert ssm_step.block_rows(128 * 64, 128) == 2048   # the benchmark's
    assert ssm_step.block_rows(128 * 64, 256) == 1024   # the same bytes
    assert ssm_step.block_rows(2 * 64, 128) == 128
    assert ssm_step.block_rows(34 * 64, 128) == 128     # 17 chunks
    assert ssm_step.block_rows(48 * 64, 128) == 1024    # 24 chunks
    assert ssm_step.tiles(128 * 64, 128)
    assert ssm_step.tiles(2 * 64, 256)
    assert not ssm_step.tiles(4 * 8, 16)           # tests/test_hybrid_block
    assert not ssm_step.tiles(4 * 64, 64)          # half a lane row
    assert not ssm_step.tiles(3 * 32, 128)         # 96 rows: no whole chunk
    with pytest.raises(ValueError, match="_one_token takes it"):
        o = operands(2, 4, p=8)
        ssm_step.ssm_step(o["state"], 0, o["x"], o["B"], o["C"], o["dt"],
                          o["A"], interpret=True)


# ---- on the served path --------------------------------------------------

# tests/test_hybrid_block.py's preset with a mixer the kernel tiles: two
# heads of 64 with state 128, the benchmark's head and state sizes.
TILED = {"ssm_heads": 2, "ssm_head_dim": 64, "ssm_state": 128}


@pytest.fixture(scope="module")
def tiled_cfg():
    return config_of(TILED)


@pytest.fixture(scope="module")
def tiled_params(tiled_cfg):
    return hybrid.init_params(jax.random.PRNGKey(0), tiled_cfg)


def test_a_served_request_through_the_kernel_is_the_one_token_path_s(
        tiled_cfg, tiled_params, monkeypatch):
    """A whole request, prefilled in chunks and decoded in windows beside
    a second one that is admitted later (so windows run with rows that
    are not live), once as the CPU serves it (``_one_token``) and once
    with the kernel taken, under the interpreter: the same greedy
    streams."""
    def serve():
        server = server_of(tiled_params, tiled_cfg, slots=3)
        try:
            first = server.submit_stream(prompt_of(11, 40), 24)
            head = next(first)
            second = server.submit(prompt_of(12, 21), 9)
            return [head, *first], second
        finally:
            server.close()

    assert not ssm.step_in_kernel(tiled_cfg, None, 1)   # the CPU's answer
    want = serve()
    jax.clear_caches()  # the decode programs are traced again
    monkeypatch.setattr(ssm, "_on_tpu", lambda: True)
    assert ssm.step_in_kernel(tiled_cfg, None, 1)
    traces = []
    real = ssm_step.ssm_step
    monkeypatch.setattr(
        ssm_step, "ssm_step",
        lambda *a, **k: traces.append(k["interpret"]) or real(*a, **k))
    try:
        got = serve()
    finally:
        jax.clear_caches()
    assert traces and all(traces)   # taken, and in the interpreter
    assert got == want


def _lowered_for_tpu(cfg, program: str) -> str:
    """The StableHLO of one of the cache's programs lowered for the TPU
    (no chip and no compiler: a kernel is a ``tpu_custom_call`` that
    names it), with ``jax.default_backend()`` answered as on the chip."""
    cache = kvcache.PagedKVCache(cfg, slots=2, pages=16, page_size=16)
    params = jax.eval_shape(
        lambda: hybrid.init_params(jax.random.PRNGKey(0), cfg))
    state = cache.state
    i32 = jax.ShapeDtypeStruct((), jnp.int32)
    if program == "decode_step":
        traced = kvcache._paged_decode_step.trace(
            params, state, jax.ShapeDtypeStruct((2,), jnp.int32), cfg,
            jax.ShapeDtypeStruct((2,), jnp.bool_))
    else:  # a prefill piece of one token: ``slot`` is given
        traced = kvcache._paged_prefill.trace(
            params, state, jax.ShapeDtypeStruct((1,), jnp.int32), i32, cfg,
            i32)
    return traced.lower(lowering_platforms=("tpu",)).as_text()


@pytest.mark.parametrize("model,program,calls", [
    (TILED, "decode_step", 3),        # m m a m: three a period's body
    (TILED, "prefill_one_token", 0),  # slot given: _one_token
    ({}, "decode_step", 0),           # state [32, 16]: does not tile
])
def test_which_programs_hold_the_kernel(model, program, calls, monkeypatch):
    import kvedge_tpu.ops

    monkeypatch.setattr(ssm, "_on_tpu", lambda: True)
    monkeypatch.setattr(kvedge_tpu.ops, "pallas_interpret", lambda: False)
    cfg = dataclasses.replace(config_of(model), paged_attention="gather")
    try:
        text = _lowered_for_tpu(cfg, program)
    finally:
        jax.clear_caches()
    assert text.count('kernel_name = "ssm_step"') == calls
    assert text.count("tpu_custom_call") == calls
