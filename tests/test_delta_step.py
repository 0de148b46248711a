"""The one-pass delta-rule step kernel (ops/delta_step.py) against
``_one_token``.

On the CPU the kernel runs in the Pallas interpreter, the same body the
chip compiles (tests/test_chip_compile.py compiles it for a described
v5e inside the benchmark's delta cell's window; chip_smoke.py and
tools/delta_kernel_readings.py run it on one). The product takes the
kernel on a TPU backend only and has no switch for it, so a test that
wants it on the served path patches the backend test both recurrent
kinds ask (``ssm._on_tpu``); ``kvedge_tpu.ops.pallas_interpret`` still
answers for the CPU.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kvedge_tpu.models import delta, hybrid, ssm
from kvedge_tpu.ops import delta_step
from tests.test_hybrid_block import BLOCKS, config_of, prompt_of, server_of
from tests.test_ssm_step import TILED as MAMBA_TILED
from tests.test_ssm_step import _lowered_for_tpu, bits

SLOTS, LAYERS = 8, 3


def operands(rows: int, heads: int, dk: int = 128, dv: int = 128,
             seed: int = 0):
    """A stacked state and one step's q, k, v, g, beta in the mixer's
    own ranges: unit keys, decays from none to all of it, beta to 2."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    shape = (rows, heads)
    return {
        "state": jax.random.normal(
            keys[0], (LAYERS, SLOTS, heads, dk, dv), jnp.float32),
        "q": delta._l2norm(jax.random.normal(keys[1], (*shape, dk)))
        * dk ** -0.5,
        "k": delta._l2norm(jax.random.normal(keys[2], (*shape, dk))),
        "v": jax.random.normal(keys[3], (*shape, dv)),
        "g": -jnp.exp(jax.random.uniform(keys[4], (*shape, dk), jnp.float32,
                                         -12.0, 2.0)),
        "beta": 2.0 * jax.nn.sigmoid(2.0 * jax.random.normal(keys[5], shape)),
    }


def stepped(o: dict, layer: int, live):
    return jax.jit(
        lambda *a: delta_step.delta_step(*a, interpret=True)
    )(o["state"], jnp.asarray(layer, jnp.int32), o["q"], o["k"], o["v"],
      o["g"], o["beta"], None if live is None else jnp.asarray(live))


def held_to_one_token(o: dict, rows: int, layer: int, live) -> None:
    """``S'`` and ``o`` of the live rows within float32 rounding of
    ``_one_token``'s; every other layer, every row that is not live and
    every slot past the batch's rows unchanged bit for bit, and the
    rows that are not live given zeros."""
    got_o, got = stepped(o, layer, live)
    want_o, want = delta._one_token(o["state"][layer, :rows], o["q"], o["k"],
                                    o["v"], o["g"], o["beta"])
    assert got.dtype == jnp.float32 and got.shape == o["state"].shape
    # sums of dk terms of size |S| |k|, in another order than XLA's
    np.testing.assert_allclose(got[layer, :rows][live], want[live],
                               rtol=0, atol=2e-6 * float(jnp.abs(want).max()))
    np.testing.assert_allclose(np.asarray(got_o)[live],
                               np.asarray(want_o)[live], rtol=0,
                               atol=2e-6 * float(jnp.abs(want_o).max()))
    assert (np.asarray(got_o)[~live] == 0).all()
    before, after = bits(o["state"]), bits(got)
    others = [i for i in range(LAYERS) if i != layer]
    assert (after[others] == before[others]).all()
    assert (after[layer, :rows][~live] == before[layer, :rows][~live]).all()
    assert (after[layer, rows:] == before[layer, rows:]).all()


@pytest.mark.parametrize("layer", [0, LAYERS - 1])
@pytest.mark.parametrize("rows,heads", [(1, 2), (3, 4), (8, 4), (3, 34)])
def test_the_kernel_is_one_token_in_place(rows, heads, layer):
    """Over rows, blocks of heads and layers. 34 heads are 17 blocks of
    two; rows 1, 4 and 7 are not live, so the grid's last iterations
    are the ones that stay where the last live row ended."""
    live = np.arange(rows) % 3 != 1
    held_to_one_token(operands(rows, heads, seed=rows + layer), rows, layer,
                      live)


@pytest.mark.parametrize("dk,dv", [(128, 256), (256, 128)])
def test_a_tile_of_more_than_one_lane_row(dk, dv):
    """Key or value channels of two lane rows: the columns are turned a
    [128, dk] tile at a time and a tile's lanes worked 128 at a time."""
    held_to_one_token(operands(3, 2, dk, dv, seed=dk), 3, 1,
                      np.array([True, False, True]))


@pytest.mark.parametrize("live", [
    [False, False, False, False],   # nothing fetched but one block
    [False, False, True, False],    # the list is one row long
    [True, True, True, True],
    None,                           # as a caller without a mask says it
])
def test_a_row_that_is_not_live_keeps_its_state_and_gets_zeros(live):
    o = operands(4, 4, seed=7)
    mask = np.ones(4, bool) if live is None else np.array(live)
    if live is None:
        got_o, got = stepped(o, 1, None)
        want_o, want = stepped(o, 1, mask)
        assert (bits(got) == bits(want)).all()
        assert (bits(got_o) == bits(want_o)).all()
    held_to_one_token(o, 4, 1, mask)


def test_heads_block_and_what_tiles():
    assert delta_step.heads_block(64, 128, 128) == 16   # the benchmark's
    assert delta_step.heads_block(64, 256, 128) == 8    # the same bytes
    assert delta_step.heads_block(64, 256, 256) == 4
    assert delta_step.heads_block(2, 128, 128) == 2
    assert delta_step.heads_block(34, 128, 128) == 2    # 17 blocks
    assert delta_step.heads_block(48, 128, 128) == 16
    assert delta_step.heads_block(3, 128, 128) == 1
    assert delta_step.tiles(64, 128, 128)
    assert delta_step.tiles(2, 256, 128)
    assert not delta_step.tiles(4, 8, 8)           # tests/test_hybrid_block
    assert not delta_step.tiles(4, 128, 64)        # half a lane row
    assert not delta_step.tiles(4, 64, 128)
    assert not delta_step.tiles(4, 192, 128)
    with pytest.raises(ValueError, match="_one_token takes it"):
        o = operands(2, 4, dk=16, dv=8)
        delta_step.delta_step(o["state"], 0, o["q"], o["k"], o["v"], o["g"],
                              o["beta"], interpret=True)


# ---- on the served path --------------------------------------------------

# tests/test_hybrid_block.py's delta preset with a mixer the kernel
# tiles: two heads of 128 by 128, the benchmark's tile.
TILED = {"ssm_heads": 2, "ssm_head_dim": 128, "ssm_state": 128}


@pytest.fixture(scope="module")
def tiled_cfg():
    return config_of(TILED, BLOCKS["delta"])


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

    assert not delta.step_in_kernel(tiled_cfg, None, 1)  # the CPU's answer
    want = serve()
    jax.clear_caches()  # the decode programs are traced again
    monkeypatch.setattr(ssm, "_on_tpu", lambda: True)
    assert delta.step_in_kernel(tiled_cfg, None, 1)
    assert not delta.step_in_kernel(tiled_cfg, 0, 1)     # a prefill piece
    assert not delta.step_in_kernel(tiled_cfg, None, 2)
    traces = []
    real = delta_step.delta_step
    monkeypatch.setattr(
        delta_step, "delta_step",
        lambda *a, **k: traces.append(k["interpret"]) or real(*a, **k))
    try:
        got = serve()
    finally:
        jax.clear_caches()
    assert traces and all(traces)   # taken, and in the interpreter
    assert got == want


@pytest.mark.parametrize("block,model,program,calls", [
    ("delta", TILED, "decode_step", {"delta_step": 3}),  # a d d d
    ("delta", TILED, "prefill_one_token", {}),   # slot given: _one_token
    ("delta", {}, "decode_step", {}),            # tiles [8, 8]: do not tile
    ("mamba", MAMBA_TILED, "decode_step", {"ssm_step": 3}),  # as before
    ("mamba", {}, "decode_step", {}),
])
def test_which_programs_hold_the_kernel(block, model, program, calls,
                                        monkeypatch):
    """Each recurrent kind answers for itself: a delta decode step holds
    ``delta_step`` once for each delta layer of a period's body and a
    mamba model's programs hold ``ssm_step`` as they did, never the
    other's."""
    import kvedge_tpu.ops

    monkeypatch.setattr(ssm, "_on_tpu", lambda: True)
    monkeypatch.setattr(kvedge_tpu.ops, "pallas_interpret", lambda: False)
    cfg = dataclasses.replace(config_of(model, BLOCKS[block]),
                              paged_attention="gather")
    try:
        text = _lowered_for_tpu(cfg, program)
    finally:
        jax.clear_caches()
    for name in ("delta_step", "ssm_step"):
        assert text.count(f'kernel_name = "{name}"') == calls.get(name, 0)
    assert text.count("tpu_custom_call") == sum(calls.values())
