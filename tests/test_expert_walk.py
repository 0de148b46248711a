"""The walk over the touched experts (ops/expert_walk.py) against the
one product over all held experts (models/moe.py ``held_experts_ffn``),
in the Pallas interpreter on the CPU.

Small sizes that keep the kernel's tiling: 16 tokens of width 128, six
held experts 256 wide of twelve, in the second of two stacked layers,
the fetched tile shrunk so that an expert's width is two tiles. What
"equal" means: with float32 tokens and matrices the two differ by the
order of a float32 sum; with bf16 ones (the served dtype) the one
product rounds its up-projection, its activation and its sum to bf16
where the walk rounds once before the down product, so the two are a
few bf16 roundings of the outputs' scale apart, and the walk is as near
the float32 product of the same bf16 values as the one product is.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kvedge_tpu.models import moe
from kvedge_tpu.ops import expert_walk

LAYERS, HELD, EXPERTS, FIRST = 2, 6, 12, 3
D, F, N, TOP_K, LAYER = 128, 256, 16, 2, 1


@pytest.fixture(scope="module", autouse=True)
def two_tiles_an_expert():
    """An expert's width in two tiles, as the chip's sizes have it in
    five; the kernel is jitted by its shapes, so its traces are dropped
    before and after."""
    was = expert_walk._TILE_BYTES
    expert_walk._TILE_BYTES = D * 128 * 4
    jax.clear_caches()
    assert expert_walk.width_tile(D, F, 4) == 128
    yield
    expert_walk._TILE_BYTES = was
    jax.clear_caches()


def _block(dtype=jnp.bfloat16, gated=True, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    wide = (2 if gated else 1) * F
    return dict(
        x=jax.random.normal(keys[0], (N, D)).astype(dtype),
        router_w=jax.random.normal(keys[1], (D, EXPERTS)) * 0.5,
        w_in=(jax.random.normal(keys[2], (LAYERS, HELD, D, wide))
              * D ** -0.5).astype(dtype),
        w_out=(jax.random.normal(keys[3], (LAYERS, HELD, F, D))
               * F ** -0.5).astype(dtype))


def _both(block, **kw):
    """(one product, walk), each ``(out float32, picks)``."""
    kw = dict(top_k=TOP_K, first=FIRST, gated=True, renormalize=True) | kw
    x, router_w = block["x"], block["router_w"]
    one = moe.held_experts_ffn(x, router_w, block["w_in"][LAYER],
                               block["w_out"][LAYER], **kw)
    walk = moe.held_experts_ffn(x, router_w, block["w_in"],
                                block["w_out"], layer=LAYER, **kw)
    return tuple((np.asarray(out, np.float32), np.asarray(picks))
                 for out, picks in (one, walk))


def _picking(choices):
    """(router_w, routed_on) under which token n picks the experts
    ``choices[n]`` (global indices, the first the strongest)."""
    scores = np.zeros((N, D), np.float32)
    for n, chosen in enumerate(choices):
        for rank, e in enumerate(chosen):
            scores[n, e] = 8.0 - rank
    return (jnp.eye(D, EXPERTS, dtype=jnp.float32), jnp.asarray(scores))


def _close(a, b, dtype):
    scale = np.abs(b).max()
    tolerance = 2e-5 if dtype == jnp.float32 else 2 ** -6
    assert np.abs(a - b).max() <= tolerance * scale, (
        np.abs(a - b).max(), scale)


def _equals_the_one_product(dtype, gated, activation):
    """With a first held expert past 0 and a mask of live rows."""
    block = _block(dtype, gated)
    live = jnp.arange(N) % 3 != 0
    (one, picks), (walk, walk_picks) = _both(
        block, gated=gated, activation=activation, live=live)
    rows = np.asarray(live)
    assert 0 < picks[-1] < HELD and picks[1] < picks[0]
    np.testing.assert_array_equal(walk_picks, picks)
    _close(walk[rows], one[rows], dtype)
    # and as near the float32 product of the same values as it is
    exact = {k: v.astype(jnp.float32) for k, v in block.items()}
    (exact, _), _ = _both(exact, gated=gated, activation=activation,
                          live=live)
    _close(walk[rows], exact[rows], dtype)


def _routed_on_another_input():
    block = _block()
    routed_on = jax.random.normal(jax.random.PRNGKey(7), (N, D),
                                  jnp.bfloat16)
    (one, picks), (walk, walk_picks) = _both(block, routed_on=routed_on)
    (_, on_x), _ = _both(block)
    assert not np.array_equal(picks, on_x)
    np.testing.assert_array_equal(walk_picks, picks)
    _close(walk, one, jnp.bfloat16)


def _touched(pattern):
    """The picks set by hand: every held expert touched, a third of
    them not, one alone, none (every row dead: zeros)."""
    held = list(range(FIRST, FIRST + HELD))
    choices = {
        "all": [(held[n % HELD], held[(n + 1) % HELD]) for n in range(N)],
        "two_thirds": [(held[n % 4], 0) for n in range(N)],
        "one": [(held[2], 1)] * N,
        "none": [(held[n % HELD], held[(n + 1) % HELD]) for n in range(N)],
    }[pattern]
    router_w, routed_on = _picking(choices)
    block = _block() | {"router_w": router_w}
    live = jnp.zeros((N,), bool) if pattern == "none" else None
    (one, picks), (walk, walk_picks) = _both(block, routed_on=routed_on,
                                             live=live)
    np.testing.assert_array_equal(walk_picks, picks)
    assert picks[-1] == {"all": 6, "two_thirds": 4, "one": 1,
                         "none": 0}[pattern]
    if pattern == "none":
        assert not walk.any() and one.any()
    else:
        _close(walk, one, jnp.bfloat16)


def _dead_rows_picks_do_not_matter():
    """A live row's output is the same to the bit whatever the dead
    rows picked: the list is made of the live rows' picks, and a row's
    sum has no term of another row."""
    held = list(range(FIRST, FIRST + HELD))
    live = jnp.arange(N) < 10
    outs = []
    for dead in ((held[4], held[5]), (held[0], 0)):
        choices = [(held[n % 3], 1) if n < 10 else dead for n in range(N)]
        router_w, routed_on = _picking(choices)
        block = _block() | {"router_w": router_w}
        (one, picks), (walk, walk_picks) = _both(
            block, routed_on=routed_on, live=live)
        np.testing.assert_array_equal(walk_picks, picks)
        assert picks[-1] == 3
        _close(walk[:10], one[:10], jnp.bfloat16)
        outs.append(walk[:10])
    np.testing.assert_array_equal(outs[0], outs[1])


def _untouched_experts_are_not_read():
    """With the untouched experts' matrices NaN the walk's result is
    finite and the same to the bit, where the one product's, which
    multiplies them by a gate of zero, is NaN."""
    held = list(range(FIRST, FIRST + HELD))
    router_w, routed_on = _picking([(held[n % 4], 0) for n in range(N)])
    block = _block() | {"router_w": router_w}
    _, (clean, _) = _both(block, routed_on=routed_on)
    poisoned = dict(block)
    for name in ("w_in", "w_out"):
        poisoned[name] = block[name].at[LAYER, 4:].set(jnp.nan)
        # the other layer's experts are no more read than they
        poisoned[name] = poisoned[name].at[1 - LAYER].set(jnp.nan)
    (one, _), (walk, _) = _both(poisoned, routed_on=routed_on)
    assert np.isnan(one).all() and np.isfinite(walk).all()
    np.testing.assert_array_equal(walk, clean)


def _a_traced_layer_in_a_scan():
    """The layer's index traced, in a scan over the layers, as the
    served programs hand it over."""
    block = _block()

    def body(x, layer):
        out, picks = moe.held_experts_ffn(
            block["x"], block["router_w"], block["w_in"], block["w_out"],
            top_k=TOP_K, first=FIRST, gated=True, layer=layer)
        return x, (out, picks)

    _, (outs, picks) = jax.jit(lambda: jax.lax.scan(
        body, 0, jnp.arange(LAYERS, dtype=jnp.int32)))()
    for layer in range(LAYERS):
        one, one_picks = moe.held_experts_ffn(
            block["x"], block["router_w"], block["w_in"][layer],
            block["w_out"][layer], top_k=TOP_K, first=FIRST, gated=True)
        np.testing.assert_array_equal(picks[layer], one_picks)
        _close(np.asarray(outs[layer], np.float32),
               np.asarray(one, np.float32), jnp.bfloat16)


def _the_kernel_refuses_what_it_does_not_tile():
    block = _block()
    with pytest.raises(ValueError, match="does not tile 12 tokens"):
        moe.held_experts_ffn(
            block["x"][:12], block["router_w"], block["w_in"],
            block["w_out"], top_k=TOP_K, gated=True, layer=0)
    assert not expert_walk.tiles(12, D, F)
    assert not expert_walk.tiles(N, D, F + 64)
    assert expert_walk.tiles(N, D, F)


def _rule(monkeypatch, on_tpu, shape, takes):
    """``walks_touched`` by shape: (tokens, top-k, experts, held,
    d_model, expert width) of the benchmark's four patterned cells."""
    monkeypatch.setattr(moe, "_on_tpu", lambda: on_tpu)
    assert moe.walks_touched(*shape) is takes


SOLAR = (8, 320, 40, 4096, 1280)
_RULE = {
    "rule_solar_64": (True, (64, *SOLAR), True),
    "rule_solar_32": (True, (32, *SOLAR), True),
    "rule_granite_64": (True, (64, 10, 72, 36, 4096, 768), False),
    "rule_granite_32": (True, (32, 10, 72, 36, 4096, 768), False),
    "rule_smallthinker_64": (True, (64, 6, 64, 64, 2560, 768), False),
    "rule_exaone_64": (True, (64, 8, 128, 16, 6144, 2048), False),
    # 12.7% untouched at 32 tokens: no cell runs it there
    "rule_exaone_32": (True, (32, 8, 128, 16, 6144, 2048), True),
    "rule_off_the_tpu": (False, (64, *SOLAR), False),
    "rule_above_the_one_product_s_tokens": (True, (128, *SOLAR), False),
    "rule_tokens_the_kernel_does_not_tile": (True, (24, *SOLAR), False),
}
_CASES = {
    **{f"equals_{name}_{np.dtype(dtype).name}":
       (lambda dtype=dtype, gated=gated, activation=activation:
        _equals_the_one_product(dtype, gated, activation))
       for name, gated, activation in (("silu_gated", True, "silu"),
                                       ("relu_gated", True, "relu"),
                                       ("gelu_ungated", False, "silu"))
       for dtype in (jnp.bfloat16, jnp.float32)},
    "routed_on_another_input": _routed_on_another_input,
    **{f"touched_{pattern}": (lambda pattern=pattern: _touched(pattern))
       for pattern in ("all", "two_thirds", "one", "none")},
    "dead_rows_picks_do_not_matter": _dead_rows_picks_do_not_matter,
    "untouched_experts_are_not_read": _untouched_experts_are_not_read,
    "a_traced_layer_in_a_scan": _a_traced_layer_in_a_scan,
    "the_kernel_refuses_what_it_does_not_tile":
        _the_kernel_refuses_what_it_does_not_tile,
}


@pytest.mark.parametrize("case", [*_CASES, *_RULE])
def test_expert_walk(case, monkeypatch):
    if case in _RULE:
        _rule(monkeypatch, *_RULE[case])
    else:
        _CASES[case]()
