"""The delta-rule mixer's two forms (models/delta.py) against each other
and against the recurrence written out: the chunk form's triangular
solve is the one-token recurrence unrolled, whatever the blocking and
whatever the decay. What the mixer does inside the served block, and
against the benchmark's reference, is tests/test_hybrid_block.py's
``delta`` cases."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kvedge_tpu.models import delta, hybrid
from kvedge_tpu.models.transformer import TransformerConfig

CFG = TransformerConfig(
    vocab=64, d_model=32, n_heads=4, n_kv_heads=2, n_layers=4, d_ff=16,
    max_seq=128, dtype="float32", n_experts=4, expert_top_k=2,
    layer_pattern=("attention", "delta", "delta", "delta"), ssm_heads=4,
    ssm_head_dim=8, ssm_state=16, ssm_conv=4, ssm_chunk=16,
    ssm_gate_rank=8, ffn_gated=True, rotary=False, head_dim=16,
    attention_gate=True, untied_head=True, norm_eps=1e-5)


def inputs(t: int, decay: float, seed: int = 0, heads: int = 4,
           dk: int = 16, dv: int = 8):
    """q, k, v, g, beta of T positions as the mixer hands them to the
    recurrence; ``decay`` scales g (its size is the decay's strength)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = delta._l2norm(jax.random.normal(ks[0], (t, heads, dk))) * dk ** -0.5
    k = delta._l2norm(jax.random.normal(ks[1], (t, heads, dk)))
    v = jax.random.normal(ks[2], (t, heads, dv))
    g = -decay * jnp.exp(jax.random.normal(ks[3], (t, heads, dk)))
    beta = 2.0 * jax.nn.sigmoid(2.0 * jax.random.normal(ks[4], (t, heads)))
    return q, k, v, g, beta


def written_out(q, k, v, g, beta):
    """The recurrence as the module's docstring states it, a head and a
    position at a time, in numpy float64: S~ = diag(exp g) S; u = beta
    (v - S~^T k); S = S~ + k u^T; o = S^T q."""
    q, k, v, g, beta = (np.asarray(a, np.float64) for a in
                        (q, k, v, g, beta))
    t, heads, dk = k.shape
    S = np.zeros((heads, dk, v.shape[-1]))
    out = np.zeros(v.shape)
    for i in range(t):
        for h in range(heads):
            decayed = np.exp(g[i, h])[:, None] * S[h]
            u = beta[i, h] * (v[i, h] - decayed.T @ k[i, h])
            S[h] = decayed + np.outer(k[i, h], u)
            out[i, h] = S[h].T @ q[i, h]
    return out, S


# A decay of 1e-6 forgets nothing over 40 positions (the solve carries
# every earlier position at full weight), one of 30 forgets everything
# within a position (exp(-30) and below: the chunk form's exponents reach
# -inf and must give zeros, not NaNs); 0.1 and 1 are what the recipe draws.
@pytest.mark.parametrize("decay", [1e-6, 0.1, 1.0, 30.0])
@pytest.mark.parametrize("block", [7, 16, 40])
def test_the_chunk_form_is_the_recurrence_unrolled(decay, block):
    """Forty positions in blocks of 7 (five full and a tail of 5), 16
    (two and a tail of 8) and 40 (one), each from the state the last
    left, against the one-token form position by position and against
    the recurrence written out in float64: outputs and the final state.
    Float32 both sides; read 2e-6 at the worst."""
    q, k, v, g, beta = inputs(40, decay)
    want_o, want_S = written_out(q, k, v, g, beta)
    S = jnp.zeros((1, 4, 16, 8))
    stepped = []
    for t in range(40):
        o, S = delta._one_token(S, *(a[None, t] for a in (q, k, v, g, beta)))
        stepped.append(o[0])
    np.testing.assert_allclose(np.stack(stepped), want_o, atol=1e-5)
    np.testing.assert_allclose(S[0], want_S, atol=1e-5)
    chunked, S2 = [], jnp.zeros((4, 16, 8))
    for lo in range(0, 40, block):
        o, S2 = delta._block(S2, *(a[lo:lo + block]
                                   for a in (q, k, v, g, beta)))
        chunked.append(o)
    chunked = np.concatenate(chunked)
    assert np.isfinite(chunked).all() and np.isfinite(S2).all()
    np.testing.assert_allclose(chunked, want_o, atol=1e-5)
    np.testing.assert_allclose(S2, want_S, atol=1e-5)


@pytest.fixture(scope="module")
def layer():
    """One delta layer's weights by the program's own recipe."""
    params = hybrid.init_params(jax.random.PRNGKey(0), CFG)
    return jax.tree_util.tree_map(lambda a: a[0, 1], params["delta"])


def test_the_mixer_in_pieces_is_the_mixer_token_by_token(layer):
    """The whole mixer (projections, conv and its tail, gates, norm)
    over 40 positions of two rows at once, token by token, and in chunks
    of 24 + 16 whose blocks of ``ssm_chunk`` 16 cross the chunk's edge:
    the same outputs, state and tail."""
    h = jax.random.normal(jax.random.PRNGKey(5), (2, 40, CFG.d_model))
    (shape, channels) = hybrid.state_shape(CFG)
    assert shape == (4, 16, 8) and channels == 2 * 64 + 32
    state = jnp.zeros((2, *shape))
    tail = jnp.zeros((2, 3 * channels))
    outs, s1, t1 = [], state, tail
    for t in range(40):
        o, s1, t1 = delta.delta_mixer(CFG, h[:, t:t + 1], layer, s1, t1)
        outs.append(o)
    stepped = jnp.concatenate(outs, axis=1)
    a, s2, t2 = delta.delta_mixer(CFG, h[:, :24], layer, state, tail)
    b, s2, t2 = delta.delta_mixer(CFG, h[:, 24:], layer, s2, t2)
    np.testing.assert_allclose(jnp.concatenate([a, b], axis=1), stepped,
                               atol=2e-5)
    np.testing.assert_allclose(s2, s1, atol=2e-5)
    np.testing.assert_array_equal(t2, t1)


def test_a_row_that_is_not_live_keeps_its_state_and_tail(layer):
    h = jax.random.normal(jax.random.PRNGKey(6), (3, 1, CFG.d_model))
    (shape, channels) = hybrid.state_shape(CFG)
    state = jax.random.normal(jax.random.PRNGKey(7), (3, *shape))
    tail = jax.random.normal(jax.random.PRNGKey(8), (3, 3 * channels))
    live = jnp.asarray([True, False, True])
    _, new_state, new_tail = delta.delta_mixer(CFG, h, layer, state, tail,
                                               live)
    np.testing.assert_array_equal(new_state[1], state[1])
    np.testing.assert_array_equal(new_tail[1], tail[1])
    assert not np.array_equal(new_state[0], state[0])
    assert not np.array_equal(new_tail[2], tail[2])


def test_a_negative_eigenvalue_is_reached_and_the_state_stays_bounded():
    """``beta`` reaches 2: along ``k`` the state's transition has the
    eigenvalue 1 - beta, down to -1, and no decay at all still leaves a
    state no larger than what was written into it."""
    q, k, v, g, beta = inputs(200, 0.0, seed=3)
    beta = jnp.full_like(beta, 2.0)
    S = jnp.zeros((1, 4, 16, 8))
    for t in range(200):
        _, S = delta._one_token(S, *(a[None, t] for a in (q, k, v, g, beta)))
    # reading k_t back gives beta v_t - (beta - 1) of what was there:
    # with the same key twice the second write flips the first's sign
    one = jnp.zeros((1, 1, 16, 8))
    key, val = k[None, 0, :1], v[None, 0, :1]
    zero_g, two = jnp.zeros_like(key), jnp.full((1, 1), 2.0)
    _, one = delta._one_token(one, key, key, val, zero_g, two)
    _, one = delta._one_token(one, key, key, jnp.zeros_like(val), zero_g,
                              two)
    np.testing.assert_allclose(
        jnp.einsum("rhkv,rhk->rhv", one, key), -2.0 * val, atol=1e-5)
    assert float(jnp.abs(S).max()) < 50.0


def test_the_config_sizes_a_delta_layer_s_state():
    recurrent = hybrid.fresh_recurrent(CFG, 5)
    assert recurrent["ssm"].shape == (3, 5, 4, 16, 8)
    assert recurrent["conv"].shape == (3, 5, 3 * (2 * 64 + 32))
    assert CFG.recurrent_kind == "delta" and CFG.d_head == 16
    assert CFG.kv_layers == 1 and CFG.ssm_layers == 3
    plain = dataclasses.replace(
        CFG, layer_pattern=(), n_experts=0, ffn_gated=False, rotary=True,
        head_dim=0, attention_gate=False, untied_head=False)
    plain.validate()
    assert plain.recurrent_kind == "" and plain.d_head == 8
