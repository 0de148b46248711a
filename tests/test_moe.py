"""Mixture-of-experts FFN: routing math and expert parallelism.

Runs on the 8-virtual-CPU-device mesh from conftest. Key properties:

* a 1-expert MoE is numerically a dense FFN (router prob 1.0, gate 1.0);
* dropped tokens (capacity exceeded) contribute exactly zero FFN output;
* the aux loss is Switch eq. 4 (min 1.0 at uniform routing);
* sharding the expert axis over the mesh changes placement, not math;
* a dp×ep train step runs, is finite, and learns.

(The reference repo has no parallelism of any kind — SURVEY.md §5; this
is payload capability, tested per the build contract on the virtual CPU
mesh.)
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kvedge_tpu.config.runtime_config import MeshSpec
from kvedge_tpu.models import (
    TransformerConfig,
    forward_with_aux,
    init_params,
    loss_fn,
    make_train_step,
)
from kvedge_tpu.models.moe import expert_capacity, moe_ffn
from kvedge_tpu.parallel import build_mesh, shard_batch, shard_params

MOE_CFG = TransformerConfig(
    vocab=128, d_model=32, n_heads=4, n_layers=2, d_ff=64, max_seq=64,
    dtype="float32", n_experts=4,
)


def test_expert_capacity_rounding():
    assert expert_capacity(64, 4, 1.0) == 16
    assert expert_capacity(64, 4, 1.25) == 20
    assert expert_capacity(3, 8, 1.0) == 1  # floor of 1 slot
    # ceil(tokens/E * factor), not ceil(floor(tokens*factor)/E):
    assert expert_capacity(10, 4, 1.25) == 4


def test_single_expert_equals_dense_ffn():
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (16, 8), jnp.float32)
    router = jnp.zeros((8, 1), jnp.float32)
    w_up = jax.random.normal(jax.random.fold_in(key, 1), (1, 8, 32))
    w_down = jax.random.normal(jax.random.fold_in(key, 2), (1, 32, 8))
    out, aux = moe_ffn(x, router, w_up, w_down, capacity_factor=1.0)
    dense = jax.nn.gelu(x @ w_up[0]) @ w_down[0]
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense),
                               atol=1e-5)
    assert float(aux) == pytest.approx(1.0)  # one expert: perfectly "balanced"


def test_dropped_tokens_get_zero_output():
    # Router forced to send every token to expert 0; capacity 1 slot.
    x = jnp.ones((8, 4), jnp.float32)
    router = jnp.stack(
        [jnp.full((4,), 10.0), jnp.full((4,), -10.0)], axis=-1
    )  # [D, 2], expert 0 always wins
    w_up = jnp.ones((2, 4, 4), jnp.float32)
    w_down = jnp.ones((2, 4, 4), jnp.float32)
    out, _ = moe_ffn(x, router, w_up, w_down, capacity_factor=1 / 8)
    # capacity = ceil(8 * (1/8) / 2) = 1: the first token fills expert
    # 0's only slot; all later tokens are dropped -> zero rows.
    out = np.asarray(out)
    assert np.abs(out[0]).sum() > 0
    np.testing.assert_allclose(out[1:], 0.0)


def test_aux_loss_minimized_at_uniform_routing():
    # Uniform router probs: aux = E * sum(1/E * 1/E * E) = 1.0.
    x = jax.random.normal(jax.random.PRNGKey(1), (32, 8))
    router = jnp.zeros((8, 4), jnp.float32)  # all logits equal
    w_up = jnp.ones((4, 8, 8), jnp.float32)
    w_down = jnp.ones((4, 8, 8), jnp.float32)
    _, aux = moe_ffn(x, router, w_up, w_down, capacity_factor=2.0)
    # argmax ties break to expert 0 (fraction collapses), but mean_prob
    # stays uniform -> aux = E * sum(f * 1/E) = sum(f) = 1.0.
    assert float(aux) == pytest.approx(1.0, abs=1e-5)


def test_moe_params_and_specs():
    params = init_params(jax.random.PRNGKey(0), MOE_CFG)
    assert "w_up_experts" in params and "router" in params
    assert "w_up" not in params
    assert params["w_up_experts"].shape == (2, 4, 32, 64)
    # The sharding rules cover the MoE params (no KeyError) and put the
    # expert dim on the expert axis.
    from kvedge_tpu.parallel.sharding import param_specs

    mesh = build_mesh(MeshSpec(axes=(("data", 2), ("expert", 4))))
    specs = param_specs(params, mesh)
    assert specs["w_up_experts"][1] == "expert"


def test_forward_aux_is_finite_and_near_balanced():
    params = init_params(jax.random.PRNGKey(0), MOE_CFG)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 128)
    logits, aux = forward_with_aux(params, tokens, MOE_CFG)
    assert logits.shape == (2, 32, 128)
    aux = float(aux)
    # Random init routes near-uniformly; Switch aux is >= 1 and should be
    # close to it. A collapsed router would read near E (= 4).
    assert 1.0 <= aux < 2.0


def test_dense_forward_aux_is_zero():
    cfg = dataclasses.replace(MOE_CFG, n_experts=0)
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jnp.zeros((1, 8), jnp.int32)
    _, aux = forward_with_aux(params, tokens, cfg)
    assert float(aux) == 0.0


def test_expert_sharding_matches_single_device_math():
    mesh = build_mesh(MeshSpec(axes=(("data", 2), ("expert", 4))))
    params = init_params(jax.random.PRNGKey(0), MOE_CFG)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 33), 0, 128)
    plain = float(loss_fn(params, tokens, MOE_CFG))
    sharded = float(
        jax.jit(loss_fn, static_argnums=(2,))(
            shard_params(mesh, params), shard_batch(mesh, tokens), MOE_CFG
        )
    )
    assert plain == pytest.approx(sharded, abs=1e-4)


def test_moe_train_step_runs_and_learns():
    mesh = build_mesh(MeshSpec(axes=(("data", 2), ("expert", 4))))
    params = shard_params(mesh, init_params(jax.random.PRNGKey(0), MOE_CFG))
    # mesh= so the MoE layer's expert-placement constraints fire.
    init_opt, train_step = make_train_step(MOE_CFG, mesh=mesh)
    opt_state = init_opt(params)
    batch = shard_batch(
        mesh,
        jax.random.randint(jax.random.PRNGKey(1), (4, 33), 0,
                           MOE_CFG.vocab, dtype=jnp.int32),
    )
    losses = []
    for _ in range(5):
        params, opt_state, loss = train_step(params, opt_state, batch)
        losses.append(float(loss))
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]


def test_moe_composes_with_tensor_parallelism():
    # ep=2 x tp=2 x dp=2: experts shard over `expert`, each expert's FFN
    # is still column/row-parallel over `model`.
    mesh = build_mesh(
        MeshSpec(axes=(("data", 2), ("expert", 2), ("model", 2)))
    )
    cfg = dataclasses.replace(MOE_CFG, n_experts=2)
    params = shard_params(mesh, init_params(jax.random.PRNGKey(0), cfg))
    init_opt, train_step = make_train_step(cfg, mesh=mesh)
    opt_state = init_opt(params)
    batch = shard_batch(
        mesh,
        jax.random.randint(jax.random.PRNGKey(1), (4, 33), 0, cfg.vocab,
                           dtype=jnp.int32),
    )
    _, _, loss = train_step(params, opt_state, batch)
    assert np.isfinite(float(loss))


def test_top2_matches_reference_implementation():
    # Small case with generous capacity: top-2 output must equal the
    # hand-written per-token reference sum_j gate_j * ffn_j(x).
    key = jax.random.PRNGKey(4)
    x = jax.random.normal(key, (12, 8), jnp.float32)
    router = jax.random.normal(jax.random.fold_in(key, 1), (8, 4))
    w_up = jax.random.normal(jax.random.fold_in(key, 2), (4, 8, 16))
    w_down = jax.random.normal(jax.random.fold_in(key, 3), (4, 16, 8))
    got, _ = moe_ffn(x, router, w_up, w_down, capacity_factor=4.0, top_k=2)

    probs = jax.nn.softmax(x @ router, axis=-1)
    top2_probs, top2_idx = jax.lax.top_k(probs, 2)
    gates = top2_probs / top2_probs.sum(axis=-1, keepdims=True)
    want = np.zeros_like(np.asarray(x))
    for n in range(x.shape[0]):
        for j in range(2):
            e = int(top2_idx[n, j])
            f = np.asarray(
                jax.nn.gelu(x[n] @ w_up[e]) @ w_down[e]
            )
            want[n] += float(gates[n, j]) * f
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-4)


def test_top2_first_choice_has_capacity_priority():
    # Token 0 routes (e0, e1); token 1 routes (e1, e2); capacity 1 slot
    # per expert. The contested slot is expert 1's: under choice-major
    # priority, token 1's FIRST choice wins it and token 0's SECOND
    # choice is dropped. A token-major (no-priority) dispatch would give
    # the slot to token 0's second choice instead — this test
    # distinguishes the two.
    x = jnp.eye(2, 4, dtype=jnp.float32)  # one-hot tokens: logits = rows of router
    router = jnp.array([
        [5.0, 4.0, -9.0, -9.0],   # token 0: top2 = (e0, e1)
        [-9.0, 5.0, 4.0, -9.0],   # token 1: top2 = (e1, e2)
        [0.0, 0.0, 0.0, 0.0],
        [0.0, 0.0, 0.0, 0.0],
    ], jnp.float32)
    # Distinct per-expert outputs: f_e(x_n) = 4 * gelu(w) per dim, where
    # w = e + 1 for a one-hot token.
    w_up = jnp.stack([jnp.full((4, 4), float(e + 1)) for e in range(4)])
    w_down = jnp.ones((4, 4, 4), jnp.float32)
    # capacity = ceil(2*2/4 * 0.5) = 1
    out, _ = moe_ffn(x, router, w_up, w_down, capacity_factor=0.5, top_k=2)

    probs = jax.nn.softmax(router[:2], axis=-1)
    g = jax.lax.top_k(probs, 2)[0]
    g = np.asarray(g / g.sum(axis=-1, keepdims=True))

    def f(e):  # per-dim expert output for a one-hot token
        return 4.0 * float(jax.nn.gelu(jnp.float32(e + 1.0)))

    # Kept: token0 first (e0); token1 first (e1) + second (e2).
    # Dropped: token0 second (e1) — lost the contested slot.
    want = np.zeros((2, 4), np.float32)
    want[0] = g[0, 0] * f(0)
    want[1] = g[1, 0] * f(1) + g[1, 1] * f(2)
    np.testing.assert_allclose(np.asarray(out), want, atol=1e-4)


def test_top2_train_step_runs_and_learns():
    cfg = dataclasses.replace(MOE_CFG, expert_top_k=2)
    mesh = build_mesh(MeshSpec(axes=(("data", 2), ("expert", 4))))
    params = shard_params(mesh, init_params(jax.random.PRNGKey(0), cfg))
    init_opt, train_step = make_train_step(cfg, mesh=mesh)
    opt_state = init_opt(params)
    batch = shard_batch(
        mesh,
        jax.random.randint(jax.random.PRNGKey(1), (4, 33), 0, cfg.vocab,
                           dtype=jnp.int32),
    )
    losses = []
    for _ in range(5):
        params, opt_state, loss = train_step(params, opt_state, batch)
        losses.append(float(loss))
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]


def test_top_k_validation():
    with pytest.raises(ValueError, match="top_k"):
        dataclasses.replace(MOE_CFG, expert_top_k=3).validate()
    with pytest.raises(ValueError, match="top_k"):
        dataclasses.replace(
            MOE_CFG, n_experts=1, expert_top_k=2
        ).validate()


# Serving: the decode paths route per-token without capacity limits, so
# they agree with the teacher-forced forward pass exactly when training
# capacity never binds — pin capacity_factor = n_experts (zero drops).
SERVE_CFG = dataclasses.replace(
    MOE_CFG, expert_capacity_factor=float(MOE_CFG.n_experts), max_seq=32
)


@pytest.mark.parametrize("top_k", [1, 2])
@pytest.mark.parametrize("tokens", [150, 160])
def test_serving_block_matches_per_token_gather(top_k, tokens):
    # The serving block is one product over every held expert
    # (moe.held_experts_ffn). Against it, each token run through the
    # matrices of its own picks, gathered token by token: what the block
    # used to do, kept here as the plain statement of dropless routing.
    from kvedge_tpu.models import moe

    key = jax.random.PRNGKey(8)
    router = jax.random.normal(jax.random.fold_in(key, 1), (16, 4))
    w_up = jax.random.normal(jax.random.fold_in(key, 2), (4, 16, 32))
    w_down = jax.random.normal(jax.random.fold_in(key, 3), (4, 32, 16))
    x = jax.random.normal(key, (2, tokens // 2, 16), jnp.float32)

    big = moe.routed_ffn_block(x, router, w_up, w_down, top_k=top_k)
    flat = x.reshape(tokens, 16)
    _, idx, gates = moe._route(flat, router, top_k)
    gathered = sum(
        jnp.einsum("nf,nfd->nd",
                   jax.nn.gelu(jnp.einsum("nd,ndf->nf", flat,
                                          w_up[idx[:, c]])),
                   w_down[idx[:, c]]) * gates[:, c, None]
        for c in range(top_k)).reshape(x.shape)
    np.testing.assert_allclose(
        np.asarray(big), np.asarray(gathered), rtol=1e-4, atol=1e-4
    )


def test_moe_long_prompt_prefill_matches_forward():
    # A long prompt's prefill is the same product over all experts as a
    # decode step's; greedy decode must still agree with teacher forcing.
    from kvedge_tpu.models import generate
    from kvedge_tpu.models.transformer import forward

    cfg = dataclasses.replace(SERVE_CFG, max_seq=128)
    params = init_params(jax.random.PRNGKey(0), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(9), (1, 96), 0,
                                cfg.vocab, dtype=jnp.int32)  # 96 > 64
    out = generate(params, prompt, cfg, n_new=4)
    logits = forward(params, out[:, :-1], cfg)
    for pos in range(95, 99):
        np.testing.assert_array_equal(
            np.asarray(jnp.argmax(logits[:, pos], axis=-1)),
            np.asarray(out[:, pos + 1]),
            err_msg=f"divergence at position {pos + 1}",
        )


@pytest.mark.parametrize("top_k", [1, 2])
def test_moe_generate_matches_argmax_of_forward(top_k):
    from kvedge_tpu.models import generate
    from kvedge_tpu.models.transformer import forward

    cfg = dataclasses.replace(SERVE_CFG, expert_top_k=top_k)
    params = init_params(jax.random.PRNGKey(0), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                                cfg.vocab, dtype=jnp.int32)
    out = generate(params, prompt, cfg, n_new=6)
    assert out.shape == (2, 14)
    # Teacher-force the generated tokens through the cache-less forward
    # pass: greedy argmax at each generated position must agree.
    logits = forward(params, out[:, :-1], cfg)
    for pos in range(8 - 1, 14 - 1):
        np.testing.assert_array_equal(
            np.asarray(jnp.argmax(logits[:, pos], axis=-1)),
            np.asarray(out[:, pos + 1]),
            err_msg=f"divergence at position {pos + 1}",
        )


def test_moe_paged_matches_contiguous():
    from kvedge_tpu.models import PagedKVCache, decode_step, init_cache, prefill

    params = init_params(jax.random.PRNGKey(0), SERVE_CFG)
    prompt = jax.random.randint(jax.random.PRNGKey(2), (8,), 0,
                                SERVE_CFG.vocab, dtype=jnp.int32)

    paged = PagedKVCache(SERVE_CFG, slots=2, pages=8, page_size=8)
    paged.admit(0, 8)
    paged_logits = paged.prefill(params, 0, prompt)

    cache = init_cache(SERVE_CFG, batch=1, max_seq=32)
    contig_logits, cache = prefill(params, prompt[None], cache, SERVE_CFG)
    np.testing.assert_allclose(
        np.asarray(paged_logits), np.asarray(contig_logits[0]),
        rtol=2e-2, atol=2e-2,
    )

    for step in range(4):
        tok = jnp.argmax(contig_logits, axis=-1).astype(jnp.int32)
        got = paged.step(params, jnp.stack([tok[0], jnp.int32(0)]))
        contig_logits, cache = decode_step(params, cache, tok, SERVE_CFG)
        np.testing.assert_allclose(
            np.asarray(got[0]), np.asarray(contig_logits[0]),
            rtol=2e-2, atol=2e-2, err_msg=f"step {step}",
        )


def test_validate_rejects_bad_moe_config():
    with pytest.raises(ValueError, match="n_experts"):
        dataclasses.replace(MOE_CFG, n_experts=-1).validate()
    with pytest.raises(ValueError, match="capacity"):
        dataclasses.replace(MOE_CFG, expert_capacity_factor=0.0).validate()


def test_serving_warns_when_training_capacity_can_bind():
    """VERDICT r1 weak #8: train-with-drops + serve-dropless diverges
    silently; the serving boundary (cache construction) must warn."""
    import warnings

    import pytest

    from kvedge_tpu.models import PagedKVCache, init_cache

    risky = TransformerConfig(
        vocab=64, d_model=32, n_heads=4, n_kv_heads=2, n_layers=2, d_ff=64,
        max_seq=16, n_experts=4, expert_capacity_factor=1.25,
    )
    safe = dataclasses.replace(risky, expert_capacity_factor=4.0)

    with pytest.warns(RuntimeWarning, match="dropless serving"):
        init_cache(risky, batch=2)
    with pytest.warns(RuntimeWarning, match="dropless serving"):
        PagedKVCache(risky, slots=2, pages=8)

    with warnings.catch_warnings():
        warnings.simplefilter("error")
        init_cache(safe, batch=2)          # no warning
        PagedKVCache(safe, slots=2, pages=8)
        # top_k scales capacity: factor 2.0 x top_k 2 covers 4 experts,
        # so this config is provably dropless and must stay silent.
        top2 = dataclasses.replace(
            risky, expert_top_k=2, expert_capacity_factor=2.0
        )
        init_cache(top2, batch=2)


# ---- Sequence x expert parallelism (a converted matrix ✗ cell, r2) -------
#
# Ring/ulysses shard_map wraps ONLY the attention op; the MoE dispatch/
# combine einsums partition via annotations outside it, so the two
# compose on a data x seq x expert mesh with no new machinery — the ✗
# in the matrix was untested, not impossible. Capacity is ample
# (factor * top_k >= E) so routing is batch-layout-invariant and parity
# against the naive+ep reference is exact.

SEQ_EP_CFG = TransformerConfig(
    vocab=128, d_model=32, n_heads=4, n_layers=2, d_ff=64, max_seq=64,
    dtype="float32", attention="ring", n_experts=2,
    expert_capacity_factor=2.0,
)


def _seq_ep_mesh():
    from kvedge_tpu.config.runtime_config import MeshSpec
    from kvedge_tpu.parallel import build_mesh

    return build_mesh(
        MeshSpec(axes=(("data", 2), ("seq", 2), ("expert", 2)))
    )


@pytest.mark.parametrize("attention", ["ring", "ulysses"])
def test_seq_expert_gradients_match_reference(attention):
    from kvedge_tpu.config.runtime_config import MeshSpec
    from kvedge_tpu.parallel import build_mesh, shard_params

    cfg = dataclasses.replace(SEQ_EP_CFG, attention=attention)
    mesh = _seq_ep_mesh()
    params = init_params(jax.random.PRNGKey(0), cfg)
    batch = jax.random.randint(jax.random.PRNGKey(1), (4, 33), 0, 128)

    ref_cfg = dataclasses.replace(cfg, attention="naive")
    ref_mesh = build_mesh(MeshSpec(axes=(("data", 4), ("expert", 2))))

    got = jax.jit(jax.grad(loss_fn), static_argnums=(2, 3))(
        shard_params(mesh, params), batch, cfg, mesh
    )
    want = jax.jit(jax.grad(loss_fn), static_argnums=(2, 3))(
        params, batch, ref_cfg, ref_mesh
    )
    for name in want:
        np.testing.assert_allclose(
            np.asarray(got[name]), np.asarray(want[name]), atol=2e-4,
            err_msg=name,
        )


def test_seq_expert_train_step_learns():
    from kvedge_tpu.models import make_train_step
    from kvedge_tpu.parallel import shard_batch, shard_params

    mesh = _seq_ep_mesh()
    params = shard_params(mesh, init_params(jax.random.PRNGKey(0),
                                            SEQ_EP_CFG))
    init_opt, train_step = make_train_step(SEQ_EP_CFG, mesh=mesh)
    opt_state = init_opt(params)
    batch = shard_batch(
        mesh,
        jax.random.randint(jax.random.PRNGKey(1), (8, 33), 0, 128,
                           dtype=jnp.int32),
    )
    losses = []
    for _ in range(5):
        params, opt_state, loss = train_step(params, opt_state, batch)
        losses.append(float(loss))
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]
