"""Autoregressive inference: prefill + decode with a static-shape KV cache.

The reference has no inference path (its payload is an opaque external
daemon, SURVEY.md §0); this module is the serving half of kvedge-tpu's
flagship payload, designed TPU-first:

* **Static shapes.** The cache is allocated once at ``[L, B, S, K, Dh]``
  and written in place with ``lax.dynamic_update_slice``; the decode loop
  is a ``lax.scan`` over steps — one compiled step regardless of length,
  no retracing as the sequence grows.
* **Donated cache.** ``decode_step`` donates the cache buffers, so XLA
  performs the slice-update in place instead of copying HBM every token.
* **GQA-aware.** K/V are cached at ``cfg.kv_heads`` — with grouped-query
  attention the cache (the HBM-bandwidth bill of decoding) shrinks by
  ``n_heads / n_kv_heads``. Attention against the cache uses a grouped
  einsum; the KV repeat is never materialized.
* **fp32 softmax, bf16 everything else** — same numerics policy as
  training (transformer.py).

The per-step layer loop is the same ``lax.scan``-over-stacked-params scheme
as the forward pass: each layer's cache slab rides the scan's xs/ys, so XLA
compiles ONE layer body and, with donation, updates slabs in place.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax import lax

from kvedge_tpu.models.transformer import (
    TransformerConfig,
    _rmsnorm,
    _rotary,
    refuse_pattern,
    split_qkv,
    stacked_layer_params,
    tied_readout,
)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class KVCache:
    """Contiguous KV cache: one [L, B, S, K, Dh] slab per projection.

    ``length`` is the number of valid positions (traced; uniform across the
    batch — ragged batches are the paged cache's job, models/kvcache.py).
    """

    k: jax.Array
    v: jax.Array
    length: jax.Array  # scalar int32

    @property
    def max_seq(self) -> int:
        return self.k.shape[2]


def init_cache(cfg: TransformerConfig, batch: int,
               max_seq: int | None = None) -> KVCache:
    from kvedge_tpu.models.moe import warn_if_train_serve_divergence

    cfg.validate()
    refuse_pattern(cfg, "the contiguous cache (models/decode.py)")
    warn_if_train_serve_divergence(cfg)
    shape = (
        cfg.n_layers, batch, max_seq or cfg.max_seq, cfg.kv_heads, cfg.d_head,
    )
    dtype = jnp.dtype(cfg.dtype)
    return KVCache(
        k=jnp.zeros(shape, dtype),
        v=jnp.zeros(shape, dtype),
        length=jnp.zeros((), jnp.int32),
    )


def _attend_layer(cfg: TransformerConfig, x, layer_params, k_slab, v_slab,
                  pos):
    """One decoder block against the cache.

    x: [B, Q, D] new positions starting at ``pos``; k_slab/v_slab:
    [B, S, K, Dh] this layer's cache. Returns (x, k_slab, v_slab) with the
    new positions written in. Works for prefill (Q = prompt len, pos = 0)
    and decode (Q = 1) alike.
    """
    if cfg.n_experts:
        w_qkv, w_out, router, w_up, w_down, ln_attn, ln_mlp = layer_params
    else:
        w_qkv, w_out, w_up, w_down, ln_attn, ln_mlp = layer_params
    batch, q_len, _ = x.shape
    h, kv, dh = cfg.n_heads, cfg.kv_heads, cfg.d_head
    group = h // kv
    max_seq = k_slab.shape[1]
    dtype = x.dtype

    normed = _rmsnorm(x, ln_attn)
    q, k, v = split_qkv(cfg, normed @ w_qkv.astype(dtype))
    positions = pos + jnp.arange(q_len)
    q = _rotary(q, positions, cfg.rope_theta)
    k = _rotary(k, positions, cfg.rope_theta)

    k_slab = lax.dynamic_update_slice(k_slab, k, (0, pos, 0, 0))
    v_slab = lax.dynamic_update_slice(v_slab, v, (0, pos, 0, 0))

    # Grouped attention against the whole slab; invalid tail positions are
    # masked out. q grouped as [B, Q, K, G, Dh] so each KV head serves its
    # G query heads without materializing a repeat.
    qg = q.reshape(batch, q_len, kv, group, dh)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, k_slab) / (dh ** 0.5)
    key_pos = jnp.arange(max_seq)
    allowed = key_pos[None, :] <= positions[:, None]  # [Q, S] causal+valid
    scores = jnp.where(
        allowed[None, None, None], scores, jnp.finfo(dtype).min
    )
    weights = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(dtype)
    attended = jnp.einsum("bkgqs,bskd->bqkgd", weights, v_slab)
    x = x + attended.reshape(batch, q_len, h * dh) @ w_out.astype(dtype)

    normed = _rmsnorm(x, ln_mlp)
    if cfg.n_experts:
        from kvedge_tpu.models.moe import routed_ffn_block

        x = x + routed_ffn_block(
            normed, router, w_up, w_down, top_k=cfg.expert_top_k
        )
    else:
        x = x + jax.nn.gelu(normed @ w_up.astype(dtype)) @ w_down.astype(dtype)
    return x, k_slab, v_slab


def _run_layers(cfg: TransformerConfig, params: dict, x, cache: KVCache,
                pos, all_positions: bool = False):
    """Scan the layer stack, threading each layer's cache slab through xs/ys.

    ``all_positions=True`` reads out logits at EVERY query position
    (speculative verification needs the argmax after each drafted
    token); the default reads only the last (prefill/decode). One
    definition of the layer pipeline for both, so the speculative
    path's numerics can never drift from plain decode's.
    """

    def body(carry, xs):
        layer_params, k_slab, v_slab = xs
        out, k_slab, v_slab = _attend_layer(
            cfg, carry, layer_params, k_slab, v_slab, pos
        )
        return out, (k_slab, v_slab)

    x, (new_k, new_v) = lax.scan(
        body, x, (stacked_layer_params(params, cfg), cache.k, cache.v)
    )
    new_cache = KVCache(k=new_k, v=new_v, length=pos + x.shape[1])
    x = _rmsnorm(x, params["ln_final"])
    logits = tied_readout(
        x if all_positions else x[:, -1], params["embedding"]
    )
    return logits, new_cache


@functools.partial(jax.jit, static_argnames=("cfg",), donate_argnums=(2,))
def prefill(params: dict, tokens, cache: KVCache, cfg: TransformerConfig):
    """Feed a [B, T] prompt into an empty cache.

    Returns (last-position logits [B, V] fp32, filled cache).
    """
    dtype = jnp.dtype(cfg.dtype)
    x = params["embedding"][tokens].astype(dtype)
    return _run_layers(cfg, params, x, cache, jnp.zeros((), jnp.int32))


@functools.partial(jax.jit, static_argnames=("cfg",), donate_argnums=(1,))
def decode_step(params: dict, cache: KVCache, tokens, cfg: TransformerConfig):
    """One decode step: [B] tokens at position ``cache.length``.

    Returns (logits [B, V] fp32, cache advanced by one).
    """
    dtype = jnp.dtype(cfg.dtype)
    x = params["embedding"][tokens][:, None].astype(dtype)  # [B, 1, D]
    return _run_layers(cfg, params, x, cache, cache.length)


def nucleus_filter(logits, temperature, top_p):
    """Temperature-scale + top-p (nucleus) filter. logits [..., V] fp32.

    Tokens outside the smallest probability mass >= ``top_p`` get -inf;
    the highest-probability token always survives (top_p -> 0 degrades
    to greedy). ONE definition shared by the contiguous scan and the
    continuous-batching server, so the two backends sample identically
    from identical logits — the cross-backend parity contract
    (tests/test_sampling.py). ``temperature``/``top_p`` are traced
    scalars: new values never recompile the serving loop.
    """
    scaled = logits / jnp.maximum(temperature, 1e-6)
    sorted_logits = jnp.sort(scaled, axis=-1)[..., ::-1]
    probs = jax.nn.softmax(sorted_logits, axis=-1)
    cumulative = jnp.cumsum(probs, axis=-1)
    # Keep ranks whose PRECEDING mass is < top_p (the first rank always
    # qualifies); map the rank cutoff back through a logit threshold.
    keep = (cumulative - probs) < top_p
    threshold = jnp.min(
        jnp.where(keep, sorted_logits, jnp.inf), axis=-1, keepdims=True
    )
    return jnp.where(scaled >= threshold, scaled, -jnp.inf)


def sample_token(logits, keys, temperature, top_p):
    """One sampled token id per row. logits [B, V] fp32, ``keys`` one
    PRNG key per row (each row owns its stream — batch composition must
    not change any row's tokens)."""
    filtered = nucleus_filter(logits, temperature, top_p)
    return jax.vmap(jax.random.categorical)(keys, filtered).astype(
        jnp.int32
    )


def row_sample_keys(seed_keys, step):
    """The shared key schedule: token ``step`` of a row samples with
    ``fold_in(row_seed, step)`` — a pure function of (row seed, token
    index), independent of batch composition or backend."""
    return jax.vmap(lambda k: jax.random.fold_in(k, step))(seed_keys)


@functools.partial(jax.jit, static_argnames=("cfg", "n_new", "sampled"))
def generate(params: dict, prompt, cfg: TransformerConfig, n_new: int,
             sampling=None, sampled: bool = False):
    """Decode ``n_new`` tokens after a [B, T] prompt.

    Greedy by default. With ``sampled=True``, ``sampling`` is a traced
    ``(seed_keys [B], temperature scalar, top_p scalar)`` triple: token
    ``t`` of row ``r`` samples from the nucleus-filtered logits with key
    ``fold_in(seed_keys[r], t)``. Temperature/top_p/keys are traced, so
    only the greedy/sampled CHOICE recompiles — not every request's
    parameters.

    Returns [B, T + n_new] int32. The whole loop is one compiled program:
    prefill, then a ``lax.scan`` of donated decode steps.
    """
    batch, prompt_len = prompt.shape
    cache = init_cache(cfg, batch, max_seq=prompt_len + n_new)
    logits, cache = prefill(params, prompt, cache, cfg)

    def pick(logits, step):
        if not sampled:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        seed_keys, temperature, top_p = sampling
        keys = row_sample_keys(seed_keys, step)
        return sample_token(logits, keys, temperature, top_p)

    def step_fn(carry, step):
        cache, logits = carry
        token = pick(logits, step)
        logits, cache = decode_step(params, cache, token, cfg)
        return (cache, logits), token

    # n_new - 1 cached steps; the final token falls out of the last carried
    # logits without paying for a decode step whose logits nobody reads.
    (_, logits), tokens = lax.scan(
        step_fn, (cache, logits), jnp.arange(n_new - 1)
    )
    last = pick(logits, n_new - 1)
    return jnp.concatenate([prompt, tokens.T, last[:, None]], axis=1)
