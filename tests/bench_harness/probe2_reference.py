"""A second block for the probe tree: one the program serves on the CPU
and ``references/starcoder2.py`` cannot compute.

The repo's expert feed-forward (``kvedge_tpu/models/moe.py``, ``[model]
experts = 4, expert_top_k = 2``): rotary grouped-query attention as in
the committed block, then a float32 softmax router over the experts, the
best two taken with their pair of gates normalised, no capacity (the
serving path drops nothing), ungated tanh-GELU experts, a tied head. The
weights are drawn as ``init_params`` draws them: ``PRNGKey(0)`` split
five ways, the router's key folded out of the up-projection's.

The tests copy this file into a probe checkout as
``benchmark/references/probe2.py``: a file and entries, as a
``model_config`` PR would add a block. ``CALLS`` marks which of the
three functions the harness called in this module.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

BF16 = 2
CALLS: list = []


def model_of(config: dict) -> dict:
    """The program's ``[model]`` from the published keys: the experts this
    chip holds (``num_local_experts`` as reduced) are the program's
    ``experts``, the experts a token takes its ``expert_top_k``."""
    return {"vocab": config["vocab_size"],
            "d_model": config["hidden_size"],
            "n_heads": config["num_attention_heads"],
            "n_kv_heads": config["num_key_value_heads"],
            "n_layers": config["num_hidden_layers"],
            "d_ff": config["intermediate_size"],
            "experts": config["num_local_experts"],
            "expert_top_k": config["num_experts_per_tok"]}


def _sizes(model: dict) -> tuple:
    d, h, kv = model["d_model"], model["n_heads"], model["n_kv_heads"]
    return (d, h, kv, d // h, model["d_ff"], model["n_layers"],
            model["experts"], model["expert_top_k"])


def make_weights(model: dict) -> dict:
    CALLS.append("make_weights")
    d, h, kv, dh, f, n, e, _ = _sizes(model)
    k_embed, k_qkv, k_out, k_up, k_down = jax.random.split(
        jax.random.PRNGKey(0), 5)

    def normal(key, shape, scale):
        return jax.random.normal(key, shape, jnp.float32) * scale

    return {
        "embedding": normal(k_embed, (model["vocab"], d), 0.02),
        "w_qkv": normal(k_qkv, (n, d, (h + 2 * kv) * dh), d ** -0.5),
        "w_out": normal(k_out, (n, h * dh, d), (h * dh) ** -0.5),
        "router": normal(jax.random.fold_in(k_up, 1), (n, d, e), d ** -0.5),
        "w_up": normal(k_up, (n, e, d, f), d ** -0.5),
        "w_down": normal(k_down, (n, e, f, d), f ** -0.5),
    }


def _rmsnorm(x):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + 1e-6)


def _rotary(x, positions):
    half = x.shape[-1] // 2
    freqs = jnp.exp(-jnp.arange(half, dtype=jnp.float32)
                    * (jnp.log(10000.0) / half))
    angles = positions[:, None].astype(jnp.float32) * freqs[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@functools.partial(jax.jit, static_argnames=("h", "kv", "top_k"))
def layer(x, w_qkv, w_out, router, w_up, w_down, *, h: int, kv: int,
          top_k: int):
    """One block over one sequence ``x`` [T, D], float32."""
    t, d = x.shape
    dh = d // h
    qkv = _rmsnorm(x) @ w_qkv
    q = qkv[:, :h * dh].reshape(t, h, dh)
    k = qkv[:, h * dh:(h + kv) * dh].reshape(t, kv, dh)
    v = qkv[:, (h + kv) * dh:].reshape(t, kv, dh)
    positions = jnp.arange(t)
    q, k = _rotary(q, positions), _rotary(k, positions)
    q = q.reshape(t, kv, h // kv, dh)
    scores = jnp.einsum("qkgd,skd->kgqs", q, k) / (dh ** 0.5)
    causal = positions[:, None] >= positions[None, :]
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    attended = jnp.einsum("kgqs,skd->qkgd", jax.nn.softmax(scores, -1),
                          v).reshape(t, h * dh)
    x = x + attended @ w_out
    normed = _rmsnorm(x)
    probs = jax.nn.softmax(normed @ router, axis=-1)            # [T, E]
    best, chosen = jax.lax.top_k(probs, top_k)
    gates = best if top_k == 1 else best / best.sum(-1, keepdims=True)
    share = (jax.nn.one_hot(chosen, probs.shape[-1]) * gates[..., None]
             ).sum(axis=1)                                       # [T, E]
    hidden = jax.nn.gelu(jnp.einsum("td,edf->tef", normed, w_up),
                         approximate=True)
    return x + jnp.einsum("te,tef,efd->td", share, hidden, w_down)


def logits(model: dict, weights: dict, sequences: list, first: list,
           quant: str = "") -> list:
    CALLS.append("logits")
    if quant:
        raise NotImplementedError("the probe's block has no control")
    _, h, kv, _, _, n, _, top_k = _sizes(model)
    out = []
    with jax.default_matmul_precision("highest"):
        for seq, f in zip(sequences, first):
            x = weights["embedding"][jnp.asarray(seq, jnp.int32)]
            for i in range(n):
                x = layer(x, *(weights[name][i] for name in (
                    "w_qkv", "w_out", "router", "w_up", "w_down")),
                    h=h, kv=kv, top_k=top_k)
            out.append(np.asarray(_rmsnorm(x) @ weights["embedding"].T)[f:])
    return out


def decode_step(model: dict, rows: float, live_tokens: float) -> dict:
    """Attention's matrices and the router for every row, each row's
    ``top_k`` experts (read once however many rows chose them, never
    more than there are), the tied head, the live keys and values."""
    CALLS.append("decode_step")
    d, h, kv, dh, f, n, e, top_k = _sizes(model)
    attention = d * (h + 2 * kv) * dh + h * dh * d
    expert = 2 * d * f
    head = model["vocab"] * d
    kv_bytes = n * 2 * kv * dh * BF16
    flops = (2.0 * rows * (n * (attention + d * e + top_k * expert) + head)
             + 4.0 * n * h * dh * live_tokens)
    nbytes = (BF16 * (n * (attention + d * e
                           + min(e, rows * top_k) * expert) + head)
              + kv_bytes * (live_tokens + rows))
    return {"flops": flops, "bytes": nbytes}
