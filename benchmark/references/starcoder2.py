"""The StarCoder2 block: its plain reference and its counts.

Everything the benchmark believes about this block's mathematics is in
this file, behind the four functions ``cellspec.py`` asks of a block's
file: ``model_of``, ``make_weights``, ``logits`` and ``decode_step``.

The reference is the configuration's forward pass in float32:
straightforward ``jax.numpy``, ``default_matmul_precision("highest")``,
no cache, no kernels, no batching, one layer at a time. It imports
nothing of the program and takes nothing the program made: the weights
are drawn here, from the same published recipe the serve payload uses
when the state volume holds no checkpoint (``PRNGKey(0)`` split five
ways; normal draws scaled by 0.02 for the embedding and by the inverse
root of the fan-in for the matrices; gains of one). The block: rotary
grouped-query attention over a pre-norm residual stream, an ungated
tanh-GELU feed-forward, RMSNorm with epsilon 1e-6, a tied head.
Departures from the published StarCoder2 block are the configuration
file's ``departures``.

The counts are what the model's equations require of one decode step,
not what the program happens to read: the matrices once a step in the
type they are multiplied in (bf16), the live keys and values once.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

WEIGHT_SEED = 0
BF16 = 2


def model_of(config: dict) -> dict:
    """The program's ``[model]`` from the published keys: the one place
    that says which of the program's sizes each is. The published keys
    this leaves unread (``rope_theta``, ``use_bias``, ``norm_type``,
    ``sliding_window``, ...) are ones the program's block cannot be told:
    the configuration's ``departures`` names each."""
    return {"vocab": config["vocab_size"],
            "d_model": config["hidden_size"],
            "n_heads": config["num_attention_heads"],
            "n_kv_heads": config["num_key_value_heads"],
            "n_layers": config["num_hidden_layers"],
            "d_ff": config["intermediate_size"]}


def _shapes(model: dict) -> dict:
    d, h, kv, f, n = (model["d_model"], model["n_heads"],
                      model["n_kv_heads"], model["d_ff"], model["n_layers"])
    dh = d // h
    return {  # name: (shape, scale), in the order the key is split
        "embedding": ((model["vocab"], d), 0.02),
        "w_qkv": ((n, d, (h + 2 * kv) * dh), d ** -0.5),
        "w_out": ((n, h * dh, d), (h * dh) ** -0.5),
        "w_up": ((n, d, f), d ** -0.5),
        "w_down": ((n, f, d), f ** -0.5),
    }


def _layer_sharding(model: dict):
    """Where the stacked matrices go: their layers split over the chips
    when there are several (a model one chip cannot hold)."""
    devices = jax.devices()
    if len(devices) == 1 or model["n_layers"] % len(devices):
        return None
    return NamedSharding(Mesh(devices, ("layers",)), PartitionSpec("layers"))


def make_weights(model: dict) -> dict:
    """The float32 weights, made on the device in one jitted call each."""
    shapes = _shapes(model)
    sharding = _layer_sharding(model)
    keys = jax.random.split(jax.random.PRNGKey(WEIGHT_SEED), len(shapes))
    out = {}
    for key, (name, (shape, scale)) in zip(keys, shapes.items()):
        draw = jax.jit(
            lambda k, shape=shape, scale=scale:
            jax.random.normal(k, shape, jnp.float32) * scale,
            out_shardings=None if name == "embedding" else sharding)
        out[name] = draw(key)
    return out


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


def _int8(x, axis: int):
    """``x`` as symmetric int8 would hold it, one scale along ``axis``
    (a row of activations, a column of weights, a head's keys)."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return jnp.round(x / scale) * scale


def _matmul(x, w, quant: str):
    """``x @ w`` in float32, or, as the control, on operands rounded to
    int8: the precision below the bf16 the configuration serves in."""
    if quant == "int8":
        return _int8(x, -1) @ _int8(w, 0)
    if quant == "bf16":  # the precision served in: what a sound run has
        return jnp.dot(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)
    return x @ w


@functools.partial(jax.jit, static_argnames=("h", "kv", "quant"))
def layer(x, w_qkv, w_out, w_up, w_down, *, h: int, kv: int,
          quant: str = ""):
    """One block over one sequence ``x`` [T, D], float32."""
    t, d = x.shape
    dh = d // h
    qkv = _matmul(_rmsnorm(x), w_qkv, quant)
    q = qkv[:, :h * dh].reshape(t, h, dh)
    k = qkv[:, h * dh:(h + kv) * dh].reshape(t, kv, dh)
    v = qkv[:, (h + kv) * dh:].reshape(t, kv, dh)
    positions = jnp.arange(t)
    q, k = _rotary(q, positions), _rotary(k, positions)
    if quant == "int8":
        q, k, v = _int8(q, -1), _int8(k, -1), _int8(v, -1)
    q = q.reshape(t, kv, h // kv, dh)
    scores = jnp.einsum("qkgd,skd->kgqs", q, k) / (dh ** 0.5)
    causal = positions[:, None] >= positions[None, :]
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    weights = jax.nn.softmax(scores, axis=-1)
    if quant == "int8":
        weights = _int8(weights, -1)
    attended = jnp.einsum("kgqs,skd->qkgd", weights, v).reshape(t, h * dh)
    x = x + _matmul(attended, w_out, quant)
    up = jax.nn.gelu(_matmul(_rmsnorm(x), w_up, quant), approximate=True)
    return x + _matmul(up, w_down, quant)


@functools.partial(jax.jit, static_argnames=("quant",))
def readout(x, embedding, quant: str = ""):
    return _matmul(_rmsnorm(x), embedding.T, quant)


def logits(model: dict, weights: dict, sequences: list,
           first: list, quant: str = "") -> list:
    """For each token sequence, float32 logits [T - first, V] of the
    positions from ``first`` on (the ones that predict served tokens).
    ``quant = "int8"`` is the control: the same pass with every matrix
    product, the keys, values and attention weights rounded to int8."""
    h, kv = model["n_heads"], model["n_kv_heads"]
    device = jax.devices()[0]
    with jax.default_matmul_precision("highest"):
        xs = [weights["embedding"][jnp.asarray(s, jnp.int32)]
              for s in sequences]
        for i in range(model["n_layers"]):
            w = [jax.device_put(weights[name][i], device)
                 for name in ("w_qkv", "w_out", "w_up", "w_down")]
            xs = [layer(x, *w, h=h, kv=kv, quant=quant) for x in xs]
        # every position is read out and the prompt's cut away on the
        # host: one program per padded length, not one per prompt length
        return [np.asarray(readout(x, weights["embedding"], quant))[f:]
                for x, f in zip(xs, first)]


# ---- what one decode step needs, from shapes -----------------------------


def layer_params(model: dict) -> int:
    """Matrix parameters of one block: fused q|k|v, output projection,
    feed-forward up and down (no biases, the gains are not counted)."""
    d, h, kv, f = (model["d_model"], model["n_heads"], model["n_kv_heads"],
                   model["d_ff"])
    dh = d // h
    return d * (h + 2 * kv) * dh + h * dh * d + 2 * d * f


def matrix_params(model: dict) -> int:
    """All matrices a token passes through, the tied head included."""
    return (model["n_layers"] * layer_params(model)
            + model["vocab"] * model["d_model"])


def kv_bytes_per_token(model: dict) -> int:
    dh = model["d_model"] // model["n_heads"]
    return model["n_layers"] * 2 * model["n_kv_heads"] * dh * BF16


def decode_step(model: dict, rows: float, live_tokens: float) -> dict:
    """One decode step over ``rows`` sequences holding ``live_tokens``
    cached positions between them."""
    d, h = model["d_model"], model["n_heads"]
    dh = d // h
    flops = (2.0 * matrix_params(model) * rows
             + 4.0 * model["n_layers"] * h * dh * live_tokens)
    nbytes = (BF16 * matrix_params(model)
              + kv_bytes_per_token(model) * (live_tokens + rows))
    return {"flops": flops, "bytes": nbytes}
