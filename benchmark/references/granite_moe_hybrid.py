"""The Granite-4.0-H block (HF ``GraniteMoeHybrid``): its plain reference
and its counts.

Everything the benchmark believes about this block's mathematics is in
this file, behind the four functions ``cellspec.py`` asks of a block's
file: ``model_of``, ``make_weights``, ``logits`` and ``decode_step``.

The block, with ``e`` = ``embedding_multiplier``, ``r`` =
``residual_multiplier``, ``a`` = ``attention_multiplier``, ``s`` =
``logits_scaling`` and RMSNorm with a gain and ``rms_norm_eps``:

    x = e * E[tokens]
    per layer:  x = x + r * Mixer(norm(x));  h = norm(x)
                x = x + r * (Routed(h) + Shared(h))
    logits = norm(x) @ E.T / s

* Attention mixer: bias-free q, k, v to ``num_attention_heads`` /
  ``num_key_value_heads`` heads, NO rotary (``nope``), causal softmax of
  ``a * q.k``, output projection.
* Mamba-2 mixer: ``z | xBC | dt = h @ W_in``; ``xBC = silu(causal
  depthwise conv(xBC) + b)``; ``x | B | C = xBC``; ``dt = softplus(dt +
  dt_bias)``; ``A = -exp(A_log)``; ``S_t = exp(dt A) S_{t-1} + dt x_t (x)
  B_t``; ``y_t = S_t C_t + D x_t``; ``y = rmsnorm(y * silu(z)) * g``
  over all inner channels (one group; gate first); ``out = y @ W_out``.
* Routed: ``l = h @ W_r`` over ALL published experts; the top
  ``num_experts_per_tok`` of ``l``, gates a softmax over those logits;
  expert ``(silu(u) * g) @ W_out`` with ``u | g = h @ W_in``. This chip
  holds ``num_local_experts`` of them, from ``first_local_expert`` on,
  and the result is their part of the gated sum, as the program's is.
* Shared: the same gated SiLU MLP at ``shared_intermediate_size``.

The reference is that in float32: straightforward ``jax.numpy``,
``default_matmul_precision("highest")``, the SSM as the literal
recurrence over positions, the experts as a loop over those held, no
cache, no kernels, no batching, one layer at a time. It imports nothing
of the program and takes nothing the program made. The weights are
drawn here from the recipe the program's initialiser states
(kvedge_tpu/models/hybrid.py): every draw float32 from
``fold_in(fold_in(PRNGKey(0), leaf number), layer)``, an expert's from
that key folded with its global index; matrices normal times fan-in **
-0.5, the embedding normal times 0.02 / ``embedding_multiplier``, the
conv's bias normal times 0.02, gains one;
``A`` uniform in [1, 16), ``dt`` log-uniform in [0.001, 0.1) through
the inverse softplus, ``D`` one. The float32 tree of the benchmark's
configuration is 19 GB, so ``make_weights`` keeps the recipe and
``logits`` draws one layer (1.84 GB) at a time.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

WEIGHT_SEED = 0
BF16 = 2
F32 = 4

# The recipe's leaf numbers (hybrid._LEAVES).
_LEAF = {"embedding": 0, "w_in": 1, "conv_w": 2, "conv_b": 3, "A_log": 4,
         "dt_bias": 5, "m_out": 6, "w_qkv": 7, "a_out": 8, "router": 9,
         "experts_in": 10, "experts_out": 11, "shared_in": 12,
         "shared_out": 13}


def _period(kinds: list) -> list:
    """The shortest pattern ``kinds`` repeats."""
    for n in range(1, len(kinds) + 1):
        if len(kinds) % n == 0 and kinds == kinds[:n] * (len(kinds) // n):
            return kinds[:n]
    return kinds


def model_of(config: dict) -> dict:
    """The program's ``[model]`` from the published keys: the one place
    that says which of the program's sizes each is. A published key the
    block cannot be told, stated at another value than the one the
    equations above assume, is refused here."""
    fixed = {"mamba_n_groups": 1, "mamba_proj_bias": False,
             "mamba_conv_bias": True, "attention_bias": False,
             "hidden_act": "silu", "position_embedding_type": "nope",
             "normalization_function": "rmsnorm",
             "tie_word_embeddings": True}
    for key, value in fixed.items():
        if config[key] != value:
            raise SystemExit(f"{key} = {config[key]!r}: this block is "
                             f"written for {value!r}")
    if (config["mamba_expand"] * config["hidden_size"]
            != config["mamba_n_heads"] * config["mamba_d_head"]):
        raise SystemExit("mamba_expand * hidden_size is not "
                         "mamba_n_heads * mamba_d_head")
    kinds = list(config["layer_types"])
    if len(kinds) != config["num_hidden_layers"]:
        raise SystemExit("layer_types does not name num_hidden_layers "
                         "layers")
    published = config.get("published", {})
    return {
        "vocab": config["vocab_size"],
        "d_model": config["hidden_size"],
        "n_heads": config["num_attention_heads"],
        "n_kv_heads": config["num_key_value_heads"],
        "n_layers": config["num_hidden_layers"],
        "layer_pattern": _period(kinds),
        "ssm_heads": config["mamba_n_heads"],
        "ssm_head_dim": config["mamba_d_head"],
        "ssm_state": config["mamba_d_state"],
        "ssm_conv": config["mamba_d_conv"],
        "ssm_chunk": config["mamba_chunk_size"],
        # The router's width is the published count; this chip's share
        # is what the file's reduced num_local_experts states.
        "experts": published.get("num_local_experts",
                                 config["num_local_experts"]),
        "experts_held": config["num_local_experts"],
        "expert_first": config.get("first_local_expert", 0),
        "expert_top_k": config["num_experts_per_tok"],
        "d_ff": config["intermediate_size"],
        "shared_ff": config["shared_intermediate_size"],
        "ffn_gated": True,
        "embedding_multiplier": config["embedding_multiplier"],
        "residual_multiplier": config["residual_multiplier"],
        "attention_multiplier": config["attention_multiplier"],
        "logits_scaling": config["logits_scaling"],
        "rotary": False,
        "norm_eps": config["rms_norm_eps"],
    }


# ---- weights: the recipe, one layer at a time ------------------------------


def _kind(model: dict, layer: int) -> str:
    pattern = model["layer_pattern"]
    return pattern[layer % len(pattern)]


def _normal(key, shape, scale):
    return jax.random.normal(key, shape, jnp.float32) * scale


@functools.partial(jax.jit, static_argnames=("shape", "scale"))
def _draw_normal(key, shape, scale):
    return _normal(key, shape, scale)


@functools.partial(jax.jit, static_argnames=("shape", "scale", "n"))
def _draw_experts(key, first, shape, scale, n):
    keys = jax.vmap(lambda e: jax.random.fold_in(key, e))(
        first + jnp.arange(n))
    return jax.vmap(lambda k: _normal(k, shape, scale))(keys)


def layer_weights(model: dict, layer: int, held: tuple | None = None) -> dict:
    """One layer's float32 weights by the recipe. ``held`` = (first, n)
    draws another share of the routed experts than the model's own."""
    base = jax.random.PRNGKey(WEIGHT_SEED)

    def key(leaf):
        return jax.random.fold_in(jax.random.fold_in(base, _LEAF[leaf]),
                                  layer)

    d, f, sf = model["d_model"], model["d_ff"], model["shared_ff"]
    w = {"kind": _kind(model, layer)}
    if w["kind"] == "mamba":
        heads, p, n = (model["ssm_heads"], model["ssm_head_dim"],
                       model["ssm_state"])
        inner, k = heads * p, model["ssm_conv"]
        conv_dim = inner + 2 * n
        dt = jnp.exp(jax.random.uniform(
            key("dt_bias"), (heads,), jnp.float32, math.log(1e-3),
            math.log(1e-1)))
        w.update(
            w_in=_draw_normal(key("w_in"), (d, 2 * inner + 2 * n + heads),
                              d ** -0.5),
            conv_w=_draw_normal(key("conv_w"), (k, conv_dim), k ** -0.5),
            conv_b=_draw_normal(key("conv_b"), (conv_dim,), 0.02),
            A_log=jnp.log(jax.random.uniform(key("A_log"), (heads,),
                                             jnp.float32, 1.0, 16.0)),
            dt_bias=dt + jnp.log(-jnp.expm1(-dt)),
            D=jnp.ones((heads,), jnp.float32),
            m_out=_draw_normal(key("m_out"), (inner, d), inner ** -0.5))
    else:
        h, kv = model["n_heads"], model["n_kv_heads"]
        dh = d // h
        w.update(
            w_qkv=_draw_normal(key("w_qkv"), (d, (h + 2 * kv) * dh),
                               d ** -0.5),
            a_out=_draw_normal(key("a_out"), (h * dh, d), (h * dh) ** -0.5))
    first, n_held = held or (model["expert_first"], model["experts_held"])
    w.update(
        first=first,
        router=_draw_normal(key("router"), (d, model["experts"]), d ** -0.5),
        experts_in=_draw_experts(key("experts_in"), first, (d, 2 * f),
                                 d ** -0.5, n_held),
        experts_out=_draw_experts(key("experts_out"), first, (f, d),
                                  f ** -0.5, n_held),
        shared_in=_draw_normal(key("shared_in"), (d, 2 * sf), d ** -0.5),
        shared_out=_draw_normal(key("shared_out"), (sf, d), sf ** -0.5))
    return w


def embedding(model: dict):
    key = jax.random.fold_in(jax.random.PRNGKey(WEIGHT_SEED),
                             _LEAF["embedding"])
    return _draw_normal(key, (model["vocab"], model["d_model"]),
                        0.02 / model["embedding_multiplier"])


def make_weights(model: dict) -> dict:
    """The recipe and the embedding (tied head); the layers are drawn
    as ``logits`` reaches them, one at a time."""
    return {"embedding": embedding(model)}


# ---- the forward pass -------------------------------------------------------


def _rmsnorm(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _int8(x, axis: int):
    """``x`` as symmetric int8 would hold it, one scale along ``axis``."""
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


@functools.partial(jax.jit, static_argnames=("quant",))
def _gated(x, w_in, w_out, quant):
    up = _matmul(x, w_in, quant)
    half = up.shape[-1] // 2
    return _matmul(jax.nn.silu(up[:, :half]) * up[:, half:], w_out, quant)


@functools.partial(jax.jit,
                   static_argnames=("heads", "p", "n", "eps", "quant"))
def mamba(x, w, *, heads: int, p: int, n: int, eps: float, quant: str = ""):
    """The Mamba-2 mixer over one sequence x [T, D] (already normed),
    from a zero state: the literal recurrence, position by position."""
    t = x.shape[0]
    inner = heads * p
    conv_dim = inner + 2 * n
    proj = _matmul(x, w["w_in"], quant)
    z, xbc, dt = (proj[:, :inner], proj[:, inner:inner + conv_dim],
                  proj[:, inner + conv_dim:])
    k = w["conv_w"].shape[0]
    padded = jnp.concatenate([jnp.zeros((k - 1, conv_dim)), xbc])
    conv = sum(padded[j:j + t] * w["conv_w"][j] for j in range(k))
    xbc = jax.nn.silu(conv + w["conv_b"])
    xs = xbc[:, :inner].reshape(t, heads, p)
    bs, cs = xbc[:, inner:inner + n], xbc[:, inner + n:]
    dt = jax.nn.softplus(dt + w["dt_bias"])
    a = -jnp.exp(w["A_log"])

    def step(state, now):
        x_t, b_t, c_t, dt_t = now
        state = (jnp.exp(dt_t * a)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :])
        return state, state @ c_t + w["D"][:, None] * x_t

    _, y = jax.lax.scan(step, jnp.zeros((heads, p, n)), (xs, bs, cs, dt))
    y = _rmsnorm(y.reshape(t, inner) * jax.nn.silu(z), eps)  # gain one
    return _matmul(y, w["m_out"], quant)


@functools.partial(jax.jit, static_argnames=("h", "kv", "scale", "quant"))
def attention(x, w, *, h: int, kv: int, scale: float, quant: str = ""):
    """Grouped-query attention over x [T, D] (already normed), no
    positional encoding, scores scaled by ``scale``."""
    t, d = x.shape
    dh = d // h
    qkv = _matmul(x, w["w_qkv"], quant)
    q = qkv[:, :h * dh].reshape(t, kv, h // kv, dh)
    k = qkv[:, h * dh:(h + kv) * dh].reshape(t, kv, dh)
    v = qkv[:, (h + kv) * dh:].reshape(t, kv, dh)
    if quant == "int8":
        q, k, v = _int8(q, -1), _int8(k, -1), _int8(v, -1)
    scores = jnp.einsum("qkgd,skd->kgqs", q, k) * scale
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    weights = jax.nn.softmax(
        jnp.where(causal[None, None], scores, -jnp.inf), axis=-1)
    if quant == "int8":
        weights = _int8(weights, -1)
    attended = jnp.einsum("kgqs,skd->qkgd", weights, v).reshape(t, h * dh)
    return _matmul(attended, w["a_out"], quant)


def route(h, router, top_k: int):
    """(picks [T, k], gates [T, k]): the top ``top_k`` of the router's
    logits, gated by a softmax over those logits alone."""
    top, idx = jax.lax.top_k(h @ router, top_k)
    return idx, jax.nn.softmax(top, axis=-1)


@functools.partial(jax.jit, static_argnames=("quant",))
def _add_expert(out, h, idx, gates, w_in, w_out, expert, quant):
    gate = jnp.sum(jnp.where(idx == expert, gates, 0.0), axis=-1)
    return out + gate[:, None] * _gated(h, w_in, w_out, quant)


def routed(h, w, *, top_k: int, quant: str = ""):
    """The held experts' part of the routed sum: a loop over them, each
    over every token, weighted by the token's gate for it (zero where
    the token did not pick it). Also returns the picks."""
    idx, gates = route(h, w["router"], top_k)
    out = jnp.zeros_like(h)
    for i in range(w["experts_in"].shape[0]):
        out = _add_expert(out, h, idx, gates, w["experts_in"][i],
                          w["experts_out"][i], w["first"] + i, quant)
    return out, idx


def feed_forward(h, w, *, top_k: int, quant: str = ""):
    out, idx = routed(h, w, top_k=top_k, quant=quant)
    return out + _gated(h, w["shared_in"], w["shared_out"], quant), idx


def layer(model: dict, x, w: dict, quant: str = ""):
    """One block over one sequence x [T, D]: ``(x, picks [T, k])``. Its
    parts are compiled one by one (a mixer, one expert, the shared
    expert), each once for a sequence length: as one program the loop
    over 36 experts took the chip's compiler six minutes."""
    eps, r = model["norm_eps"], model["residual_multiplier"]
    if w["kind"] == "mamba":
        arrays = {k: w[k] for k in ("w_in", "conv_w", "conv_b", "A_log",
                                    "dt_bias", "D", "m_out")}
        mixed = mamba(_rmsnorm(x, eps), arrays, heads=model["ssm_heads"],
                      p=model["ssm_head_dim"], n=model["ssm_state"],
                      eps=eps, quant=quant)
    else:
        arrays = {k: w[k] for k in ("w_qkv", "a_out")}
        mixed = attention(_rmsnorm(x, eps), arrays, h=model["n_heads"],
                          kv=model["n_kv_heads"],
                          scale=model["attention_multiplier"], quant=quant)
    x = x + r * mixed
    out, idx = feed_forward(_rmsnorm(x, eps), w,
                            top_k=model["expert_top_k"], quant=quant)
    return x + r * out, idx


@functools.partial(jax.jit, static_argnames=("eps", "scaling", "quant"))
def readout(x, embedding, *, eps: float, scaling: float, quant: str = ""):
    return _matmul(_rmsnorm(x, eps), embedding.T, quant) / scaling


def logits(model: dict, weights: dict, sequences: list,
           first: list, quant: str = "", picks: list | None = None) -> list:
    """For each token sequence, float32 logits [T - first, V] of the
    positions from ``first`` on (the ones that predict served tokens).
    ``quant = "int8"`` is the control: the same pass with every matrix
    product, the keys, values and attention weights rounded to int8.
    ``picks``, a list, receives each layer's [sequences][T, k] picks."""
    with jax.default_matmul_precision("highest"):
        table = weights["embedding"]
        xs = [model["embedding_multiplier"] * table[jnp.asarray(s, jnp.int32)]
              for s in sequences]
        for i in range(model["n_layers"]):
            w = layer_weights(model, i)
            done = [layer(model, x, w, quant) for x in xs]
            xs = [x for x, _ in done]
            if picks is not None:
                picks.append([np.asarray(idx) for _, idx in done])
            del w
        # every position is read out and the prompt's cut away on the
        # host: one program per padded length, not one per prompt length
        return [np.asarray(readout(
            x, table, eps=model["norm_eps"],
            scaling=model["logits_scaling"], quant=quant))[f:]
            for x, f in zip(xs, first)]


# ---- what one decode step needs, from shapes -----------------------------


def _layer_kinds(model: dict) -> tuple[int, int]:
    kinds = [_kind(model, i) for i in range(model["n_layers"])]
    return kinds.count("mamba"), kinds.count("attention")


def mamba_params(model: dict) -> int:
    d, n = model["d_model"], model["ssm_state"]
    inner = model["ssm_heads"] * model["ssm_head_dim"]
    return d * (2 * inner + 2 * n + model["ssm_heads"]) + inner * d


def attention_params(model: dict) -> int:
    d, h, kv = model["d_model"], model["n_heads"], model["n_kv_heads"]
    dh = d // h
    return d * (h + 2 * kv) * dh + h * dh * d


def expert_params(model: dict) -> int:
    return 3 * model["d_model"] * model["d_ff"]


def shared_params(model: dict) -> int:
    return 3 * model["d_model"] * model["shared_ff"]


def state_bytes_per_row(model: dict) -> int:
    """A row's recurrent state: float32 SSM state and the conv's tail
    in bf16, every mamba layer."""
    n_mamba, _ = _layer_kinds(model)
    inner = model["ssm_heads"] * model["ssm_head_dim"]
    return n_mamba * (F32 * inner * model["ssm_state"]
                      + BF16 * (model["ssm_conv"] - 1)
                      * (inner + 2 * model["ssm_state"]))


def kv_bytes_per_token(model: dict) -> int:
    _, n_att = _layer_kinds(model)
    dh = model["d_model"] // model["n_heads"]
    return n_att * 2 * model["n_kv_heads"] * dh * BF16


def decode_step(model: dict, rows: float, live_tokens: float) -> dict:
    """One decode step over ``rows`` sequences holding ``live_tokens``
    cached positions between them, by the equations: every mixer, the
    shared expert and the head's slice once in bf16, the router in
    float32, each held expert that the rows' picks reach under even
    routing once (all of them from about 40 rows at 10 of 72), the
    rows' recurrent state once in and once out, the live keys and
    values once. Operations: two a parameter a token passes, the
    state's update and read, attention over the live tokens."""
    n_mamba, n_att = _layer_kinds(model)
    layers, d = model["n_layers"], model["d_model"]
    k, total, held = (model["expert_top_k"], model["experts"],
                      model["experts_held"])
    reached = held * (1.0 - (1.0 - k / total) ** rows)
    always = (n_mamba * mamba_params(model)
              + n_att * attention_params(model)
              + layers * shared_params(model) + model["vocab"] * d)
    nbytes = (BF16 * (always + layers * reached * expert_params(model))
              + F32 * layers * d * total
              + 2.0 * rows * state_bytes_per_row(model)
              + kv_bytes_per_token(model) * (live_tokens + rows))
    inner = model["ssm_heads"] * model["ssm_head_dim"]
    flops = (2.0 * rows * (always + layers * d * total
                           + layers * k * held / total
                           * expert_params(model))
             + 6.0 * rows * n_mamba * inner * model["ssm_state"]
             + 4.0 * n_att * d * live_tokens)
    return {"flops": flops, "bytes": nbytes}
