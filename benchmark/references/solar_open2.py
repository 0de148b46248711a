"""The Solar Open 2 block (HF ``solar_open2``): its plain reference and its
counts.

Everything the benchmark believes about this block's mathematics is in
this file, behind the four functions ``cellspec.py`` asks of a block's
file: ``model_of``, ``make_weights``, ``logits`` and ``decode_step``.

The block (RMSNorm with a gain and ``rms_norm_eps`` before every mixer
and every feed-forward; ``h`` a layer's normed input):

    x = E[tokens]
    per layer:  x = x + Mixer(norm(x));  h = norm(x)
                x = x + Routed(h) + Shared(h)
    logits = norm(x) @ W_head.T                  (``tie_word_embeddings`` false)

* Layers in ``gqa_layers`` are softmax attention: bias-free q, k, v to
  ``num_attention_heads`` / ``num_key_value_heads`` heads of
  ``head_dim``, NO positional encoding (``use_rope`` false), causal
  softmax of ``q.k / sqrt(head_dim)``, and an output gate
  (``use_gqa_gate``): ``out = (sigmoid(h W_gate) * attended) W_o``.
* Every other layer is KDA, a gated delta rule with a decay per key
  channel (``linear_attn_config``: H heads, ``dk = dv = head_dim``, a
  causal depthwise conv of ``short_conv_kernel_size`` over each of the
  three projections, no bias):

      q = l2norm(silu(conv(h W_q))) / sqrt(dk);  k = l2norm(silu(conv(h W_k)))
      v = silu(conv(h W_v))
      g = -exp(A_log) * softplus((h W_f1) W_f2 + dt_bias)     [H, dk], <= 0
      beta = 2 * sigmoid(h W_b)        (the 2: ``kda_allow_neg_eigval``)
      S~ = diag(exp(g_t)) S_{t-1};  u_t = beta_t (v_t - S~^T k_t)
      S_t = S~ + k_t u_t^T;  o_t = S_t^T q_t                  S: [dk, dv]
      out = (sigmoid((h W_g1) W_g2) * rmsnorm_head(o)) W_o

  (``l2norm(x) = x / sqrt(sum(x^2) + 1e-6)`` and the last norm over each
  head's channels; the two gates are low-rank, ``kda_use_full_proj``
  false.)
* Routed: ``p = softmax(h W_r)`` over ALL published experts; the
  ``num_experts_per_tok`` largest, gates ``p_i`` over their sum
  (``norm_topk_prob``) times ``routed_scaling_factor``; an expert is
  ``(silu(u) * g) W_2`` with ``u | g = h W_13``. This chip holds
  ``n_routed_experts`` of them, from ``first_routed_expert`` on, and
  the result is their part of the gated sum, as the program's is.
* Shared: the same gated MLP at ``moe_intermediate_size *
  n_shared_experts``.

The reference is that in float32: straightforward ``jax.numpy``,
``default_matmul_precision("highest")``, KDA as the literal recurrence
position by position, the experts as a loop over those held, no cache,
no kernels, no batching, one layer at a time (attention's scores in
blocks of queries, so that 64 heads over 3,072 positions fit beside a
layer's weights). It imports nothing of the program and takes nothing
the program made. The weights are drawn here from the recipe the
program's initialiser states (kvedge_tpu/models/hybrid.py): every draw
float32 from ``fold_in(fold_in(PRNGKey(0), leaf number), layer)``, an
expert's from that key folded with its global index; matrices normal
times fan-in ** -0.5 (``W_q | W_k | W_v`` one leaf, ``W_f1 | W_g1 | W_b``
one leaf), embedding and head normal times 0.02, gains one; ``A``
uniform in [1, 16) a head, ``dt`` log-uniform in [0.001, 0.1) a key
channel through the inverse softplus. One layer is 3.1 GB in float32,
so ``make_weights`` keeps the two tables and ``logits`` draws a layer
at a time.
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
_LEAF = {"embedding": 0, "w_qkv": 7, "a_out": 8, "router": 9,
         "experts_in": 10, "experts_out": 11, "shared_in": 12,
         "shared_out": 13, "d_qkv": 14, "conv_w": 15, "w_low": 16,
         "w_f2": 17, "w_g2": 18, "A_log": 19, "dt_bias": 20, "d_out": 21,
         "w_gate": 22, "head": 23}


def model_of(config: dict) -> dict:
    """The program's ``[model]`` from the published keys: the one place
    that says which of the program's sizes each is. A published key the
    block cannot be told, stated at another value than the one the
    equations above assume, is refused here."""
    fixed = {"use_rope": False, "use_gqa_gate": True,
             "kda_use_full_proj": False, "kda_allow_neg_eigval": True,
             "tie_word_embeddings": False, "norm_topk_prob": True,
             "routed_scaling_factor": 1, "first_k_dense_replace": 0}
    for key, value in fixed.items():
        if config[key] != value:
            raise SystemExit(f"{key} = {config[key]!r}: this block is "
                             f"written for {value!r}")
    linear = config["linear_attn_config"]
    if linear["num_kv_heads"] not in (None, linear["num_heads"]):
        raise SystemExit("linear_attn_config.num_kv_heads: this block's "
                         "KDA has a key head for every head")
    layers, period = config["num_hidden_layers"], config["gqa_interval"] + 1
    softmax = sorted(config["gqa_layers"])
    if layers % period or softmax != list(range(0, layers, period)):
        raise SystemExit(
            f"gqa_layers {softmax} is not every {period}th of "
            f"{layers} layers from 0 (gqa_interval + 1)")
    published = config.get("published", {})
    return {
        "vocab": config["vocab_size"],
        "d_model": config["hidden_size"],
        "n_heads": config["num_attention_heads"],
        "n_kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "n_layers": layers,
        "layer_pattern": ["attention"] + ["delta"] * config["gqa_interval"],
        "ssm_heads": linear["num_heads"],
        "ssm_head_dim": linear["head_dim"],
        "ssm_state": linear["head_dim"],
        "ssm_conv": linear["short_conv_kernel_size"],
        # assumed: the low-rank gates are as wide as a head
        "ssm_gate_rank": linear["head_dim"],
        # The router's width is the published count; this chip's share
        # is what the file's reduced n_routed_experts states.
        "experts": published.get("n_routed_experts",
                                 config["n_routed_experts"]),
        "experts_held": config["n_routed_experts"],
        "expert_first": config.get("first_routed_expert", 0),
        "expert_top_k": config["num_experts_per_tok"],
        "d_ff": config["moe_intermediate_size"],
        "shared_ff": (config["moe_intermediate_size"]
                      * config["n_shared_experts"]),
        "ffn_gated": True,
        "attention_gate": True,
        "untied_head": True,
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


def _delta_sizes(model: dict) -> tuple:
    """(heads, dk, dv, keys = H dk, values = H dv, gate rank)."""
    heads, dv, dk = (model["ssm_heads"], model["ssm_head_dim"],
                     model["ssm_state"])
    return heads, dk, dv, heads * dk, heads * dv, model["ssm_gate_rank"]


def layer_weights(model: dict, layer: int, held: tuple | None = None) -> dict:
    """One layer's float32 weights by the recipe. ``held`` = (first, n)
    draws another share of the routed experts than the model's own."""
    base = jax.random.PRNGKey(WEIGHT_SEED)

    def key(leaf):
        return jax.random.fold_in(jax.random.fold_in(base, _LEAF[leaf]),
                                  layer)

    d, f, sf = model["d_model"], model["d_ff"], model["shared_ff"]
    w = {"kind": _kind(model, layer)}
    if w["kind"] == "delta":
        heads, _, _, keys, values, rank = _delta_sizes(model)
        k = model["ssm_conv"]
        dt = jnp.exp(jax.random.uniform(
            key("dt_bias"), (keys,), jnp.float32, math.log(1e-3),
            math.log(1e-1)))
        w.update(
            d_qkv=_draw_normal(key("d_qkv"), (d, 2 * keys + values),
                               d ** -0.5),
            conv_w=_draw_normal(key("conv_w"), (k, 2 * keys + values),
                                k ** -0.5),
            w_low=_draw_normal(key("w_low"), (d, 2 * rank + heads),
                               d ** -0.5),
            w_f2=_draw_normal(key("w_f2"), (rank, keys), rank ** -0.5),
            w_g2=_draw_normal(key("w_g2"), (rank, values), rank ** -0.5),
            A_log=jnp.log(jax.random.uniform(key("A_log"), (heads,),
                                             jnp.float32, 1.0, 16.0)),
            dt_bias=dt + jnp.log(-jnp.expm1(-dt)),
            d_out=_draw_normal(key("d_out"), (values, d), values ** -0.5))
    else:
        h, kv, dh = model["n_heads"], model["n_kv_heads"], model["head_dim"]
        w.update(
            w_qkv=_draw_normal(key("w_qkv"), (d, (h + 2 * kv) * dh),
                               d ** -0.5),
            w_gate=_draw_normal(key("w_gate"), (d, h * dh), d ** -0.5),
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


def table(model: dict, leaf: str):
    """The embedding or the head, [vocab, hidden]."""
    key = jax.random.fold_in(jax.random.PRNGKey(WEIGHT_SEED), _LEAF[leaf])
    return _draw_normal(key, (model["vocab"], model["d_model"]), 0.02)


def make_weights(model: dict) -> dict:
    """The embedding and the head of its own; the layers are drawn as
    ``logits`` reaches them, one at a time."""
    return {"embedding": table(model, "embedding"),
            "head": table(model, "head")}


# ---- the forward pass -------------------------------------------------------


def _rmsnorm(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _l2norm(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


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
    return x @ w


@functools.partial(jax.jit, static_argnames=("quant",))
def _gated(x, w_in, w_out, quant):
    up = _matmul(x, w_in, quant)
    half = up.shape[-1] // 2
    return _matmul(jax.nn.silu(up[:, :half]) * up[:, half:], w_out, quant)


@functools.partial(jax.jit, static_argnames=("heads", "dk", "dv", "rank",
                                             "eps", "quant"))
def kda(x, w, *, heads: int, dk: int, dv: int, rank: int, eps: float,
        quant: str = ""):
    """The KDA mixer over one sequence x [T, D] (already normed), from a
    zero state: the literal recurrence, position by position."""
    t = x.shape[0]
    keys = heads * dk
    qkv = _matmul(x, w["d_qkv"], quant)
    low = _matmul(x, w["w_low"], quant)
    k_conv = w["conv_w"].shape[0]
    padded = jnp.concatenate([jnp.zeros((k_conv - 1, qkv.shape[1])), qkv])
    qkv = jax.nn.silu(sum(padded[j:j + t] * w["conv_w"][j]
                          for j in range(k_conv)))
    q = _l2norm(qkv[:, :keys].reshape(t, heads, dk)) * dk ** -0.5
    k = _l2norm(qkv[:, keys:2 * keys].reshape(t, heads, dk))
    v = qkv[:, 2 * keys:].reshape(t, heads, dv)
    g = -jnp.exp(w["A_log"])[:, None] * jax.nn.softplus(
        _matmul(low[:, :rank], w["w_f2"], quant) + w["dt_bias"]
    ).reshape(t, heads, dk)
    beta = 2.0 * jax.nn.sigmoid(low[:, 2 * rank:])
    gate = jax.nn.sigmoid(_matmul(low[:, rank:2 * rank], w["w_g2"], quant))

    def step(state, now):
        q_t, k_t, v_t, g_t, beta_t = now
        state = jnp.exp(g_t)[:, :, None] * state
        u = beta_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", state, k_t))
        state = state + k_t[:, :, None] * u[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    _, o = jax.lax.scan(step, jnp.zeros((heads, dk, dv)),
                        (q, k, v, g, beta))
    o = _rmsnorm(o, eps).reshape(t, heads * dv)          # gain one
    return _matmul(gate * o, w["d_out"], quant)


@functools.partial(jax.jit, static_argnames=("h", "kv", "dh", "quant"))
def attention(x, w, *, h: int, kv: int, dh: int, quant: str = ""):
    """Gated grouped-query attention over x [T, D] (already normed), no
    positional encoding, scores over sqrt(dh); the queries in blocks."""
    t = x.shape[0]
    qkv = _matmul(x, w["w_qkv"], quant)
    q = qkv[:, :h * dh].reshape(t, kv, h // kv, dh)
    k = qkv[:, h * dh:(h + kv) * dh].reshape(t, kv, dh)
    v = qkv[:, (h + kv) * dh:].reshape(t, kv, dh)
    if quant == "int8":
        q, k, v = _int8(q, -1), _int8(k, -1), _int8(v, -1)
    blocks = []
    for lo in range(0, t, 512):
        hi = min(t, lo + 512)
        scores = jnp.einsum("qkgd,skd->kgqs", q[lo:hi], k) / math.sqrt(dh)
        causal = jnp.arange(lo, hi)[:, None] >= jnp.arange(t)[None, :]
        weights = jax.nn.softmax(
            jnp.where(causal[None, None], scores, -jnp.inf), axis=-1)
        if quant == "int8":
            weights = _int8(weights, -1)
        blocks.append(jnp.einsum("kgqs,skd->qkgd", weights, v))
    attended = jnp.concatenate(blocks).reshape(t, h * dh)
    gate = jax.nn.sigmoid(_matmul(x, w["w_gate"], quant))
    return _matmul(gate * attended, w["a_out"], quant)


def route(h, router, top_k: int):
    """(picks [T, k], gates [T, k]): the ``top_k`` largest of the
    softmax over all experts, each over their sum."""
    top, idx = jax.lax.top_k(jax.nn.softmax(h @ router, axis=-1), top_k)
    return idx, top / jnp.sum(top, axis=-1, keepdims=True)


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
    expert), each once for a sequence length: as one program a loop
    over dozens of experts takes the chip's compiler minutes."""
    eps = model["norm_eps"]
    if w["kind"] == "delta":
        heads, dk, dv, _, _, rank = _delta_sizes(model)
        arrays = {k: w[k] for k in ("d_qkv", "conv_w", "w_low", "w_f2",
                                    "w_g2", "A_log", "dt_bias", "d_out")}
        mixed = kda(_rmsnorm(x, eps), arrays, heads=heads, dk=dk, dv=dv,
                    rank=rank, eps=eps, quant=quant)
    else:
        arrays = {k: w[k] for k in ("w_qkv", "w_gate", "a_out")}
        mixed = attention(_rmsnorm(x, eps), arrays, h=model["n_heads"],
                          kv=model["n_kv_heads"], dh=model["head_dim"],
                          quant=quant)
    x = x + mixed
    out, idx = feed_forward(_rmsnorm(x, eps), w,
                            top_k=model["expert_top_k"], quant=quant)
    return x + out, idx


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def readout(x, head, *, eps: float, quant: str = ""):
    return _matmul(_rmsnorm(x, eps), head.T, quant)


def logits(model: dict, weights: dict, sequences: list,
           first: list, quant: str = "", picks: list | None = None) -> list:
    """For each token sequence, float32 logits [T - first, V] of the
    positions from ``first`` on (the ones that predict served tokens).
    ``quant = "int8"`` is the control: the same pass with every matrix
    product, the keys, values and attention weights rounded to int8.
    ``picks``, a list, receives each layer's [sequences][T, k] picks."""
    with jax.default_matmul_precision("highest"):
        xs = [weights["embedding"][jnp.asarray(s, jnp.int32)]
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
        return [np.asarray(readout(x, weights["head"],
                                   eps=model["norm_eps"], quant=quant))[f:]
                for x, f in zip(xs, first)]


# ---- what one decode step needs, from shapes -----------------------------


def _layer_kinds(model: dict) -> tuple[int, int]:
    kinds = [_kind(model, i) for i in range(model["n_layers"])]
    return kinds.count("delta"), kinds.count("attention")


def delta_params(model: dict) -> int:
    """A KDA mixer's matrices: q | k | v, the two low-rank gates and
    ``W_b``, the conv, the output projection."""
    heads, _, _, keys, values, rank = _delta_sizes(model)
    d = model["d_model"]
    return (d * (2 * keys + values) + d * (2 * rank + heads)
            + rank * (keys + values)
            + model["ssm_conv"] * (2 * keys + values) + values * d)


def attention_params(model: dict) -> int:
    """q | k | v, the output gate and the output projection."""
    d, h, kv, dh = (model["d_model"], model["n_heads"], model["n_kv_heads"],
                    model["head_dim"])
    return d * (h + 2 * kv) * dh + 2 * h * dh * d


def expert_params(model: dict) -> int:
    return 3 * model["d_model"] * model["d_ff"]


def shared_params(model: dict) -> int:
    return 3 * model["d_model"] * model["shared_ff"]


def state_bytes_per_row(model: dict) -> int:
    """A row's recurrent state: the float32 [H dk, dv] of every KDA
    layer and its conv's tail in bf16."""
    n_delta, _ = _layer_kinds(model)
    _, _, dv, keys, values, _ = _delta_sizes(model)
    return n_delta * (F32 * keys * dv
                      + BF16 * (model["ssm_conv"] - 1) * (2 * keys + values))


def kv_bytes_per_token(model: dict) -> int:
    _, n_att = _layer_kinds(model)
    return n_att * 2 * model["n_kv_heads"] * model["head_dim"] * BF16


def decode_step(model: dict, rows: float, live_tokens: float) -> dict:
    """One decode step over ``rows`` sequences holding ``live_tokens``
    cached positions between them, by the equations: every mixer, the
    shared expert, the head's slice and every HELD expert once in bf16
    (a product over all held experts reads them whatever the routing;
    ``expert_touched_pct`` says how many a step's picks reach), the
    router in float32, the rows' recurrent state once in and once out,
    the live keys and values once. Operations: two a parameter a token
    passes, the state's decay, two reads and update, attention over the
    live tokens."""
    n_delta, n_att = _layer_kinds(model)
    layers, d = model["n_layers"], model["d_model"]
    k, total, held = (model["expert_top_k"], model["experts"],
                      model["experts_held"])
    always = (n_delta * delta_params(model)
              + n_att * attention_params(model)
              + layers * shared_params(model) + model["vocab"] * d)
    nbytes = (BF16 * (always + layers * held * expert_params(model))
              + F32 * layers * d * total
              + 2.0 * rows * state_bytes_per_row(model)
              + kv_bytes_per_token(model) * (live_tokens + rows))
    _, _, dv, keys, _, _ = _delta_sizes(model)
    flops = (2.0 * rows * (always + layers * d * total
                           + layers * k * held / total
                           * expert_params(model))
             + 7.0 * rows * n_delta * keys * dv
             + 4.0 * n_att * model["n_heads"] * model["head_dim"]
             * live_tokens)
    return {"flops": flops, "bytes": nbytes}
