"""The K-EXAONE block (HF ``exaone_moe``): its plain reference and its
counts.

Everything the benchmark believes about this block's mathematics is in
this file, behind the four functions ``cellspec.py`` asks of a block's
file: ``model_of``, ``make_weights``, ``logits`` and ``decode_step``.

The block (RMSNorm with a gain and ``rms_norm_eps``; no bias on a
projection; layer ``l`` is a *window* layer where ``layer_types[l]`` is
``sliding_attention`` and a *full* layer where it is ``full_attention``;
*dense* where ``mlp_layer_types[l]`` says so, the first
``first_k_dense_replace``, and *sparse* otherwise):

    x = E[tokens]
    per layer:  q, k, v = x W_q, x W_k, x W_v     H query / K key heads of Dh
                q = RMSNorm(q; g_q),  k = RMSNorm(k; g_k)        a head, [Dh]
                window layer:  q, k = RoPE(q, k; position i, rope_theta)
                               query i sees keys i - W + 1 .. i
                full layer:    q, k as normed (no positional encoding)
                               query i sees keys 0 .. i
                x = x + RMSNorm(softmax(q k^T / sqrt(Dh)) v W_o; g_attn)
                dense layer:   f = (silu(x W_gate) * (x W_up)) W_down
                sparse layer:  s = sigmoid(x W_r)        float32, every expert
                               picks = the k largest of s + b
                               gate_e = c * s_e / sum of s over the picks
                               f = sum over picks of gate_e Expert_e(x) + Shared(x)
                x = x + RMSNorm(f; g_ffn)
    logits = RMSNorm(x; g_f) W_head^T             (``tie_word_embeddings`` false)

* The norm of a sublayer is on its OUTPUT and its input is the stream as
  it stands (the family's reordered norm; assumed, like the norm on q and
  k and rotary on the window layers only: EXAONE 4.0's).
* ``b`` is a choice bias an expert (assumed: the router keys are
  DeepSeek-V3's, where it comes with them): it moves the picks and never
  a gate. ``c`` is ``routed_scaling_factor``; ``n_group = topk_group =
  1``: no group limit.
* An expert, the shared expert (``moe_intermediate_size *
  num_shared_experts`` wide, added ungated) and the dense MLP are
  ``(silu(u) * g) W_2`` with ``u | g = x W_13``. This chip holds
  ``num_experts`` of the published experts, from ``first_routed_expert``
  on, and the routed sum is their part, as the program's is.
* RoPE rotates halves: with ``f_j = rope_theta ** (-j / (Dh / 2))``,
  ``(x1, x2) -> (x1 cos - x2 sin, x1 sin + x2 cos)`` at angle ``i f_j``.
* ``W`` is ``sliding_window`` and counts the query's own position.
* The multi-token-prediction module (``num_nextn_predict_layers``) is no
  part of the next token's logits and is left out (the configuration's
  ``departures``).

The reference is that in float32: straightforward ``jax.numpy``,
``default_matmul_precision("highest")``, the window as a band mask over
the whole sequence (the scores in blocks of queries, so that 64 heads
over 8,192 positions fit), the experts as a loop over those held, no
cache, no kernels, no batching, one layer at a time, the head's product
in blocks of positions. It imports nothing of the program and takes
nothing the program made. The weights are drawn here from the recipe the
program's initialiser states (kvedge_tpu/models/hybrid.py): every draw
float32 from ``fold_in(fold_in(PRNGKey(0), leaf number), layer)``,
``layer`` counted from the leading dense layer, an expert's from that key
folded with its global index; matrices normal times fan-in ** -0.5
(``W_q | W_k | W_v`` one leaf), ``b`` normal times 0.01, embedding and
head normal times 0.02, gains one. A sparse layer is 3.0 GB in float32,
so ``make_weights`` keeps the two tables and ``logits`` draws a layer at
a time.
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
QUERY_BLOCK = 256   # queries scored at once
READ_BLOCK = 512    # positions read out at once

# The recipe's leaf numbers (hybrid._LEAVES), a mixer's by layer kind.
_LEAF = {"embedding": 0, "router": 9, "experts_in": 10, "experts_out": 11,
         "shared_in": 12, "shared_out": 13, "head": 23,
         "dense_in": 26, "dense_out": 27, "router_bias": 28,
         "attention": {"w_qkv": 7, "w_out": 8},
         "window": {"w_qkv": 24, "w_out": 25}}
_KINDS = {"sliding_attention": "window", "full_attention": "attention"}


def _period(kinds: list) -> list:
    n = len(kinds)
    period = next(p for p in range(1, n + 1)
                  if n % p == 0 and kinds == kinds[:p] * (n // p))
    return kinds[:period]


def model_of(config: dict) -> dict:
    """The program's ``[model]`` from the published keys: the one place
    that says which of the program's sizes each is. A published key the
    block cannot be told, stated at another value than the one the
    equations above assume, is refused here. ``seq`` is not the
    program's (it reads no such key): ``decode_step`` bounds a row's
    length by it, and it is the ``payload``'s."""
    fixed = {"scoring_func": "sigmoid", "n_group": 1, "topk_group": 1,
             "norm_topk_prob": True, "tie_word_embeddings": False,
             "hidden_act": "silu", "num_shared_experts": 1}
    for key, value in fixed.items():
        if config[key] != value:
            raise SystemExit(f"{key} = {config[key]!r}: this block is "
                             f"written for {value!r}")
    rope = config["rope_parameters"]
    if rope["rope_type"] != "default":
        raise SystemExit(f"rope_parameters.rope_type = {rope['rope_type']!r}"
                         ": this block is written for 'default'")
    layers, dense = config["num_hidden_layers"], config["first_k_dense_replace"]
    types, window = list(config["layer_types"]), config["sliding_window"]
    if (len(types) != layers or set(types) - set(_KINDS)
            or list(config["mlp_layer_types"])
            != ["dense"] * dense + ["sparse"] * (layers - dense)
            or list(config["sliding_windows"])
            != [window if t == "sliding_attention" else 0 for t in types]):
        raise SystemExit(
            "layer_types, mlp_layer_types and sliding_windows: each names "
            f"every one of the {layers} layers, the first "
            f"first_k_dense_replace = {dense} dense and no other, a "
            f"sliding_attention layer's window sliding_window = {window} "
            "and a full_attention layer's 0")
    kinds = [_KINDS[t] for t in types]
    pattern = _period(kinds[dense:])
    if kinds[:dense] != [pattern[(i - dense) % len(pattern)]
                         for i in range(dense)]:
        raise SystemExit(
            "layer_types: the leading dense layers' kinds are the later "
            f"layers' period {pattern} continued backwards, got "
            f"{kinds[:dense]}")
    published = config.get("published", {})
    return {
        "vocab": config["vocab_size"],
        "d_model": config["hidden_size"],
        "n_heads": config["num_attention_heads"],
        "n_kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "n_layers": layers,
        "layer_pattern": pattern,
        "dense_layers": dense,
        "dense_ff": config["intermediate_size"],
        "attention_window": window,
        "rope_theta": float(rope["rope_theta"]),
        "rotary": False,
        "qk_norm": True,
        "norm_after": True,
        # The router's width is the published count; this chip's share
        # is what the file's reduced num_experts states.
        "experts": published.get("num_experts", config["num_experts"]),
        "experts_held": config["num_experts"],
        "expert_first": config.get("first_routed_expert", 0),
        "expert_top_k": config["num_experts_per_tok"],
        "d_ff": config["moe_intermediate_size"],
        "shared_ff": (config["moe_intermediate_size"]
                      * config["num_shared_experts"]),
        "ffn_gated": True,
        "router_score": "sigmoid",
        "router_bias": True,
        "router_scale": float(config["routed_scaling_factor"]),
        "untied_head": True,
        "norm_eps": config["rms_norm_eps"],
        "seq": config["payload"]["seq"],
    }


# ---- weights: the recipe, one layer at a time ------------------------------


def _kind(model: dict, layer: int) -> str:
    pattern = model["layer_pattern"]
    return pattern[(layer - model["dense_layers"]) % len(pattern)]


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
    kind = _kind(model, layer)

    def key(leaf):
        number = _LEAF[kind][leaf] if leaf in _LEAF[kind] else _LEAF[leaf]
        return jax.random.fold_in(jax.random.fold_in(base, number), layer)

    d, f, sf = model["d_model"], model["d_ff"], model["shared_ff"]
    h, kv, dh = model["n_heads"], model["n_kv_heads"], model["head_dim"]
    w = {
        "kind": kind,
        "dense": layer < model["dense_layers"],
        "w_qkv": _draw_normal(key("w_qkv"), (d, (h + 2 * kv) * dh),
                              d ** -0.5),
        "w_out": _draw_normal(key("w_out"), (h * dh, d), (h * dh) ** -0.5),
    }
    if w["dense"]:
        df = model["dense_ff"]
        w.update(
            dense_in=_draw_normal(key("dense_in"), (d, 2 * df), d ** -0.5),
            dense_out=_draw_normal(key("dense_out"), (df, d), df ** -0.5))
        return w
    first, n_held = held or (model["expert_first"], model["experts_held"])
    w.update(
        first=first,
        router=_draw_normal(key("router"), (d, model["experts"]), d ** -0.5),
        router_bias=_draw_normal(key("router_bias"), (model["experts"],),
                                 0.01),
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


def rope(x, theta: float):
    """x [T, heads, Dh] rotated by its position, halves paired."""
    t, _, dh = x.shape
    half = dh // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def band(lo, n: int, t: int, window: int):
    """[n, t] bool: may query ``lo + i`` see key ``j``. Causal, and
    with ``window`` > 0 the last ``window`` positions only, the query's
    own among them."""
    q = lo + jnp.arange(n)[:, None]
    k = jnp.arange(t)[None, :]
    seen = k <= q
    if window:
        seen = seen & (k > q - window)
    return seen


@functools.partial(jax.jit, static_argnames=("h", "kv", "dh", "window",
                                             "theta", "eps", "quant"))
def attention(x, w, *, h: int, kv: int, dh: int, eps: float,
              window: int = 0, theta: float = 0.0, quant: str = ""):
    """Grouped-query attention over the stream x [T, D] as it stands
    (T whole blocks of queries), q and k normed a head (gain one),
    scores over sqrt(dh), the queries in blocks. ``window`` > 0 is a
    window layer: q and k rotated (``theta``), the mask a band."""
    t = x.shape[0]
    qkv = _matmul(x, w["w_qkv"], quant)
    q = _rmsnorm(qkv[:, :h * dh].reshape(t, h, dh), eps)
    k = _rmsnorm(qkv[:, h * dh:(h + kv) * dh].reshape(t, kv, dh), eps)
    v = qkv[:, (h + kv) * dh:].reshape(t, kv, dh)
    if window:
        q, k = rope(q, theta), rope(k, theta)
    q = q.reshape(t, kv, h // kv, dh)
    if quant == "int8":
        q, k, v = _int8(q, -1), _int8(k, -1), _int8(v, -1)
    def block(lo):
        """Queries ``lo`` to ``lo + QUERY_BLOCK`` over every key."""
        scores = jnp.einsum(
            "qkgd,skd->kgqs",
            jax.lax.dynamic_slice_in_dim(q, lo, QUERY_BLOCK), k
        ) / math.sqrt(dh)
        weights = jax.nn.softmax(
            jnp.where(band(lo, QUERY_BLOCK, t, window)[None, None], scores,
                      -jnp.inf), axis=-1)
        if quant == "int8":
            weights = _int8(weights, -1)
        return jnp.einsum("kgqs,skd->qkgd", weights, v)

    # one block's program, mapped over the blocks (unrolled, 32 blocks of
    # an 8,192-position sequence took the chip's compiler minutes)
    blocks = jax.lax.map(block, jnp.arange(0, t, QUERY_BLOCK))
    attended = blocks.reshape(t, h * dh)
    return _matmul(attended, w["w_out"], quant)


@functools.partial(jax.jit, static_argnames=("top_k", "scale", "biased"))
def route(x, router, bias, top_k: int, scale: float, biased: bool = True):
    """(picks [T, k], gates [T, k]): every expert's sigmoid score, the
    ``top_k`` largest of score plus bias, the picked scores over their
    sum times ``scale``. ``biased`` false is not this block: the choice
    without its bias, which a test must tell from the block's."""
    scores = jax.nn.sigmoid(x @ router)
    _, idx = jax.lax.top_k(scores + bias if biased else scores, top_k)
    picked = jnp.take_along_axis(scores, idx, axis=-1)
    return idx, scale * picked / jnp.sum(picked, axis=-1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("quant",))
def _gated(x, w_in, w_out, quant):
    up = _matmul(x, w_in, quant)
    half = up.shape[-1] // 2
    return _matmul(jax.nn.silu(up[:, :half]) * up[:, half:], w_out, quant)


@functools.partial(jax.jit, static_argnames=("quant",))
def _add_expert(out, x, idx, gates, w_in, w_out, expert, quant):
    gate = jnp.sum(jnp.where(idx == expert, gates, 0.0), axis=-1)
    return out + gate[:, None] * _gated(x, w_in, w_out, quant)


def routed(x, w, *, top_k: int, scale: float, quant: str = ""):
    """The held experts' part of the routed sum: a loop over them, each
    over every token, weighted by the token's gate for it (zero where
    the token did not pick it). Also returns the picks."""
    idx, gates = route(x, w["router"], w["router_bias"], top_k, scale)
    out = jnp.zeros_like(x)
    for i in range(w["experts_in"].shape[0]):
        out = _add_expert(out, x, idx, gates, w["experts_in"][i],
                          w["experts_out"][i], w["first"] + i, quant)
    return out, idx


def feed_forward(model: dict, x, w: dict, quant: str = ""):
    """A layer's feed-forward over the stream as it stands, before its
    norm: ``(f, picks)``; a dense layer has no picks (None)."""
    if w["dense"]:
        return _gated(x, w["dense_in"], w["dense_out"], quant), None
    out, idx = routed(x, w, top_k=model["expert_top_k"],
                      scale=model["router_scale"], quant=quant)
    return out + _gated(x, w["shared_in"], w["shared_out"], quant), idx


def layer(model: dict, x, w: dict, quant: str = ""):
    """One block over one sequence x [T, D]: ``(x, picks [T, k] or
    None)``. Its parts are compiled one by one (the mixer, the router,
    one expert), each once for a sequence length."""
    eps = model["norm_eps"]
    window = model["attention_window"] if w["kind"] == "window" else 0
    x = x + _rmsnorm(attention(
        x, {"w_qkv": w["w_qkv"], "w_out": w["w_out"]},
        h=model["n_heads"], kv=model["n_kv_heads"], dh=model["head_dim"],
        eps=eps, window=window,
        theta=model["rope_theta"] if window else 0.0, quant=quant), eps)
    f, idx = feed_forward(model, x, w, quant)
    return x + _rmsnorm(f, eps), idx


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def readout(x, head, *, eps: float, quant: str = ""):
    return _matmul(_rmsnorm(x, eps), head.T, quant)


def _read(x, head, start: int, eps: float, quant: str):
    """Logits of positions ``start`` on, the head's product in blocks
    of positions (one program whatever the length)."""
    t = x.shape[0]
    lo = start - start % READ_BLOCK
    pad = -t % READ_BLOCK
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad, x.shape[1]), x.dtype)])
    rows = [np.asarray(readout(x[b:b + READ_BLOCK], head, eps=eps,
                               quant=quant))
            for b in range(lo, t, READ_BLOCK)]
    return np.concatenate(rows)[start - lo:t - lo]


def logits(model: dict, weights: dict, sequences: list,
           first: list, quant: str = "", picks: list | None = None):
    """For each token sequence, float32 logits [T - first, V] of the
    positions from ``first`` on (the ones that predict served tokens).
    ``quant = "int8"`` is the control: the same pass with every matrix
    product, the keys, values and attention weights rounded to int8.
    ``picks``, a list, receives each sparse layer's [sequences][T, k]
    picks. The layers run here; the head's product is made sequence by
    sequence as the result is read, once through. Every sequence is
    padded to one length, the served context ``seq`` (causal: the tail
    changes nothing before it), so that each part is one program
    whatever the sample's lengths: a length of its own cost each part a
    compile, and a cold check 497 s (PERF.md section 6, PR 43)."""
    lengths = [len(s) for s in sequences]
    span = -(-max(lengths + [model["seq"]]) // QUERY_BLOCK) * QUERY_BLOCK
    with jax.default_matmul_precision("highest"):
        xs = [weights["embedding"][jnp.asarray(
            list(s) + [0] * (span - len(s)), jnp.int32)] for s in sequences]
        for i in range(model["n_layers"]):
            w = layer_weights(model, i)
            done = [layer(model, x, w, quant) for x in xs]
            xs = [x for x, _ in done]
            if picks is not None and not w["dense"]:
                picks.append([np.asarray(idx)[:n]
                              for (_, idx), n in zip(done, lengths)])
            del w

    def read(x, n, start):
        with jax.default_matmul_precision("highest"):
            return _read(x[:n], weights["head"], start, model["norm_eps"],
                         quant)

    return map(read, xs, lengths, first)


# ---- what one decode step needs, from shapes -----------------------------


def attention_layers(model: dict) -> tuple[int, int]:
    """(full layers, window layers)."""
    kinds = [_kind(model, i) for i in range(model["n_layers"])]
    return kinds.count("attention"), kinds.count("window")


def attention_params(model: dict) -> int:
    """q | k | v and the output projection, of either kind."""
    d, h, kv, dh = (model["d_model"], model["n_heads"], model["n_kv_heads"],
                    model["head_dim"])
    return d * (h + 2 * kv) * dh + h * dh * d


def expert_params(model: dict) -> int:
    return 3 * model["d_model"] * model["d_ff"]


def shared_params(model: dict) -> int:
    return 3 * model["d_model"] * model["shared_ff"]


def dense_params(model: dict) -> int:
    return 3 * model["d_model"] * model["dense_ff"]


def kv_bytes_per_token(model: dict) -> int:
    """One layer's keys and values of one position, bf16."""
    return 2 * model["n_kv_heads"] * model["head_dim"] * BF16


def page_bytes(model: dict, page_size: int) -> int:
    """One layer's keys and values on one page of ``page_size``
    positions: what the paged-attention kernel reads for it."""
    return page_size * kv_bytes_per_token(model)


def window_tokens(model: dict, rows: float, live_tokens: float) -> float:
    """The least that any split of ``live_tokens`` positions over
    ``rows`` rows of at most ``seq`` positions holds in the rows'
    windows: a row of ``n`` positions holds ``min(n, window)``, and the
    whole is least when the positions crowd into as few rows as they
    can, ``seq`` each, which keep ``window`` of every ``seq``. The
    counters that reach here are totals; a count from the mean context,
    ``rows * min(live_tokens / rows, window)``, would overstate what
    short and long rows together hold."""
    window, seq = model["attention_window"], model["seq"]
    if not window or window >= seq:
        return live_tokens
    return live_tokens * window / seq


def decode_step(model: dict, rows: float, live_tokens: float) -> dict:
    """One decode step over ``rows`` sequences holding ``live_tokens``
    cached positions between them, by the equations: every layer's
    attention matrices, the dense layers' MLP, every sparse layer's
    shared expert and HELD experts (a product over all held experts
    reads them whatever the routing) and the head's slice once in bf16,
    the router and its bias in float32, the full layers' keys and values
    of the live positions once, the window layers' of the least the
    rows' windows can hold (:func:`window_tokens`), and the rows' new
    keys and values. Operations: two a parameter a token passes (of the
    held experts the share of its picks that falls on them), attention
    over the positions counted."""
    n_full, n_win = attention_layers(model)
    layers, d = model["n_layers"], model["d_model"]
    dense = model["dense_layers"]
    sparse = layers - dense
    k, total, held = (model["expert_top_k"], model["experts"],
                      model["experts_held"])
    always = (layers * attention_params(model) + dense * dense_params(model)
              + sparse * shared_params(model) + model["vocab"] * d)
    windowed = window_tokens(model, rows, live_tokens)
    positions = n_full * (live_tokens + rows) + n_win * (windowed + rows)
    nbytes = (BF16 * (always + sparse * held * expert_params(model))
              + F32 * sparse * (d + 1) * total
              + kv_bytes_per_token(model) * positions)
    flops = (2.0 * rows * (always + sparse * d * total
                           + sparse * k * held / total
                           * expert_params(model))
             + 4.0 * model["n_heads"] * model["head_dim"] * positions)
    return {"flops": flops, "bytes": nbytes}
