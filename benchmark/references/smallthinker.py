"""The SmallThinker block (HF ``smallthinker``): its plain reference and
its counts.

Everything the benchmark believes about this block's mathematics is in
this file, behind the four functions ``cellspec.py`` asks of a block's
file: ``model_of``, ``make_weights``, ``logits`` and ``decode_step``.

The block (RMSNorm with a gain and ``rms_norm_eps``; no bias anywhere;
layer ``l`` is a *full* layer where ``sliding_window_layout[l]`` is 0
and a *window* layer where it is 1, and ``rope_layout`` is the same
list):

    x = E[tokens]
    per layer:  a = norm(x)
                r = a W_r                      float32, every routed expert
                picks = the k largest of r;  gate = softmax over those k
                q, k, v = a W_q, a W_k, a W_v  H query / K key heads of Dh
                window layer:  q, k = RoPE(q, k; position i, rope_theta)
                               query i sees keys i - W + 1 .. i
                full layer:    q, k as projected (no positional encoding)
                               query i sees keys 0 .. i
                x = x + softmax(q k^T / sqrt(Dh)) v W_o
                h = norm(x)
                x = x + sum over picks of gate_e (relu(h W_gate,e) * (h W_up,e)) W_down,e
    logits = norm(x) W_head^T                  (``tie_word_embeddings`` false)

* The router reads the layer's normed input ``a``, the mixer's, and not
  the feed-forward's own ``h`` (the family's "router placed before
  attention"); its gates are the softmax over all experts' logits, the
  ``moe_num_active_primary_experts`` largest, each over their sum
  (``moe_primary_router_apply_softmax``, ``norm_topk_prob``): the
  softmax over the picked logits.
* An expert is a ReGLU: ``(relu(u) * g) W_2`` with ``u | g = h W_13``.
  There is no shared expert and no dense layer.
* RoPE rotates halves: with ``f_j = rope_theta ** (-j / (Dh / 2))``,
  ``(x1, x2) -> (x1 cos - x2 sin, x1 sin + x2 cos)`` at angle ``i f_j``.
* ``W`` is ``sliding_window_size`` and counts the query's own position.

The reference is that in float32: straightforward ``jax.numpy``,
``default_matmul_precision("highest")``, the window as a band mask over
the whole sequence (the scores in blocks of queries, so that 28 heads
over 8,192 positions fit), the experts as a loop over all of them, no
cache, no kernels, no batching, one layer at a time, the head's product
in blocks of positions (8,192 x 151,936 logits are 5 GB). It imports
nothing of the program and takes nothing the program made. The weights
are drawn here from the recipe the program's initialiser states
(kvedge_tpu/models/hybrid.py): every draw float32 from
``fold_in(fold_in(PRNGKey(0), leaf number), layer)``, an expert's from
that key folded with its index; matrices normal times fan-in ** -0.5
(``W_q | W_k | W_v`` one leaf), embedding and head normal times 0.02,
gains one. One layer is 1.6 GB in float32, so ``make_weights`` keeps the
two tables and ``logits`` draws a layer at a time.
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
QUERY_BLOCK = 512   # queries scored at once
READ_BLOCK = 512    # positions read out at once

# The recipe's leaf numbers (hybrid._LEAVES), by layer kind.
_LEAF = {"embedding": 0, "router": 9, "experts_in": 10, "experts_out": 11,
         "head": 23,
         "attention": {"w_qkv": 7, "w_out": 8},
         "window": {"w_qkv": 24, "w_out": 25}}


def _pattern(layout: list) -> list:
    """One period of layer kinds from a 0/1 layout, 0 a full layer."""
    n = len(layout)
    period = next(p for p in range(1, n + 1)
                  if n % p == 0 and layout == layout[:p] * (n // p))
    return ["window" if bit else "attention" for bit in layout[:period]]


def model_of(config: dict) -> dict:
    """The program's ``[model]`` from the published keys: the one place
    that says which of the program's sizes each is. A published key the
    block cannot be told, stated at another value than the one the
    equations above assume, is refused here. ``seq`` is not the
    program's (it reads no such key): ``decode_step`` bounds a row's
    length by it, and it is the ``payload``'s."""
    fixed = {"moe_primary_router_apply_softmax": True,
             "norm_topk_prob": True, "tie_word_embeddings": False,
             "rope_scaling": None}
    for key, value in fixed.items():
        if config[key] != value:
            raise SystemExit(f"{key} = {config[key]!r}: this block is "
                             f"written for {value!r}")
    layers = config["num_hidden_layers"]
    layout = list(config["sliding_window_layout"])
    if list(config["rope_layout"]) != layout or len(layout) != layers:
        raise SystemExit(
            "rope_layout and sliding_window_layout: this block rotates "
            "the layers it binds to a window and no other, and each "
            f"list names every one of the {layers} layers")
    return {
        "vocab": config["vocab_size"],
        "d_model": config["hidden_size"],
        "n_heads": config["num_attention_heads"],
        "n_kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "n_layers": layers,
        "layer_pattern": _pattern(layout),
        "attention_window": config["sliding_window_size"],
        "rope_theta": float(config["rope_theta"]),
        "rotary": False,
        "experts": config["moe_num_primary_experts"],
        "expert_top_k": config["moe_num_active_primary_experts"],
        "d_ff": config["moe_ffn_hidden_size"],
        "ffn_gated": True,
        "ffn_activation": "relu",
        "router_before_mixer": True,
        "untied_head": True,
        "norm_eps": config["rms_norm_eps"],
        "seq": config["payload"]["seq"],
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
def _draw_experts(key, shape, scale, n):
    keys = jax.vmap(lambda e: jax.random.fold_in(key, e))(jnp.arange(n))
    return jax.vmap(lambda k: _normal(k, shape, scale))(keys)


def layer_weights(model: dict, layer: int) -> dict:
    """One layer's float32 weights by the recipe."""
    base = jax.random.PRNGKey(WEIGHT_SEED)
    kind = _kind(model, layer)

    def key(leaf):
        number = _LEAF[kind][leaf] if leaf in _LEAF[kind] else _LEAF[leaf]
        return jax.random.fold_in(jax.random.fold_in(base, number), layer)

    d, f, n = model["d_model"], model["d_ff"], model["experts"]
    h, kv, dh = model["n_heads"], model["n_kv_heads"], model["head_dim"]
    return {
        "kind": kind,
        "w_qkv": _draw_normal(key("w_qkv"), (d, (h + 2 * kv) * dh),
                              d ** -0.5),
        "w_out": _draw_normal(key("w_out"), (h * dh, d), (h * dh) ** -0.5),
        "router": _draw_normal(key("router"), (d, n), d ** -0.5),
        "experts_in": _draw_experts(key("experts_in"), (d, 2 * f),
                                    d ** -0.5, n),
        "experts_out": _draw_experts(key("experts_out"), (f, d),
                                     f ** -0.5, n),
    }


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


def band(lo: int, hi: int, t: int, window: int):
    """[hi - lo, t] bool: may query ``lo + i`` see key ``j``. Causal,
    and with ``window`` > 0 the last ``window`` positions only, the
    query's own among them."""
    q = jnp.arange(lo, hi)[:, None]
    k = jnp.arange(t)[None, :]
    seen = k <= q
    if window:
        seen = seen & (k > q - window)
    return seen


@functools.partial(jax.jit, static_argnames=("h", "kv", "dh", "window",
                                             "theta", "quant"))
def attention(x, w, *, h: int, kv: int, dh: int, window: int = 0,
              theta: float = 0.0, quant: str = ""):
    """Grouped-query attention over x [T, D] (already normed), scores
    over sqrt(dh), the queries in blocks. ``window`` > 0 is a window
    layer: q and k rotated (``theta``), the mask a band."""
    t = x.shape[0]
    qkv = _matmul(x, w["w_qkv"], quant)
    q = qkv[:, :h * dh].reshape(t, h, dh)
    k = qkv[:, h * dh:(h + kv) * dh].reshape(t, kv, dh)
    v = qkv[:, (h + kv) * dh:].reshape(t, kv, dh)
    if window:
        q, k = rope(q, theta), rope(k, theta)
    q = q.reshape(t, kv, h // kv, dh)
    if quant == "int8":
        q, k, v = _int8(q, -1), _int8(k, -1), _int8(v, -1)
    blocks = []
    for lo in range(0, t, QUERY_BLOCK):
        hi = min(t, lo + QUERY_BLOCK)
        scores = jnp.einsum("qkgd,skd->kgqs", q[lo:hi], k) / math.sqrt(dh)
        weights = jax.nn.softmax(
            jnp.where(band(lo, hi, t, window)[None, None], scores,
                      -jnp.inf), axis=-1)
        if quant == "int8":
            weights = _int8(weights, -1)
        blocks.append(jnp.einsum("kgqs,skd->qkgd", weights, v))
    attended = jnp.concatenate(blocks).reshape(t, h * dh)
    return _matmul(attended, w["w_out"], quant)


@functools.partial(jax.jit, static_argnames=("top_k",))
def route(a, router, top_k: int):
    """(picks [T, k], gates [T, k]) off the router's input ``a``: the
    ``top_k`` largest logits, their softmax."""
    top, idx = jax.lax.top_k(a @ router, top_k)
    return idx, jax.nn.softmax(top, axis=-1)


@functools.partial(jax.jit, static_argnames=("quant",))
def _add_expert(out, h, idx, gates, w_in, w_out, expert, quant):
    gate = jnp.sum(jnp.where(idx == expert, gates, 0.0), axis=-1)
    up = _matmul(h, w_in, quant)
    half = up.shape[-1] // 2
    act = jax.nn.relu(up[:, :half]) * up[:, half:]
    return out + gate[:, None] * _matmul(act, w_out, quant)


def routed(h, idx, gates, w, quant: str = ""):
    """The routed sum over every expert: a loop over them, each over
    every token, weighted by the token's gate for it (zero where the
    token did not pick it)."""
    out = jnp.zeros_like(h)
    for e in range(w["experts_in"].shape[0]):
        out = _add_expert(out, h, idx, gates, w["experts_in"][e],
                          w["experts_out"][e], e, quant)
    return out


def layer(model: dict, x, w: dict, quant: str = "",
          router_after: bool = False):
    """One block over one sequence x [T, D]: ``(x, picks [T, k])``. Its
    parts are compiled one by one (the mixer, the router, one expert),
    each once for a sequence length. ``router_after`` is not this
    block: the router read off the feed-forward's own input, which a
    test must tell from the block's."""
    eps = model["norm_eps"]
    a = _rmsnorm(x, eps)
    window = model["attention_window"] if w["kind"] == "window" else 0
    x = x + attention(
        a, {"w_qkv": w["w_qkv"], "w_out": w["w_out"]},
        h=model["n_heads"], kv=model["n_kv_heads"], dh=model["head_dim"],
        window=window, theta=model["rope_theta"] if window else 0.0,
        quant=quant)
    h = _rmsnorm(x, eps)
    idx, gates = route(h if router_after else a, w["router"],
                       model["expert_top_k"])
    return x + routed(h, idx, gates, w, quant), idx


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
    ``picks``, a list, receives each layer's [sequences][T, k] picks.
    The layers run here; the head's product is made sequence by
    sequence as the result is read, once through (3,000 positions of
    151,936 ids are 1.8 GB on the host, and a check reads 16
    sequences)."""
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

    def read(x, start):
        with jax.default_matmul_precision("highest"):
            return _read(x, weights["head"], start, model["norm_eps"], quant)

    return map(read, xs, first)


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
    counters that reach here are totals (``decode_roofline_pct`` hands
    over mean rows and mean live positions); a count from the mean
    context, ``rows * min(live_tokens / rows, window)``, would overstate
    what short and long rows together hold."""
    window, seq = model["attention_window"], model["seq"]
    if not window or window >= seq:
        return live_tokens
    return live_tokens * window / seq


def decode_step(model: dict, rows: float, live_tokens: float) -> dict:
    """One decode step over ``rows`` sequences holding ``live_tokens``
    cached positions between them, by the equations: every layer's
    attention matrices, all its experts (a product over all of them
    reads them whatever the routing) and the head once in bf16, the
    router in float32, the full layers' keys and values of the live
    positions once, the window layers' of the least the rows' windows
    can hold (:func:`window_tokens`), and the rows' new keys and
    values. Operations: two a parameter a token passes, attention over
    the positions counted."""
    n_full, n_win = attention_layers(model)
    layers, d = model["n_layers"], model["d_model"]
    k, total = model["expert_top_k"], model["experts"]
    always = layers * attention_params(model) + model["vocab"] * d
    held = window_tokens(model, rows, live_tokens)
    positions = n_full * (live_tokens + rows) + n_win * (held + rows)
    nbytes = (BF16 * (always + layers * total * expert_params(model))
              + F32 * layers * d * total
              + kv_bytes_per_token(model) * positions)
    flops = (2.0 * rows * (always + layers * d * total
                           + layers * k * expert_params(model))
             + 4.0 * model["n_heads"] * model["head_dim"] * positions)
    return {"flops": flops, "bytes": nbytes}
