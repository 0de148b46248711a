"""The patterned block: recurrent mixers (Mamba-2, or a delta rule),
attention mixers and attention mixers bound to a window in a repeating
pattern of layers, every layer with routed and, where the model has
one, shared gated experts.

Written once, for the paged serving path (models/kvcache.py runs it
from ``_run_paged``); the trainer, the contiguous cache and a mesh of
several devices refuse a model with a ``layer_pattern``. The equations
(``e``, ``r``, ``s`` are ``cfg.embedding_multiplier``,
``residual_multiplier``, ``logits_scaling``; RMSNorm with a gain and
``cfg.norm_eps``):

    x = e * E[tokens]
    per layer:  a = norm(x);  x = x + r * Mixer(a);  h = norm(x)
                x = x + r * (Routed(h) + Shared(h))
    logits = norm(x) @ E.T / s          (``head.T`` for ``E.T`` where the
                                         tree has a head of its own)

With ``cfg.norm_after`` a sublayer's norm is on its output and its
input is the stream as it stands: ``x = x + r * norm(Mixer(x))``, ``x =
x + r * norm(Routed(x) + Shared(x))``. ``cfg.dense_layers`` leading
layers run before the periods, unscanned, through the same two
functions as a period's layers (:func:`run_layers`): their mixer is the
pattern's, continued backwards (``cfg.leading_kinds``), their
feed-forward a gated MLP of ``cfg.dense_ff`` with no router
(``params["leading"]`` and ``params["dense"]``, stacked over them).
With ``cfg.qk_norm`` an attention layer of either kind has gains
``q_norm`` and ``k_norm`` [Dh] for an RMSNorm of each head's q and k;
with ``cfg.router_score`` "sigmoid" the router is moe._route's sigmoid
form, ``router_bias`` [E] float32 its choice bias where the tree has
one.

``Mixer`` is the pattern's recurrent kind, models/ssm.py's Mamba-2
mixer ("mamba") or models/delta.py's delta rule ("delta"; a pattern
holds one of the two), or kvcache's paged attention (no rotary when
``cfg.rotary`` is false, scores scaled by ``cfg.attention_multiplier``,
``sigmoid(h W_gate)`` times what it attended where the layer has a
``w_gate``), or the same attention bound to a window ("window": rotary
always, base ``cfg.rope_theta``, query i over keys i -
``cfg.attention_window`` + 1 to i, keys and values in the window
layers' own pool; a pattern may hold both kinds, and need hold no
recurrent one); ``Routed`` is moe.held_experts_ffn over the experts
this device holds, its picks read off ``h`` or, with
``cfg.router_before_mixer``, off ``a``; ``Shared`` a gated MLP every
token passes (``cfg.shared_ff`` 0: none); the gate is SiLU, or ReLU
where ``cfg.ffn_activation`` says so.

**Weights** are a named tree per layer kind, every leaf stacked over
``[periods, layers of that kind in a period, ...]``:
``params["mamba"]`` or ``params["delta"]``, ``params["attention"]``,
``params["window"]``,
``params["ffn"]`` (one entry per layer of the period), beside
``embedding``, ``ln_final`` and, with ``cfg.untied_head``, ``head``.
The layer loop scans over periods and runs the period's layers in its
body, so one period's program is compiled whatever the depth; the body
slices each layer's leaves out of the stacked tree itself, by period
and place (``run_layers``).

**The initialiser draws leaf by leaf, layer by layer, in the serving
dtype** (:func:`init_params`): each leaf is one jitted call that maps
over its layers, each layer from its own key, so no float32 copy of
the tree, nor of one stacked leaf, ever stands on the device (the
float32 tree of the benchmark's configuration is 19 GB). The recipe,
which the benchmark's reference copies: every draw is float32 from
``fold_in(fold_in(PRNGKey(seed), leaf number), layer)``, an expert's
from that key folded with its global index; matrices are normal times
fan-in ** -0.5, the conv's bias normal times 0.02, gains one; the
embedding is normal times 0.02 / ``embedding_multiplier``, so that the
residual stream starts at the 0.02 it starts at in the plain block (at
0.02 itself the tied head's logit for the token just read stood 15
standard deviations above every other's, 12 * 0.02 * sqrt(4096), and
a greedy row repeated its prompt's last token for ever: nothing a
check of served tokens could tell one precision from another by); a
head of its own is normal times 0.02; for the SSM the Mamba-2 paper's
own: ``A`` uniform in [1, 16) (``A_log`` its log), ``dt`` log-uniform in
[0.001, 0.1) and ``dt_bias`` its inverse softplus, ``D`` one; a delta
layer's decay takes the same two draws, ``A_log`` a head and
``dt_bias`` a key channel.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from kvedge_tpu.models import delta, moe, ssm
from kvedge_tpu.models.moe import ffn_activation, held_experts_ffn
from kvedge_tpu.models.transformer import TransformerConfig, _rmsnorm

# Leaf numbers of the recipe: a leaf keeps its number when others are
# added after it, so a tree drawn today is drawn again tomorrow.
_LEAVES = {
    "embedding": 0,
    ("mamba", "w_in"): 1, ("mamba", "conv_w"): 2, ("mamba", "conv_b"): 3,
    ("mamba", "A_log"): 4, ("mamba", "dt_bias"): 5, ("mamba", "w_out"): 6,
    ("attention", "w_qkv"): 7, ("attention", "w_out"): 8,
    ("ffn", "router"): 9, ("ffn", "experts_in"): 10,
    ("ffn", "experts_out"): 11, ("ffn", "shared_in"): 12,
    ("ffn", "shared_out"): 13,
    ("delta", "w_qkv"): 14, ("delta", "conv_w"): 15, ("delta", "w_low"): 16,
    ("delta", "w_f2"): 17, ("delta", "w_g2"): 18, ("delta", "A_log"): 19,
    ("delta", "dt_bias"): 20, ("delta", "w_out"): 21,
    ("attention", "w_gate"): 22, "head": 23,
    ("window", "w_qkv"): 24, ("window", "w_out"): 25,
    ("dense", "w_in"): 26, ("dense", "w_out"): 27,
    ("ffn", "router_bias"): 28,
}

_KINDS = ("mamba", "delta", "attention", "window", "ffn")
# The leading dense layers' two trees, stacked over those layers alone.
_LEADING = ("leading", "dense")
# The kinds whose mixer is paged attention, each over a pool of its own.
ATTENTION_KINDS = ("attention", "window")
# A recurrent kind's mixer, its answer to whether a trace's one-token
# form is its kernel, and the scope that names it in a trace.
_MIXERS = {"mamba": (ssm.mamba_mixer, ssm.step_in_kernel, "kvedge/ssm"),
           "delta": (delta.delta_mixer, delta.step_in_kernel,
                     "kvedge/delta")}


def layers_of(cfg: TransformerConfig, kind: str) -> list[int]:
    """Global indices of the periods' layers of ``kind`` ("ffn": every
    one of them; "leading", "dense": the layers before them)."""
    period, lead = len(cfg.layer_pattern), cfg.dense_layers
    if kind in ("leading", "dense"):
        return list(range(lead))
    return [i for i in range(lead, cfg.n_layers)
            if kind == "ffn" or cfg.layer_pattern[(i - lead) % period] == kind]


def _normal(scale):
    return lambda key, shape: jax.random.normal(key, shape,
                                                jnp.float32) * scale


def _recipes(cfg: TransformerConfig) -> dict:
    """(kind, leaf) -> (one layer's shape, draw(key, shape) -> float32,
    whether the leading dimension is one key per held expert)."""
    d, f, sf = cfg.d_model, cfg.d_ff, cfg.shared_ff
    h, kv, dh = cfg.n_heads, cfg.kv_heads, cfg.d_head
    heads, n, k = cfg.ssm_heads, cfg.ssm_state, cfg.ssm_conv
    inner, conv_dim = cfg.ssm_inner, cfg.ssm_conv_dim
    keys, rank = heads * n, cfg.ssm_gate_rank
    gate = 2 if cfg.ffn_gated else 1

    def a_log(key, shape):
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0,
                                          16.0))

    def dt_bias(key, shape):
        dt = jnp.exp(jax.random.uniform(
            key, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))  # softplus(dt_bias) == dt

    out = {}
    if "mamba" in cfg.layer_pattern:
        out.update({
            ("mamba", "w_in"): ((d, 2 * inner + 2 * n + heads),
                                _normal(d ** -0.5), False),
            ("mamba", "conv_w"): ((k, conv_dim), _normal(k ** -0.5), False),
            ("mamba", "conv_b"): ((conv_dim,), _normal(0.02), False),
            ("mamba", "A_log"): ((heads,), a_log, False),
            ("mamba", "dt_bias"): ((heads,), dt_bias, False),
            ("mamba", "w_out"): ((inner, d), _normal(inner ** -0.5), False),
        })
    if "delta" in cfg.layer_pattern:
        out.update({
            ("delta", "w_qkv"): ((d, 2 * keys + inner), _normal(d ** -0.5),
                                 False),
            ("delta", "conv_w"): ((k, delta.conv_dim(cfg)),
                                  _normal(k ** -0.5), False),
            ("delta", "w_low"): ((d, 2 * rank + heads), _normal(d ** -0.5),
                                 False),
            ("delta", "w_f2"): ((rank, keys), _normal(rank ** -0.5), False),
            ("delta", "w_g2"): ((rank, inner), _normal(rank ** -0.5), False),
            ("delta", "A_log"): ((heads,), a_log, False),
            ("delta", "dt_bias"): ((keys,), dt_bias, False),
            ("delta", "w_out"): ((inner, d), _normal(inner ** -0.5), False),
        })
    if "attention" in cfg.layer_pattern:
        out.update({
            ("attention", "w_qkv"): ((d, (h + 2 * kv) * dh),
                                     _normal(d ** -0.5), False),
            ("attention", "w_out"): ((h * dh, d),
                                     _normal((h * dh) ** -0.5), False),
        })
        if cfg.attention_gate:
            out[("attention", "w_gate")] = (
                (d, h * dh), _normal(d ** -0.5), False)
    if "window" in cfg.layer_pattern:
        out.update({
            ("window", "w_qkv"): ((d, (h + 2 * kv) * dh),
                                  _normal(d ** -0.5), False),
            ("window", "w_out"): ((h * dh, d),
                                  _normal((h * dh) ** -0.5), False),
        })
    if cfg.dense_layers:
        kind, df = cfg.leading_kinds[0], cfg.dense_ff
        out.update({
            # A leading layer's mixer draws as its kind's does, from
            # the kind's leaf numbers and its own layer index.
            ("leading", leaf): out[kind, leaf]
            for leaf in ("w_qkv", "w_out", "w_gate") if (kind, leaf) in out})
        out.update({
            ("dense", "w_in"): ((d, 2 * df), _normal(d ** -0.5), False),
            ("dense", "w_out"): ((df, d), _normal(df ** -0.5), False),
        })
    if cfg.router_bias:
        # Small against the scores' spread (a sigmoid of logits of the
        # stream's scale), large against the gaps between neighbouring
        # scores near the top: it changes picks (tests/test_exaone_block.py
        # counts them).
        out[("ffn", "router_bias")] = ((cfg.n_experts,), _normal(0.01),
                                       False)
    out.update({
        ("ffn", "router"): ((d, cfg.n_experts), _normal(d ** -0.5), False),
        ("ffn", "experts_in"): ((cfg.held_experts, d, gate * f),
                                _normal(d ** -0.5), True),
        ("ffn", "experts_out"): ((cfg.held_experts, f, d),
                                 _normal(f ** -0.5), True),
    })
    if sf:
        out.update({
            ("ffn", "shared_in"): ((d, gate * sf), _normal(d ** -0.5),
                                   False),
            ("ffn", "shared_out"): ((sf, d), _normal(sf ** -0.5), False),
        })
    return out


# Leaves the equations read in float32 (transformer.serving_params
# names the ones held in the compute dtype).
_FLOAT32 = frozenset({"router", "router_bias", "A_log", "dt_bias"})


def init_params(key, cfg: TransformerConfig) -> dict:
    """The patterned block's tree, each leaf in the dtype serving reads
    it in (module docstring). ``key`` is ``PRNGKey(seed)``."""
    cfg.validate()
    if not cfg.layer_pattern:
        raise ValueError("hybrid.init_params draws a patterned block's "
                         "tree: cfg.layer_pattern is empty")
    dtype = jnp.dtype(cfg.dtype)
    periods = cfg.periods
    first = cfg.expert_first

    def stacked_over(kind):
        """The dimensions a kind's leaves are stacked over."""
        n = len(layers_of(cfg, kind))
        return (n,) if kind in _LEADING else (periods, n // periods)

    def stacked(kind, leaf, shape, draw, per_expert):
        drawn_as = cfg.leading_kinds[0] if kind == "leading" else kind
        leaf_key = jax.random.fold_in(key, _LEAVES[drawn_as, leaf])
        layers = jnp.asarray(layers_of(cfg, kind), jnp.int32)
        to = jnp.float32 if leaf in _FLOAT32 else dtype

        def one_layer(layer):
            k = jax.random.fold_in(leaf_key, layer)
            if per_expert:
                ks = jax.vmap(lambda e: jax.random.fold_in(k, e))(
                    first + jnp.arange(shape[0]))
                return jax.vmap(lambda ke: draw(ke, shape[1:]))(ks).astype(to)
            return draw(k, shape).astype(to)

        flat = jax.jit(lambda ls: lax.map(one_layer, ls))(layers)
        return jax.block_until_ready(
            flat.reshape(*stacked_over(kind), *shape))

    params: dict = {kind: {} for kind in _KINDS + _LEADING}
    for (kind, leaf), recipe in _recipes(cfg).items():
        params[kind][leaf] = stacked(kind, leaf, *recipe)

    def ones(kind, leaf, width):
        params[kind][leaf] = jnp.ones((*stacked_over(kind), width), jnp.float32)

    if params["mamba"]:
        ones("mamba", "D", cfg.ssm_heads)
        ones("mamba", "norm", cfg.ssm_inner)
        ones("mamba", "ln", cfg.d_model)
    if params["delta"]:
        ones("delta", "norm", cfg.ssm_head_dim)
        ones("delta", "ln", cfg.d_model)
    for kind in ATTENTION_KINDS + ("leading",):
        if params[kind]:
            ones(kind, "ln", cfg.d_model)
            if cfg.qk_norm:
                ones(kind, "q_norm", cfg.d_head)
                ones(kind, "k_norm", cfg.d_head)
    ones("ffn", "ln", cfg.d_model)
    if cfg.dense_layers:
        ones("dense", "ln", cfg.d_model)
    def table(leaf, scale):
        return jax.jit(
            lambda k: (jax.random.normal(k, (cfg.vocab, cfg.d_model),
                                         jnp.float32) * scale).astype(dtype)
        )(jax.random.fold_in(key, _LEAVES[leaf]))

    params["embedding"] = table("embedding",
                                0.02 / cfg.embedding_multiplier)
    if cfg.untied_head:
        params["head"] = table("head", 0.02)
    params["ln_final"] = jnp.ones((cfg.d_model,), jnp.float32)
    return {name: leaf for name, leaf in params.items() if len(leaf)}


def _shared_expert(cfg: TransformerConfig, h, w_in, w_out):
    act = ffn_activation(h @ w_in.astype(h.dtype), cfg.ffn_gated,
                         cfg.ffn_activation)
    return act @ w_out.astype(h.dtype)


def walks_touched(cfg: TransformerConfig, n_tokens: int) -> bool:
    """Whether a program of ``n_tokens`` tokens a layer (a decode
    batch's rows, a prefill chunk's positions) reads only the held
    experts a live token picked (``moe.walks_touched`` at this block's
    sizes): what :func:`run_layers` asks, and the server's count of the
    matrices its windows read."""
    return moe.walks_touched(n_tokens, cfg.expert_top_k, cfg.n_experts,
                             cfg.held_experts, cfg.d_model, cfg.d_ff)


def expert_reads_per_step(cfg: TransformerConfig) -> int:
    """The held experts' matrices of every routed layer: what a step
    reads that reads them all."""
    return (cfg.n_layers - cfg.dense_layers) * cfg.held_experts


def feed_forward(cfg: TransformerConfig, x, w: dict, live, routed_on=None,
                 layer=None):
    """``x + r * (Routed(norm(x)) + Shared(norm(x)))`` over x [R, Q, D]
    and the picks of the ``live`` rows' tokens (held_experts_ffn).
    ``routed_on`` [R, Q, D], where given, is what the router reads
    (``cfg.router_before_mixer``: the mixer's normed input). A tree
    with no ``router`` is a leading dense layer's: one gated MLP, and
    no picks (None). ``cfg.norm_after``: the norm is on the sum, not on
    ``x``. With ``layer`` given the tree's ``experts_in`` and
    ``experts_out`` are the stacked leaves, [layers, Eh, ...], and the
    layer's experts are at ``layer`` of them (:func:`walks_touched`)."""
    rows, q_len, d = x.shape
    routed = "router" in w
    with jax.named_scope("kvedge/experts" if routed else "kvedge/dense"):
        h = (x if cfg.norm_after
             else _rmsnorm(x, w["ln"], cfg.norm_eps)).reshape(rows * q_len, d)
        if routed:
            out, picks = held_experts_ffn(
                h, w["router"], w["experts_in"], w["experts_out"],
                top_k=cfg.expert_top_k, first=cfg.expert_first,
                gated=cfg.ffn_gated, renormalize=True,
                live=None if live is None else jnp.repeat(live, q_len),
                activation=cfg.ffn_activation,
                routed_on=(None if routed_on is None
                           else routed_on.reshape(rows * q_len, d)),
                score=cfg.router_score, bias=w.get("router_bias"),
                scale=cfg.router_scale, layer=layer)
            if "shared_in" in w:
                out = out + _shared_expert(cfg, h, w["shared_in"],
                                           w["shared_out"])
        else:
            out, picks = _shared_expert(cfg, h, w["w_in"], w["w_out"]), None
        if cfg.norm_after:
            out = _rmsnorm(out, w["ln"], cfg.norm_eps)
        r = jnp.asarray(cfg.residual_multiplier, x.dtype)
        return x + r * out.reshape(rows, q_len, d), picks


def run_layers(cfg: TransformerConfig, params: dict, x, pools, recurrent,
               attend, slot, live):
    """The layer loop of a patterned block: a scan over periods, the
    period's layers in its body. ``pools`` (kind -> that kind's page
    pool, a 4-tuple, for each of :data:`ATTENTION_KINDS` the pattern
    holds) and ``recurrent`` (``ssm`` [recurrent layers, slots, a
    layer's state: :func:`state_shape`], ``conv`` [recurrent layers,
    slots, (K-1)*C], both absent where the pattern has no recurrent
    kind, and ``picks``) ride the carry whole and
    are updated in place. ``attend(h, w, layer, pool, kind) -> (out,
    pool)`` is the caller's paged attention over normed activations,
    ``layer`` its index into ``kind``'s pool. ``x``'s rows are the first R slots, or,
    with ``slot`` given, that one prefilling slot; ``live`` [R] says
    which of them advance (None = all). Returns
    ``(x, pools, recurrent)``.
    """
    pattern = cfg.layer_pattern
    n_of = {kind: pattern.count(kind) for kind in ATTENTION_KINDS}
    # A kind's leading layers hold the first places of its pool.
    n_lead = {kind: cfg.leading_kinds.count(kind) for kind in ATTENTION_KINDS}
    n_recurrent = len(pattern) - sum(n_of.values())
    r = jnp.asarray(cfg.residual_multiplier, x.dtype)

    if slot is None:
        n_rows = x.shape[0]

        def take(state, layer):
            return state[layer, :n_rows]

        def put(state, layer, new):
            return state.at[layer, :n_rows].set(new)
    else:
        def take(state, layer):
            return state[layer, slot][None]

        def put(state, layer, new):
            return state.at[layer, slot].set(new[0])

    def at(tree, period, i):
        """Layer ``i`` of period ``period`` of every stacked leaf, sliced
        where it is used. As the scan's ``xs`` a whole period's leaves
        were sliced out at the top of the body, and with more periods
        than one the chip's compiler made that slice a copy: 1.9 GB of
        expert matrices a period and step at 64 experts of 2,560 x
        1,536 in 4 layers (tests/test_chip_compile.py holds the
        window's temporaries under a layer of a pool)."""
        return jax.tree_util.tree_map(
            lambda a: lax.dynamic_slice(
                a, (period, i) + (0,) * (a.ndim - 2),
                (1, 1) + a.shape[2:]).reshape(a.shape[2:]), tree)

    def ffn_at(period, i):
        """Layer ``i`` of period ``period`` of the feed-forward's tree
        and, where the experts' sum walks the touched experts, the
        layer's index into the experts' two leaves, which then stay
        whole, their layers in one leading dimension: the kernel
        reads the layer's touched experts where they lie."""
        tree = weights["ffn"]
        if not walks:
            return at(tree, period, i), None
        whole = {name: tree[name].reshape((-1,) + tree[name].shape[2:])
                 for name in ("experts_in", "experts_out")}
        rest = {name: a for name, a in tree.items() if name not in whole}
        return {**at(rest, period, i), **whole}, period * len(pattern) + i

    def one_layer(carry, kind, w, ffn_of, layer):
        """A layer of ``kind``, ``layer`` its place among the layers
        that share its pool or state: mixer, then feed-forward over
        the tree ``ffn_of()`` slices out (where it is used, as
        :func:`at` says) and the index it gives beside it."""
        x, pools, state, conv, picks = carry
        h = x if cfg.norm_after else _rmsnorm(x, w["ln"], cfg.norm_eps)
        if kind in ATTENTION_KINDS:
            with jax.named_scope("kvedge/" + kind):
                out, pool = attend(h, w, layer, pools[kind], kind)
            pools = {**pools, kind: pool}
        else:
            mixer, in_kernel, scope = _MIXERS[kind]
            with jax.named_scope(scope):
                if in_kernel(cfg, slot, x.shape[1]):
                    # A decode step on the chip: the mixer's kernel
                    # works on the stacked state where it lies, so
                    # there is nothing to take or put.
                    out, state, new_tail = mixer(
                        cfg, h, w, state, take(conv, layer), live,
                        layer=layer)
                else:
                    out, new_state, new_tail = mixer(
                        cfg, h, w, take(state, layer),
                        take(conv, layer), live)
                    state = put(state, layer, new_state)
                conv = put(conv, layer, new_tail)
        if cfg.norm_after:
            out = _rmsnorm(out, w["ln"], cfg.norm_eps)
        x = x + r * out
        ffn, ffn_layer = ffn_of()
        x, layer_picks = feed_forward(
            cfg, x, ffn, live, h if cfg.router_before_mixer else None,
            ffn_layer)
        if layer_picks is not None:
            picks = picks + layer_picks
        return x, pools, state, conv, picks

    def body(carry, period):
        seen = dict.fromkeys(pattern, 0)
        for j, kind in enumerate(pattern):
            carry = one_layer(
                carry, kind, at(weights[kind], period, seen[kind]),
                functools.partial(ffn_at, period, j),
                (period * n_of[kind] + (n_lead[kind] + seen[kind])
                 if kind in ATTENTION_KINDS
                 else period * n_recurrent + seen[kind]))
            seen[kind] += 1
        return carry, None

    weights = {kind: params.get(kind, {}) for kind in _KINDS}
    walks = walks_touched(cfg, x.shape[0] * x.shape[1])
    carry = (x, pools, recurrent.get("ssm"), recurrent.get("conv"),
             recurrent["picks"])
    for i, kind in enumerate(cfg.leading_kinds):
        carry = one_layer(
            carry, kind,
            jax.tree_util.tree_map(lambda a: a[i], params["leading"]),
            lambda: (jax.tree_util.tree_map(lambda a: a[i],
                                            params["dense"]), None),
            i)
    (x, pools, state, conv, picks), _ = lax.scan(
        body, carry, jnp.arange(cfg.periods, dtype=jnp.int32))
    if state is None:
        return x, pools, {"picks": picks}
    return x, pools, {"ssm": state, "conv": conv, "picks": picks}


def state_shape(cfg: TransformerConfig) -> tuple:
    """The shape of one layer's recurrent state for one slot, and the
    channels its conv runs over, by the pattern's recurrent kind: a
    mamba layer's ``[H * P, N]`` or a delta layer's ``[H, dk, dv]``."""
    if cfg.recurrent_kind == "delta":
        return ((cfg.ssm_heads, cfg.ssm_state, cfg.ssm_head_dim),
                delta.conv_dim(cfg))
    return (cfg.ssm_inner, cfg.ssm_state), cfg.ssm_conv_dim


def fresh_recurrent(cfg: TransformerConfig, slots: int) -> dict:
    """Zeroed recurrent state for ``slots`` rows: the float32 state of
    the pattern's recurrent kind (:func:`state_shape`), the conv's tail
    in the compute dtype, flat so that its minor dimension is whole
    lanes, and the window's pick counters (moe.held_experts_ffn); the
    counters alone where the pattern has no recurrent kind."""
    picks = jnp.zeros((3 + cfg.held_experts,), jnp.int32)
    if not cfg.recurrent_kind:
        return {"picks": picks}
    layers = cfg.ssm_layers
    shape, channels = state_shape(cfg)
    return {
        "ssm": jnp.zeros((layers, slots, *shape), jnp.float32),
        "conv": jnp.zeros((layers, slots, (cfg.ssm_conv - 1) * channels),
                          jnp.dtype(cfg.dtype)),
        "picks": picks,
    }


@functools.partial(jax.jit, donate_argnums=(0,))
def reset_rows(recurrent: dict, slot):
    """Zero slot ``slot``'s state in every layer (admission)."""
    return {
        "ssm": recurrent["ssm"].at[:, slot].set(0.0),
        "conv": recurrent["conv"].at[:, slot].set(0),
        "picks": recurrent["picks"],
    }


@jax.jit
def gather_rows(recurrent: dict, slot):
    """Fresh copies of slot ``slot``'s state, as stored."""
    return recurrent["ssm"][:, slot], recurrent["conv"][:, slot]


@functools.partial(jax.jit, donate_argnums=(0,))
def scatter_rows(recurrent: dict, slot, ssm, conv):
    """Write :func:`gather_rows`' arrays back into slot ``slot``."""
    return {
        "ssm": recurrent["ssm"].at[:, slot].set(ssm),
        "conv": recurrent["conv"].at[:, slot].set(conv),
        "picks": recurrent["picks"],
    }
