"""Mixture-of-experts FFN: expert parallelism over an ``expert`` mesh axis.

The third payload scale-out dimension alongside ``model`` (tensor) and
``seq`` (sequence) — the reference has no parallelism of any kind
(SURVEY.md §5); this exists because MoE is how a TPU-native payload
scales parameter count past one chip's HBM without scaling per-token
FLOPs.

TPU-first design decisions:

* **Top-k routing (k = 1 Switch, k = 2 GShard) with a static capacity.**
  Every shape is compile-time constant: each expert processes exactly
  ``C = ceil(k * tokens/E * capacity_factor)`` slots, and dispatches
  routed past an expert's capacity are *dropped* (their FFN contribution
  is zero and the residual connection carries them through — the
  standard trade that keeps XLA shapes static instead of introducing
  data-dependent gather/scatter). First choices take capacity priority
  over second choices; top-1 gates with the raw router probability,
  top-2 normalizes the pair.
* **Dispatch and combine are einsums with one-hot tensors**, not
  scatters: ``[N, E, C]`` dispatch against ``[N, D]`` activations gives
  ``[E, C, D]`` expert inputs on the MXU, and the transpose einsum
  combines outputs back. XLA partitions these einsums over the mesh.
* **Sharding is annotation-only**, like the rest of the package: expert
  weights are stacked on a leading ``E`` axis sharded over the
  ``expert`` mesh axis (parallel/sharding.py), activations get a
  ``with_sharding_constraint`` pinning the ``E`` dim of the dispatched
  block — XLA's SPMD partitioner inserts the all-to-alls. No shard_map.
* **Router math in fp32** (softmax over expert logits is tiny but
  numerically load-bearing); expert FFN matmuls in the model's compute
  dtype (bf16 on TPU).

The router's load-balancing aux loss (Switch eq. 4 over *first* choices:
``E * Σ_e f_e·P_e``, minimized at 1.0 when routing is uniform) is
returned alongside the output and folded into the training loss by
``loss_fn`` — without it, learned routing collapses onto a few experts.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from kvedge_tpu.ops import expert_walk


def warn_if_train_serve_divergence(cfg) -> None:
    """Warn when cached serving can silently disagree with training.

    The serving paths route droplessly; training drops dispatches past
    capacity. Per-expert demand is at most ``n_tokens`` (a token's top-k
    choices are distinct experts) and capacity is
    ``ceil(top_k * n_tokens * factor / E)``, so
    ``expert_capacity_factor * expert_top_k >= n_experts`` guarantees
    zero training drops (the two paths then compute the same function);
    below that, an operator who trains with drops and serves dropless
    diverges *silently* — hence a loud warning at the serving boundary
    (cache construction), where the pairing actually happens. Training
    alone with a binding capacity is a deliberate, standard trade and
    stays silent.
    """
    import warnings

    if cfg.layer_pattern:
        return  # served only: no trainer whose capacity could bind
    if (cfg.n_experts
            and cfg.expert_capacity_factor * cfg.expert_top_k
            < cfg.n_experts):
        warnings.warn(
            f"MoE serving with expert_capacity_factor="
            f"{cfg.expert_capacity_factor} * expert_top_k="
            f"{cfg.expert_top_k} < n_experts={cfg.n_experts}: training "
            "may have dropped dispatches that dropless serving will "
            "route, so cached decode can disagree with the "
            "teacher-forced forward pass. Train with "
            "expert_capacity_factor >= n_experts / expert_top_k for "
            "exact train/serve agreement (models/moe.py).",
            RuntimeWarning, stacklevel=3,
        )


def expert_capacity(n_tokens: int, n_experts: int,
                    capacity_factor: float) -> int:
    """Per-expert slot count: ceil(tokens/E * factor), at least 1."""
    import math

    return max(1, math.ceil(n_tokens * capacity_factor / n_experts))


def _route(x, router_w, top_k: int, renormalize: bool | None = None,
           score: str = "softmax", bias=None, scale: float = 1.0):
    """Shared routing decision. Returns (probs [N, E], idx [N, k],
    gates [N, k] fp32).

    Gate convention follows the source papers: top-1 uses the raw router
    probability (Switch); top-2 normalizes the pair to sum to 1 (GShard).
    ``renormalize`` says it outright (None = as above): true makes the
    gates a softmax over the ``top_k`` picked logits, which is the
    picked probabilities normalized, for any ``top_k``.
    Both training dispatch and the dropless serving path call this, so
    the two cannot disagree about gating. The logits are a float32
    product in full: a token's picks are read off them, and the
    device's one-pass float32 product moves near-ties.

    ``score`` "sigmoid" is the router that scores each expert alone:
    ``s = sigmoid(logits)``, the picks the ``top_k`` largest of ``s +
    bias`` (``bias`` [E] float32, where the tree has one: it moves the
    choice and never a gate), the gates the picked ``s`` over their sum
    times ``scale``.
    """
    logits = jnp.dot(x.astype(jnp.float32), router_w.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    if score == "sigmoid":
        scores = jax.nn.sigmoid(logits)                     # [N, E]
        _, topk_idx = lax.top_k(
            scores if bias is None else scores + bias, top_k)
        picked = jnp.take_along_axis(scores, topk_idx, axis=-1)
        gates = picked / jnp.sum(picked, axis=-1, keepdims=True) * scale
        return scores, topk_idx, gates
    probs = jax.nn.softmax(logits, axis=-1)                 # [N, E]
    topk_probs, topk_idx = lax.top_k(probs, top_k)          # [N, k]
    if top_k > 1 if renormalize is None else renormalize:
        gates = topk_probs / jnp.sum(topk_probs, axis=-1, keepdims=True)
    else:
        gates = topk_probs
    return probs, topk_idx, gates


def moe_ffn(x, router_w, w_up, w_down, *, capacity_factor: float,
            top_k: int = 1, mesh=None, expert_axis: str = "expert"):
    """Top-k (k = 1 or 2) MoE feed-forward. x: [N, D] tokens.

    router_w: [D, E] fp32; w_up: [E, D, F]; w_down: [E, F, D] (compute
    dtype). Returns ``(out [N, D], aux_loss scalar fp32)``.

    Top-2: each token dispatches to its two highest-probability experts
    with gates normalized over the pair (GShard). Capacity accounting
    gives first choices strict priority — every token's first choice
    claims its expert slot before any second choice does — and capacity
    itself scales with k (k dispatches per token).
    """
    n_tokens, d = x.shape
    n_experts = router_w.shape[-1]
    capacity = expert_capacity(top_k * n_tokens, n_experts, capacity_factor)

    probs, topk_idx, gates = _route(x, router_w, top_k)
    onehots = jax.nn.one_hot(topk_idx, n_experts,
                             dtype=jnp.float32)             # [N, k, E]

    # Flatten (choice, token) with all FIRST choices before any second
    # choice, so the cumsum-based capacity positions give first choices
    # strict priority. Each flat row then routes independently, exactly
    # like the top-1 scheme.
    flat = onehots.transpose(1, 0, 2).reshape(
        top_k * n_tokens, n_experts
    )                                                        # [kN, E]
    position = jnp.cumsum(flat, axis=0) * flat - 1.0
    within = (position < capacity) & (position >= 0)
    dispatch = jnp.where(within, flat, 0.0)                 # [kN, E]
    # Each kept row's slot index (dropped rows contribute a zero row in
    # dispatch_ohc regardless of the slot value picked here).
    slot_index = jnp.sum(position * dispatch, axis=-1).astype(jnp.int32)
    slot = jax.nn.one_hot(slot_index, capacity, dtype=jnp.float32)
    dispatch_ohc = dispatch[:, :, None] * slot[:, None, :]  # [kN, E, C]

    # Aux load-balancing loss over the *pre-capacity* FIRST-choice
    # routing (Switch Transformer eq. 4): minimized at 1.0 when uniform.
    fraction = jnp.mean(onehots[:, 0, :], axis=0)           # [E]
    mean_prob = jnp.mean(probs, axis=0)                     # [E]
    aux_loss = n_experts * jnp.sum(fraction * mean_prob)

    # Merge the k choices back to per-token dispatch/combine tensors
    # before the big einsums: a token's choices route to *distinct*
    # experts and every kept dispatch owns a unique (expert, slot), so
    # the per-choice one-hots never overlap and summing them is exact —
    # and the dispatch/combine einsums then run over N rows, not kN.
    dispatch_tok = dispatch_ohc.reshape(
        top_k, n_tokens, n_experts, capacity
    )                                                        # [k, N, E, C]
    gates_flat = gates.transpose(1, 0).reshape(top_k * n_tokens)
    combine_tok = (
        dispatch_ohc * gates_flat[:, None, None]
    ).reshape(top_k, n_tokens, n_experts, capacity)

    dtype = x.dtype
    expert_in = jnp.einsum(
        "nec,nd->ecd", dispatch_tok.sum(axis=0).astype(dtype), x
    )                                                        # [E, C, D]
    if mesh is not None and expert_axis in mesh.axis_names:
        constrain = NamedSharding(mesh, P(expert_axis, None, None))
        expert_in = lax.with_sharding_constraint(expert_in, constrain)
    hidden = jax.nn.gelu(
        jnp.einsum("ecd,edf->ecf", expert_in, w_up.astype(dtype))
    )
    expert_out = jnp.einsum("ecf,efd->ecd", hidden, w_down.astype(dtype))
    if mesh is not None and expert_axis in mesh.axis_names:
        expert_out = lax.with_sharding_constraint(expert_out, constrain)

    combine = combine_tok.sum(axis=0).astype(dtype)          # [N, E, C]
    out = jnp.einsum("nec,ecd->nd", combine, expert_out)     # [N, D]
    return out, aux_loss


# Tokens up to which the held experts' first product is one product over
# all of them (held_experts_ffn): a decode batch, a 64-token chunk. At 64
# tokens it reads 0 to 1.3% under the batch of products on the chip, at
# 256 1.5% over, and inside a 256-token chunk's program it does not fit
# (tools/expert_product_readings.py; PERF.md section 6, PR 40).
_ONE_PRODUCT_TOKENS = 64
# The share of the experts that must go untouched, under even routing,
# before the walk over the touched ones (ops/expert_walk.py) is taken
# for the one product. With every held expert on its list the walk
# reads within a hundredth of the one product at 64 tokens on the chip,
# us a layer, the one product first: 1,937 and 1,905 to 1,935 at 40
# experts of 4,096 x 1,280; 1,011 and 1,013 at 36 of 4,096 x 768; 1,134
# and 1,150 (1.4% over) at 64 of 2,560 x 768; 1,968 and 1,853 (6%
# under) at 16 of 6,144 x 2,048; and with a third off the list 1,296 to
# 1,313, 719, 809 and 1,329 (tools/expert_product_readings.py; PERF.md
# section 5, PR 45). So the kernel's stream costs 1.4% at most, and
# twice that untouched pays for it at every shape read.
_WALK_UNTOUCHED = 0.03


def _on_tpu() -> bool:
    """Apart from ``ops.pallas_interpret``, as ``ssm._on_tpu`` is, so
    that a CPU test can take the walk and run it in the interpreter."""
    return jax.default_backend() == "tpu"


def walks_touched(n_tokens: int, top_k: int, experts: int, held: int,
                  d: int, f: int) -> bool:
    """Whether this trace's held experts' feed-forward is the walk over
    the touched experts (ops/expert_walk.py) and not the one product
    over all ``held`` of them: ``n_tokens`` tokens of width ``d``, each
    picking ``top_k`` of ``experts`` experts ``f`` wide. On a TPU
    backend, at sizes the kernel tiles, at a decode batch's or a short
    chunk's tokens, and where so few picks fall on so many experts that
    leaving the untouched unread pays for what the kernel's stream may
    cost (``_WALK_UNTOUCHED``):
    under even routing an expert goes untouched with probability ``(1 -
    top_k / experts) ** n_tokens``, whichever of the experts are held.
    Decided from what the trace can see; there is no option."""
    return (_on_tpu() and held > 0 and n_tokens <= _ONE_PRODUCT_TOKENS
            and expert_walk.tiles(n_tokens, d, f)
            and (1 - top_k / experts) ** n_tokens > _WALK_UNTOUCHED)


def ffn_activation(up, gated: bool, gate: str = "silu"):
    """A feed-forward's hidden activation from its up-projection:
    ``gate(u) * g`` over ``up = u | g`` when ``gated`` (``gate`` names
    the function: "silu", or "relu" for a ReGLU), else ``gelu(up)``."""
    if not gated:
        return expert_walk.hidden(up)
    half = up.shape[-1] // 2
    return expert_walk.hidden(up[..., :half], up[..., half:], gate)


def held_experts_ffn(x, router_w, w_in, w_out, *, top_k: int,
                     first: int = 0, gated: bool = False,
                     renormalize: bool | None = None, live=None,
                     activation: str = "silu", routed_on=None,
                     score: str = "softmax", bias=None,
                     scale: float = 1.0, layer=None):
    """The routed experts held here, for every token: the serving path.

    x: [N, D]; router_w [D, E] fp32 over ALL ``E`` routed experts;
    w_in [Eh, D, F] (``[Eh, D, 2F]`` when ``gated``: u | g, the expert
    is ``(activation(u) * g) @ w_out``, :func:`ffn_activation`; ungated it is
    ``gelu(x @ w_in) @ w_out``) and w_out [Eh, F, D] are the ``Eh``
    experts this device holds, global indices ``first`` to ``first +
    Eh - 1``. ``routed_on`` [N, D], where given, is what the router
    reads in ``x``'s place (a block whose router sits before the mixer
    hands the mixer's normed input; the experts still take ``x``).
    ``score``, ``bias`` and ``scale`` are the router's (:func:`_route`).
    Each token
    routes over all ``E`` (:func:`_route`), droplessly, and the result
    is the part of its gated sum that the held experts give: with
    ``Eh == E`` the whole layer, on a chip that shares the layer the
    half that its all-reduce would add to the other chip's. Returns
    ``(out [N, D], picks int32 [3 + Eh])``: of the ``live`` tokens'
    picks (all, when ``live`` is None) how many there were, how many
    fell on a held expert, the count for each held expert and, last,
    how many held experts got one or more (with many small experts and
    few tokens some get none, and the product below reads them all the
    same: summed over layers and steps this is what a product that
    skipped the untouched would still have to read).

    One product over all held experts and all tokens: every expert
    matrix is read once, whatever the routing, and a token's gate for
    an expert it did not pick is zero. At decode and at a prefill
    chunk the tokens are few (a batch, 64 positions) and the weights
    are what the step reads, so the work not needed (Eh / top_k times
    the operations) costs less than its bytes. The per-token gather of
    ``w_in[idx]`` this replaced copied a token's matrices out for each
    of its picks: ``N * top_k`` matrices a layer where this reads
    ``Eh`` (PERF.md section 6 has both timed at the benchmark's
    widths).

    With ``layer`` given (:func:`walks_touched` said so), ``w_in`` and
    ``w_out`` are the stacked leaves whole, [layers, Eh, ...], the
    layer's experts are at ``layer`` of them, and the sum is the walk
    over the experts a ``live`` token picked (ops/expert_walk.py): the
    others, whose every live gate is zero, are not read. A token that
    is not live gets the touched experts' part of its sum, which
    nothing reads.
    """
    with jax.named_scope("kvedge/router"):
        _, topk_idx, gates = _route(
            x if routed_on is None else routed_on, router_w, top_k,
            renormalize, score, bias, scale)
    held = w_out.shape[-3]
    dtype = x.dtype
    # [N, k, Eh]: pick j of token n is held expert e.
    hit = (topk_idx[:, :, None] - first
           == jnp.arange(held, dtype=topk_idx.dtype)[None, None, :])
    gate = jnp.sum(jnp.where(hit, gates[:, :, None], 0.0), axis=1)  # [N, Eh]
    counted = hit if live is None else hit & live[:, None, None]
    by_expert = jnp.sum(counted, axis=(0, 1), dtype=jnp.int32)
    n_live = (x.shape[0] if live is None
              else jnp.sum(live, dtype=jnp.int32))
    picks = jnp.concatenate([
        jnp.stack([jnp.asarray(n_live * top_k, jnp.int32),
                   jnp.sum(by_expert)]), by_expert,
        jnp.sum(by_expert > 0, dtype=jnp.int32)[None]])
    if layer is not None:
        from kvedge_tpu.ops import pallas_interpret

        out = expert_walk.expert_walk(
            x, gate, w_in, w_out, layer, by_expert > 0, gated=gated,
            gate=activation, interpret=pallas_interpret())
        return out.astype(dtype), picks
    if x.shape[0] > _ONE_PRODUCT_TOKENS:
        # A prefill chunk of more tokens: as one product with the tokens
        # shared by every expert, the chip's compiler, inside the
        # chunk's program, wants the experts' matrices transposed and
        # copies the whole stacked leaf to get them (3.75 GB at 64
        # experts of 2,560 x 1,536 in 8 layers: 15.82 GB where the chip
        # has 15.75; tests/test_chip_compile.py). With the tokens stated
        # once an expert it is a batch of plain products over the
        # matrices as they lie.
        up = jnp.einsum("end,edf->enf",
                        jnp.broadcast_to(x, (held,) + x.shape),
                        w_in.astype(dtype))
    else:
        up = jnp.einsum("nd,edf->enf", x, w_in.astype(dtype))
    act = ffn_activation(up, gated, activation)
    act = act * gate.T[:, :, None].astype(dtype)
    out = jnp.einsum("enf,efd->nd", act, w_out.astype(dtype))
    return out, picks


def routed_ffn_block(normed, router_w, w_up, w_down, *, top_k: int = 1):
    """The serving layers' MoE MLP block: [B, Q, D] in, [B, Q, D] out.

    Shared by the contiguous (decode.py) and paged (kvcache.py) decode
    paths so the two cannot drift: every expert is held, ungated, the
    Switch/GShard gates of :func:`_route`. Dropless, so cached decode
    agrees with the teacher-forced forward pass *provided training
    capacity never bound* (:func:`warn_if_train_serve_divergence`).
    """
    batch, q_len, d = normed.shape
    out, _ = held_experts_ffn(
        normed.reshape(batch * q_len, d), router_w, w_up, w_down,
        top_k=top_k)
    return out.reshape(batch, q_len, d)
