"""Causal ring attention: sequence parallelism over a mesh axis.

Long-context capability for the hosted payload. The reference has no
sequence dimension at all (SURVEY.md §5: "no sequence dimension exists in
this repo"); this module exists because a TPU-native runtime payload must
scale context length past one chip's HBM, and the TPU-idiomatic way is a
ring over the ICI torus:

* The sequence dim of q/k/v is sharded over a ``seq`` mesh axis — each
  device holds a contiguous chunk of ``T/sp`` tokens.
* K/V chunks rotate one hop per step with ``lax.ppermute`` (neighbor
  traffic only — rides ICI links, never DCN), while each device folds the
  visiting chunk into a running online softmax (max + denominator), the
  same combine flash attention uses across k blocks.
* Peak score memory per device is ``[B, H, T/sp, T/sp]`` — sp² smaller
  than naive — and K/V memory is ``1/sp`` of the full sequence.
* Causality by global position ids; chunks strictly above the diagonal
  (source index > own index) skip their matmuls via ``lax.cond`` — the
  ring still rotates, but ~half the MXU work is elided, mirroring the
  block-skip in the Pallas flash kernel.

The whole thing is a ``shard_map`` region: collectives are explicit here
(ppermute is the algorithm), whereas everywhere else in this package
sharding is annotation-only and XLA inserts the collectives.

Differentiability: the ring loop is a ``lax.scan`` (reverse-mode works
through ``ppermute`` — its transpose is the inverted ring). Each step is
``jax.checkpoint``-ed so the backward recomputes per-chunk scores instead
of storing ``sp`` score matrices.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

# Finite stand-in for -inf: keeps fully-masked rows NaN-free in the online
# softmax (exp(-BIG - m) == 0 exactly in fp32) without special-casing.
_MASKED = -1e30


def _ring_attention_local(q, k, v, *, axis_name: str, sp: int):
    """Per-device body. q, k, v: [B, Tl, H, dh] local sequence chunks.

    Runs inside ``shard_map``; ``lax.axis_index(axis_name)`` is this
    device's ring position, and global token positions are reconstructed
    from it (chunks are contiguous in sequence order).
    """
    batch, t_local, heads, dh = q.shape
    my = lax.axis_index(axis_name)
    scale = dh ** -0.5

    qf = q.astype(jnp.float32) * scale
    # [B, H, Tq, dh] — head-major for the score matmuls.
    qf = qf.transpose(0, 2, 1, 3)
    q_pos = my * t_local + jnp.arange(t_local)

    perm = [(i, (i + 1) % sp) for i in range(sp)]

    # Derive initializers from qf so they carry qf's varying-axes type —
    # a plain jnp.full would be device-invariant and the two lax.cond
    # branches below would disagree on varying manual axes.
    m0 = qf[..., :1] * 0.0 + _MASKED
    l0 = qf[..., :1] * 0.0
    acc0 = qf * 0.0

    @jax.checkpoint
    def fold(carry_mla, k_cur, v_cur, src):
        """Fold the kv chunk originating at device ``src`` into the state."""
        m, l, acc = carry_mla
        kf = k_cur.astype(jnp.float32).transpose(0, 2, 1, 3)  # [B, H, Tk, dh]
        vf = v_cur.astype(jnp.float32).transpose(0, 2, 1, 3)
        kv_pos = src * t_local + jnp.arange(t_local)
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, kf)
        mask = kv_pos[None, None, None, :] <= q_pos[None, None, :, None]
        s = jnp.where(mask, s, _MASKED)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        correction = jnp.exp(m - m_new)
        l_new = l * correction + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * correction + jnp.einsum("bhqk,bhkd->bhqd", p, vf)
        return m_new, l_new, acc_new

    def masked_fold(mla, k_cur, v_cur, src):
        # Above-diagonal chunks contribute nothing — skip their matmuls.
        return lax.cond(src > my, lambda mla, *_: mla, fold,
                        mla, k_cur, v_cur, src)

    def step(carry, s):
        k_cur, v_cur, m, l, acc = carry
        src = (my - s) % sp  # chunk origin after s ring hops
        m, l, acc = masked_fold((m, l, acc), k_cur, v_cur, src)
        k_next = lax.ppermute(k_cur, axis_name, perm)
        v_next = lax.ppermute(v_cur, axis_name, perm)
        return (k_next, v_next, m, l, acc), None

    # Scan the first sp-1 chunks (fold, then rotate); fold the last chunk
    # outside the scan — its trailing rotate would be a wasted ring hop.
    (k_last, v_last, m, l, acc), _ = lax.scan(
        step, (k, v, m0, l0, acc0), jnp.arange(sp - 1)
    )
    m, l, acc = masked_fold(
        (m, l, acc), k_last, v_last, (my - (sp - 1)) % sp
    )
    out = acc / l  # every q row attends at least to itself, so l > 0
    return out.transpose(0, 2, 1, 3).astype(q.dtype)  # [B, Tl, H, dh]


def ring_attention(q, k, v, mesh, *, seq_axis: str = "seq",
                   data_axis: str = "data", model_axis: str = "model"):
    """Causal self-attention with the sequence dim sharded over ``seq_axis``.

    q, k, v: [B, T, H, dh] (global shapes; rotary already applied). The
    batch dim shards on ``data_axis`` and — when the mesh has one — the
    head dim shards on ``model_axis``, composing sp×tp×dp on one mesh.
    T must divide by the ``seq_axis`` size.
    """
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    if seq_axis not in axis_sizes:
        raise ValueError(
            f"mesh has no {seq_axis!r} axis (axes: {sorted(axis_sizes)}) — "
            "ring attention needs a sequence axis"
        )
    sp = axis_sizes[seq_axis]
    seq = q.shape[1]
    if seq % sp:
        raise ValueError(
            f"sequence length {seq} must divide by the {seq_axis!r} axis "
            f"size {sp}"
        )
    heads = q.shape[2]
    head_axis = model_axis if model_axis in axis_sizes else None
    if head_axis and heads % axis_sizes[model_axis]:
        raise ValueError(
            f"n_heads {heads} must divide by the {model_axis!r} axis size "
            f"{axis_sizes[model_axis]} when composing ring attention with tp"
        )
    dspec = data_axis if data_axis in axis_sizes else None
    spec = P(dspec, seq_axis, head_axis, None)
    local = functools.partial(
        _ring_attention_local, axis_name=seq_axis, sp=sp
    )
    return jax.shard_map(
        local, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec
    )(q, k, v)


def sequence_sharding(mesh, *, seq_axis: str = "seq",
                      data_axis: str = "data"):
    """NamedSharding for [B, T, D] activations under sequence parallelism."""
    axis_names = set(mesh.axis_names)
    return NamedSharding(
        mesh,
        P(data_axis if data_axis in axis_names else None,
          seq_axis if seq_axis in axis_names else None,
          None),
    )
