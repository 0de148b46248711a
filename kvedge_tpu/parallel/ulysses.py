"""Ulysses-style sequence parallelism: all-to-all head scatter/gather.

The second long-context strategy alongside :mod:`ringattention` (the
reference has no sequence dimension at all — SURVEY.md §5 — both exist
because a TPU-native payload must scale context past one chip's HBM).
Where the ring rotates K/V chunks around the ``seq`` axis one hop at a
time, Ulysses re-shards *once* in each direction:

* inputs arrive sequence-sharded — each device holds ``[B, T/sp, H, dh]``;
* one ``lax.all_to_all`` per tensor swaps the sharded dim: split the head
  axis ``sp`` ways, concatenate the sequence axis — every device now holds
  ``H/sp`` full-sequence heads ``[B, T, H/sp, dh]``;
* attention runs *locally and exactly* — a dense causal softmax in fp32
  over the device's heads, materializing an ``[B, H/sp, T, T]`` score
  block per device (same peak-memory shape as the naive path over fewer
  heads; the *ring* is the strategy that avoids full-sequence scores);
* a reverse all-to-all restores sequence sharding for the rest of the
  layer (LN/MLP stay sequence-parallel).

Trade-off vs the ring (why both exist): Ulysses moves Q/K/V/O exactly
once over the all-to-all (cheap on a TPU slice where the ICI torus gives
all-to-all high bisection bandwidth) and keeps the matmuls as one big
MXU-friendly block per head — but its parallelism spends the HEAD
dimension: a ``model`` tensor-parallel axis shards heads first and the
``seq`` axis scatters each shard's remainder, so ``n_heads`` must divide
by ``tp * sp`` — while the ring scales to any ``sp`` that divides the
sequence and never materializes a full-sequence tensor on one device.
Short-to-medium contexts with spare head parallelism favor Ulysses;
extreme contexts (or head-poor models) favor the ring.

Differentiability is free: ``all_to_all`` is its own transpose under
reverse-mode, and the local attention is plain jnp.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

# Same finite -inf stand-in as the ring: exp(_MASKED - m) == 0 in fp32.
_MASKED = -1e30


def _local_causal_attention(q, k, v):
    """Exact causal attention on full-sequence, head-local tensors.

    q, k, v: [B, T, Hl, dh], any dtype — scores and softmax run in fp32
    locally. Causality is the plain global triangle because every device
    sees the whole sequence.
    """
    qf, kf, vf = (x.astype(jnp.float32) for x in (q, k, v))
    dh = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", qf, kf) / (dh ** 0.5)
    seq = q.shape[1]
    causal = jnp.tril(jnp.ones((seq, seq), jnp.bool_))
    s = jnp.where(causal[None, None], s, _MASKED)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", w, vf)


def _ulysses_local(q, k, v, *, axis_name: str):
    """Per-device body. q, k, v: [B, Tl, H, dh] local sequence chunks."""
    orig_dtype = q.dtype

    def scatter_heads(x):
        # [B, Tl, H, dh] -> [B, T, H/sp, dh]: split heads over the axis,
        # gather the sequence. tiled=True concatenates (the axis dim does
        # not appear as a new leading dim).
        return lax.all_to_all(
            x, axis_name, split_axis=2, concat_axis=1, tiled=True
        )

    # Communicate in the model dtype (bf16 halves the all-to-all bytes —
    # the dominant cost Ulysses is chosen for); cast to fp32 only for the
    # local softmax math, matching the ring's cast-after-ppermute.
    q, k, v = (scatter_heads(x) for x in (q, k, v))
    out = _local_causal_attention(q, k, v).astype(orig_dtype)
    # [B, T, H/sp, dh] -> [B, Tl, H, dh]: the reverse re-shard.
    return lax.all_to_all(
        out, axis_name, split_axis=1, concat_axis=2, tiled=True
    )


def ulysses_attention(q, k, v, mesh, *, seq_axis: str = "seq",
                      data_axis: str = "data", model_axis: str = "model"):
    """Causal self-attention, sequence-sharded via all-to-all head scatter.

    q, k, v: [B, T, H, dh] (global shapes; rotary already applied). The
    batch dim shards on ``data_axis``. With a ``model_axis`` in the mesh
    (sp x tp composition, the matrix cell converted in round 3), the
    head dim shards over it FIRST — each device's all-to-all then
    scatters its ``H/tp`` local heads over the ``seq_axis``, so
    ``n_heads`` must divide by ``tp * sp`` (both axes are spent on the
    head dimension; attention itself is per-head, so the model axis
    needs no collective here — the qkv/out projections' Megatron psums
    happen outside, exactly as with ring).
    """
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    if seq_axis not in axis_sizes:
        raise ValueError(
            f"mesh has no {seq_axis!r} axis (axes: {sorted(axis_sizes)}) — "
            "ulysses attention needs a sequence axis"
        )
    sp = axis_sizes[seq_axis]
    tp = axis_sizes.get(model_axis, 1)
    head_axis = model_axis if tp > 1 else None
    seq, heads = q.shape[1], q.shape[2]
    if seq % sp:
        raise ValueError(
            f"sequence length {seq} must divide by the {seq_axis!r} axis "
            f"size {sp}"
        )
    if heads % (sp * tp):
        raise ValueError(
            f"n_heads {heads} must divide by {seq_axis!r} x "
            f"{model_axis!r} = {sp} x {tp} — ulysses scatters each "
            f"model shard's heads over the sequence axis; use ring "
            "attention when the axes exceed the head count"
        )
    dspec = data_axis if data_axis in axis_sizes else None
    spec = P(dspec, seq_axis, head_axis, None)
    local = functools.partial(_ulysses_local, axis_name=seq_axis)
    return jax.shard_map(
        local, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec
    )(q, k, v)
