"""Fused softmax-cross-entropy readout as Pallas TPU kernels.

Why: the flagship's training loss is dominated in HBM terms by the logits.
``tied_readout`` materializes ``[B*T, V]`` fp32 (at the bench shape,
32768 x 32000 x 4B = 4.2 GB), and the loss + its backward then stream that
tensor several times (logsumexp reads, the softmax-minus-onehot cotangent,
and both readout matmul transposes). Measured on v5e this kept the train
step ~35% MFU while the sweep showed throughput flat in batch — a
bandwidth ceiling, not a compute one.

This module applies the flash-attention trick to the vocab axis instead:
logits are computed blockwise (``[bn, bv]`` tiles live only in VMEM), an
online max/sum accumulates the logsumexp, and the target logit is
extracted with a masked reduce as its block streams past. The backward
recomputes each block's probabilities from the saved LSE (numerically
identical to the forward's final state) and accumulates ``dx`` and
``d_embedding`` in VMEM scratch — so neither pass ever materializes a
``[*, V]`` tensor in HBM. Matmul operands stay bf16 (MXU rate) with fp32
accumulation, matching ``tied_readout``'s
``preferred_element_type=float32`` contract.

No reference counterpart: levi106/kvedge has no compute path at all
(SURVEY.md §0); this is TPU-first optimization of the payload this repo
adds.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Vocab-block preference: bigger tiles amortize grid overhead; 2048 x fp32
# rows start pressuring the ~16 MB VMEM scope once the embedding block and
# double-buffering are counted (same budget reasoning as ops/attention.py).
_VOCAB_BLOCKS = (1280, 1024, 512, 256, 128)
_ROW_BLOCKS = (1024, 512, 256, 128, 64, 32, 16, 8)

# Row-block ceilings, from the [bn, bv] fp32 intermediates each kernel
# holds live at once (s / p / ds are ~bn*bv*4B each): the forward keeps
# two, the backward kernels keep three plus a [*, D] accumulator —
# bn=1024 in backward was measured to exceed the 16 MB scoped-vmem limit
# by 668 KB on v5e at bv=1280, D=512.
FWD_MAX_ROWS = 512
BWD_MAX_ROWS = 256


# What one vocab row of a block costs the dE kernel in VMEM, per model
# dim: the fp32 accumulator, the double-buffered fp32 output block and
# the double-buffered bf16 embedding block (4 + 8 + 4 bytes). Capped so
# they leave room under the 16 MB scope for the row-side blocks: at
# D=512 that still admits 1280; at D=1024 the v5e compiler refused 1280
# by 80 KB (tests/test_chip_compile.py), and the cap picks 256.
_BWD_VOCAB_ROW_BYTES = 16
_BWD_VOCAB_BUDGET = 10 * 1024 * 1024


def pick_vocab_block(vocab: int, max_block: int = _VOCAB_BLOCKS[0]) -> int:
    """Largest lane-aligned vocab block <= max_block dividing ``vocab``
    (never below the smallest, one 128-lane tile)."""
    cap = max(max_block, _VOCAB_BLOCKS[-1])
    for block in _VOCAB_BLOCKS:
        if block <= cap and vocab % block == 0:
            return block
    raise ValueError(
        f"fused cross-entropy needs vocab divisible by 128, got {vocab} "
        "(pad the vocabulary or disable fused_xent)"
    )


def pick_row_block(rows: int, max_block: int = 1024) -> int:
    """Largest sublane-aligned row block <= max_block dividing ``rows``."""
    for block in _ROW_BLOCKS:
        if block <= max_block and rows % block == 0:
            return block
    raise ValueError(
        f"fused cross-entropy needs batch*seq divisible by 8, got {rows}"
    )


def _fwd_kernel(x_ref, e_ref, tgt_ref, lse_ref, tlogit_ref,
                m_scr, l_scr, t_scr, *, bv: int):
    """One (ni, vi) step: fold vocab block vi into row block ni's state.

    x_ref: [bn, D] bf16; e_ref: [bv, D] bf16; tgt_ref: [bn, 1] int32;
    lse_ref/tlogit_ref: [bn, 1] f32; scratches m/l/t: [bn, 1] f32,
    persisting across the sequential vocab grid dimension.
    """
    vi = pl.program_id(1)
    nv = pl.num_programs(1)

    @pl.when(vi == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[:] = jnp.zeros_like(l_scr)
        t_scr[:] = jnp.zeros_like(t_scr)

    s = jax.lax.dot_general(
        x_ref[...], e_ref[...],
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [bn, bv]

    m_prev = m_scr[:]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    l_scr[:] = l_scr[:] * jnp.exp(m_prev - m_new) + jnp.sum(
        jnp.exp(s - m_new), axis=-1, keepdims=True
    )
    m_scr[:] = m_new

    # Each row's target id falls in exactly one vocab block, so summing the
    # masked scores across blocks yields precisely that one logit.
    cols = vi * bv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    t_scr[:] += jnp.sum(
        jnp.where(cols == tgt_ref[...], s, 0.0), axis=-1, keepdims=True
    )

    @pl.when(vi == nv - 1)
    def _():
        lse_ref[...] = m_scr[:] + jnp.log(l_scr[:])
        tlogit_ref[...] = t_scr[:]


def _dx_kernel(x_ref, e_ref, tgt_ref, lse_ref, g_ref, dx_ref, acc_scr,
               *, bv: int):
    """One (ni, vi) step: fold vocab block vi into row block ni's dx.

    dx_i = g_i * (softmax_i @ E - E[target_i]); both terms stream through
    the same ``ds = g * (p - onehot)`` cotangent tile.
    """
    vi = pl.program_id(1)
    nv = pl.num_programs(1)

    @pl.when(vi == 0)
    def _():
        acc_scr[:] = jnp.zeros_like(acc_scr)

    s = jax.lax.dot_general(
        x_ref[...], e_ref[...],
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    p = jnp.exp(s - lse_ref[...])  # exact recompute from the saved LSE
    cols = vi * bv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    ds = (p - jnp.where(cols == tgt_ref[...], 1.0, 0.0)) * g_ref[...]
    acc_scr[:] += jax.lax.dot_general(
        ds.astype(e_ref.dtype), e_ref[...],
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(vi == nv - 1)
    def _():
        dx_ref[...] = acc_scr[:].astype(dx_ref.dtype)


def _de_kernel(x_ref, e_ref, tgt_ref, lse_ref, g_ref, de_ref, acc_scr,
               *, bv: int):
    """One (vi, ni) step: fold row block ni into vocab block vi's dE.

    Grid is vocab-major (rows innermost) so the [bv, D] accumulator can
    carry across all row blocks and write once at the end.
    """
    ni = pl.program_id(1)
    nn = pl.num_programs(1)

    @pl.when(ni == 0)
    def _():
        acc_scr[:] = jnp.zeros_like(acc_scr)

    vi = pl.program_id(0)
    s = jax.lax.dot_general(
        x_ref[...], e_ref[...],
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [bn, bv]
    p = jnp.exp(s - lse_ref[...])
    cols = vi * bv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    ds = (p - jnp.where(cols == tgt_ref[...], 1.0, 0.0)) * g_ref[...]
    acc_scr[:] += jax.lax.dot_general(
        ds.astype(x_ref.dtype), x_ref[...],
        dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [bv, D]

    @pl.when(ni == nn - 1)
    def _():
        de_ref[...] = acc_scr[:]


def _xent_fwd_raw(x, embedding, targets, *, bn: int, bv: int,
                  interpret: bool):
    """x [N, D] bf16, embedding [V, D] bf16, targets [N] int32 ->
    (lse [N] f32, target_logit [N] f32)."""
    n, d = x.shape
    v = embedding.shape[0]
    tgt = targets.reshape(n, 1).astype(jnp.int32)
    grid = (n // bn, v // bv)
    row_spec = pl.BlockSpec((bn, d), lambda i, j: (i, 0))
    out_row = pl.BlockSpec((bn, 1), lambda i, j: (i, 0))
    lse, tlogit = pl.pallas_call(
        functools.partial(_fwd_kernel, bv=bv),
        grid=grid,
        in_specs=[
            row_spec,
            pl.BlockSpec((bv, d), lambda i, j: (j, 0)),
            out_row,
        ],
        out_specs=[out_row, out_row],
        out_shape=[
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bn, 1), jnp.float32)] * 3,
        interpret=interpret,
    )(x, embedding, tgt)
    return lse[:, 0], tlogit[:, 0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def fused_xent(x, embedding, targets, interpret: bool = False):
    """Per-row softmax cross-entropy of the tied readout, fused.

    x: [N, D] (compute dtype), embedding: [V, D] (fp32 master — cast to
    the compute dtype once, in here, so its cotangent stays fp32 for the
    optimizer), targets: [N] int32 -> [N] f32 losses
    ``logsumexp(x @ E^T) - logit[t]``. Semantically identical to the
    naive path built on
    :func:`~kvedge_tpu.models.transformer.tied_readout`, but no [N, V]
    tensor ever reaches HBM in either pass. Requires N % 8 == 0 and
    V % 128 == 0 (checked with actionable errors at trace time).
    """
    # One forward recipe: the primal delegates to the VJP-forward so the
    # two paths can never drift apart.
    return _fused_xent_fwd(x, embedding, targets, interpret)[0]


def _fused_xent_fwd(x, embedding, targets, interpret):
    e16 = embedding.astype(x.dtype)
    v = embedding.shape[0]
    # Match the naive path's jnp.take_along_axis semantics on garbage ids
    # exactly: negative ids wrap (-1 -> V-1), ids outside [-V, V) gather
    # a NaN fill — so a corrupt corpus NaNs the loss LOUDLY in both paths
    # instead of silently training on a wrong extraction here. (Backward
    # NaN poisoning is not bit-matched; forward loss is, which is what a
    # diverging-loss check sees.) The wrapped ids ride the residuals so
    # the backward's onehot matches the forward's extraction.
    wrapped = jnp.where(targets < 0, targets + v, targets)
    valid = (targets >= -v) & (targets < v)
    lse, tlogit = _xent_fwd_raw(
        x, e16, jnp.clip(wrapped, 0, v - 1),
        bn=pick_row_block(x.shape[0], FWD_MAX_ROWS),
        bv=pick_vocab_block(v),
        interpret=interpret,
    )
    tlogit = jnp.where(valid, tlogit, jnp.nan)
    return lse - tlogit, (x, e16, jnp.clip(wrapped, 0, v - 1), lse)


def _fused_xent_bwd(interpret, residuals, g):
    x, embedding, targets, lse = residuals
    n, d = x.shape
    v = embedding.shape[0]
    bn = pick_row_block(n, BWD_MAX_ROWS)
    bv = pick_vocab_block(
        v, _BWD_VOCAB_BUDGET // (_BWD_VOCAB_ROW_BYTES * d)
    )
    tgt = targets.reshape(n, 1).astype(jnp.int32)
    lse2 = lse.reshape(n, 1)
    g2 = g.reshape(n, 1).astype(jnp.float32)

    row_spec = pl.BlockSpec((bn, d), lambda i, j: (i, 0))
    row_col = pl.BlockSpec((bn, 1), lambda i, j: (i, 0))
    dx = pl.pallas_call(
        functools.partial(_dx_kernel, bv=bv),
        grid=(n // bn, v // bv),
        in_specs=[
            row_spec,
            pl.BlockSpec((bv, d), lambda i, j: (j, 0)),
            row_col, row_col, row_col,
        ],
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct((n, d), x.dtype),
        scratch_shapes=[pltpu.VMEM((bn, d), jnp.float32)],
        interpret=interpret,
    )(x, embedding, tgt, lse2, g2)

    # Vocab-major grid for dE: row blocks are grid dim 1 (innermost).
    vrow_spec = pl.BlockSpec((bn, d), lambda i, j: (j, 0))
    vrow_col = pl.BlockSpec((bn, 1), lambda i, j: (j, 0))
    de = pl.pallas_call(
        functools.partial(_de_kernel, bv=bv),
        grid=(v // bv, n // bn),
        in_specs=[
            vrow_spec,
            pl.BlockSpec((bv, d), lambda i, j: (i, 0)),
            vrow_col, vrow_col, vrow_col,
        ],
        out_specs=pl.BlockSpec((bv, d), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((v, d), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bv, d), jnp.float32)],
        interpret=interpret,
    )(x, embedding, tgt, lse2, g2)

    d_targets = jax.numpy.zeros(targets.shape, jax.dtypes.float0)
    # de is fp32 from the kernel accumulator and the embedding primal is
    # the fp32 master, so the optimizer sees full-precision grads.
    return dx, de, d_targets


fused_xent.defvjp(_fused_xent_fwd, _fused_xent_bwd)
