"""Pallas fused RMSNorm — the VERDICT r3 #8 experiment.

A round-3 profiler breakdown (pre-PR-1 chip run, record removed in
PR 21, not comparable with today's code) named ~33 ms/step of
non-dot device work in the flagship train step, with ``reduce_sum``
(the norm mean-squares + the readout logsumexp) the largest category.
This kernel is the one named untried mechanism: fuse each RMSNorm's
reduce + rsqrt + two multiplies into a single one-pass Pallas kernel
(one HBM read of x, one write of y) instead of whatever fusion XLA
chooses.

Expectation going in (recorded so the result reads honestly either
way): XLA already emits a fused bandwidth-bound loop for this pattern,
so parity is the likely outcome — but "likely" is not a measurement.
No record of an A/B on today's code exists (ROADMAP D5).

Numerics mirror models/transformer.py ``_rmsnorm`` exactly in forward
(fp32 mean-square, scale cast to the compute dtype before the
multiply); backward is the analytic VJP in plain jnp — the backward
norm work is inside the rematerialized forward anyway, so the kernel
covers it there too.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from kvedge_tpu.ops import pallas_interpret

_EPS = 1e-6


def _fwd_kernel(x_ref, g_ref, o_ref):
    x = x_ref[...]
    xf = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(
        jnp.mean(xf * xf, axis=-1, keepdims=True) + _EPS
    )
    # Same cast chain as the jnp reference: scale down to the compute
    # dtype BEFORE multiplying, gain likewise.
    o_ref[...] = (x * scale.astype(x.dtype)) * g_ref[...].astype(x.dtype)


def _rmsnorm_fwd_pallas(x2d, gain, *, block_rows: int, interpret: bool):
    n, d = x2d.shape
    return pl.pallas_call(
        _fwd_kernel,
        grid=(n // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, d), x2d.dtype),
        interpret=interpret,
    )(x2d, gain)


@functools.partial(jax.custom_vjp, nondiff_argnums=())
def rmsnorm_fused(x, gain):
    """Drop-in for transformer._rmsnorm: ``x [..., D]``, ``gain [D]``."""
    y, _ = _rmsnorm_vjp_fwd(x, gain)
    return y


def _pick_block_rows(n: int) -> int:
    # Largest power-of-two block <= 512 rows that divides n; 512 x 512
    # bf16 is 0.5 MB of VMEM — comfortable double-buffering headroom.
    for b in (512, 256, 128, 64, 32, 16, 8):
        if n % b == 0:
            return b
    return 1


def _rmsnorm_vjp_fwd(x, gain):
    d = x.shape[-1]
    x2d = x.reshape(-1, d)
    n = x2d.shape[0]
    block = _pick_block_rows(n)
    if block < 8:
        # Degenerate row counts: fall back to the jnp formula rather
        # than a 1-row Pallas grid.
        scale = jax.lax.rsqrt(
            jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1,
                     keepdims=True) + _EPS
        )
        y = (x * scale.astype(x.dtype)) * gain.astype(x.dtype)
    else:
        y = _rmsnorm_fwd_pallas(
            x2d, gain, block_rows=block, interpret=pallas_interpret()
        ).reshape(x.shape)
    return y, (x, gain)


def _rmsnorm_vjp_bwd(res, dy):
    x, gain = res
    d = x.shape[-1]
    xf = x.astype(jnp.float32)
    dyf = dy.astype(jnp.float32)
    gf = gain.astype(jnp.float32)
    s = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + _EPS)
    dyg = dyf * gf  # [..., D]
    proj = jnp.sum(dyg * xf, axis=-1, keepdims=True) / d
    dx = (dyg * s - xf * proj * (s ** 3)).astype(x.dtype)
    dg = jnp.sum(
        (dyf * (xf * s)).reshape(-1, d), axis=0
    ).astype(gain.dtype)
    return dx, dg


rmsnorm_fused.defvjp(_rmsnorm_vjp_fwd, _rmsnorm_vjp_bwd)
