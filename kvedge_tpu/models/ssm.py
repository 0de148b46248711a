"""The Mamba-2 mixer of a patterned block, in the two forms serving needs.

Both forms share their projections and everything around the recurrence
(:func:`mamba_mixer`): ``z | xBC | dt = h @ w_in``, the causal
depthwise conv over ``xBC`` with SiLU, ``dt = softplus(dt + dt_bias)``,
``A = -exp(A_log)``, the gated RMSNorm (gate first, then the norm over
all channels: one group) and the output projection. Between them stands

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t        S: [H, P, N]
    y_t = S_t C_t + D x_t

(a row's ``S`` is stored and passed as the matrix ``[H * P, N]``: as
``[H, P, N]`` the compiler chose another layout for the chunk form's
products than for the stored state and, to take one row's 4 MB,
copied every row's state, 2.4 GB at the benchmark's sizes, into that
layout and back on every prefill chunk)

* the **one-token form** (a decode step, Q = 1) is that line, once, for
  every row of the batch: elementwise over the rows' states, which is
  what a step reads and writes (4 MB a row and layer at 128 heads of
  64 with state 128) and almost no operations. Written in
  ``jax.numpy`` (:func:`_one_token`) XLA makes two passes of it, the
  update in place and then ``y`` as a reduction of its own, so the
  state is read twice; in a decode step or window on a TPU backend, at
  sizes it tiles, it is one pass in a kernel instead
  (ops/ssm_step.py, handed the stacked state whole and updating it in
  place; :func:`step_in_kernel` decides, from the trace alone).
  ``_one_token`` stays the form of a one-token prefill piece, of every
  other backend and size, and what the kernel is held to;
* the **chunk form** (a prefill chunk, Q > 1) is the same sum written
  out over a block of positions (the state-space dual form): within a
  block ``y_t = sum_{s<=t} exp(a_t - a_s) (C_t . B_s) dt_s x_s`` with
  ``a`` the running sum of ``dt A``, plus what the carried state gives,
  ``exp(a_t) C_t S_0``; the state after the block is the carried one
  decayed by ``exp(a_Q)`` plus every position's outer product decayed
  to the block's end. Blocks of ``cfg.ssm_chunk`` positions follow one
  another from the row's carried state, so a long chunk costs no
  [Q, Q] of its whole length. The blocking changes no result.

The recurrent state is float32 and so is everything between the conv
and the gated norm; the small products of the chunk form are full
float32 products (``Precision.HIGHEST``), because a state carried over
thousands of positions keeps what each step rounds away. The conv's
tail (its last ``ssm_conv - 1`` inputs) is kept in the compute dtype,
the dtype ``xBC`` is produced in.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from kvedge_tpu.ops import ssm_step

_HIGHEST = lax.Precision.HIGHEST


def _on_tpu() -> bool:
    """Apart from ``ops.pallas_interpret`` so that a CPU test can take
    the kernel and still run it in the interpreter."""
    return jax.default_backend() == "tpu"


def step_in_kernel(cfg, slot, q_len: int) -> bool:
    """Whether this trace's one-token form is the kernel
    (ops/ssm_step.py): a decode step or window (the batch's rows are
    the first slots and each brings one token: the test kvcache makes
    for the paged-attention kernel), on a TPU backend, at sizes the
    kernel tiles. Decided from what the trace can see; there is no
    option. A one-token prefill piece, every other backend and every
    other size keep :func:`_one_token`."""
    return (slot is None and q_len == 1 and _on_tpu()
            and ssm_step.tiles(cfg.ssm_inner, cfg.ssm_state))


def _one_token(S, x, B, C, dt, A):
    """S [R, H * P, N]; x [R, H, P]; B, C [R, N]; dt [R, H]; A [H].
    Returns (y [R, H, P] without the D term, the new S)."""
    rows, heads, p = x.shape
    decay = jnp.repeat(jnp.exp(dt * A), p, axis=1)[:, :, None]
    dtx = (dt[:, :, None] * x).reshape(rows, heads * p)
    S = decay * S + dtx[:, :, None] * B[:, None, :]
    y = jnp.sum(S * C[:, None, :], axis=-1)
    return y.reshape(rows, heads, p), S


def _block(S, x, B, C, dt, A):
    """One row, one block of T positions from state S [H * P, N]:
    x [T, H, P]; B, C [T, N]; dt [T, H]. Returns (y [T, H, P], S)."""
    a = jnp.cumsum(dt * A, axis=0)                       # [T, H], falling
    t, heads, p = x.shape
    causal = jnp.tril(jnp.ones((t, t), jnp.bool_))
    # exp(a_t - a_s) for s <= t: the exponent is never positive.
    span = jnp.where(causal[:, :, None], a[:, None, :] - a[None, :, :],
                     -jnp.inf)
    mix = jnp.exp(span) * jnp.dot(C, B.T, precision=_HIGHEST)[:, :, None]
    dtx = dt[:, :, None] * x                             # [T, H, P]
    y = jnp.einsum("tsh,shp->thp", mix, dtx, precision=_HIGHEST)
    y = y + jnp.exp(a)[:, :, None] * jnp.dot(
        C, S.T, precision=_HIGHEST).reshape(t, heads, p)
    to_end = jnp.exp(a[-1][None, :] - a)                 # [T, H]
    S = (jnp.repeat(jnp.exp(a[-1]), p)[:, None] * S
         + jnp.dot((to_end[:, :, None] * dtx).reshape(t, heads * p).T,
                   B, precision=_HIGHEST))
    return y, S


def mamba_mixer(cfg, h, w: dict, ssm, tail, live=None, layer=None):
    """The mixer over normed activations ``h`` [R, Q, D] of R rows.

    ``w``: one layer's ``w_in`` [D, 2I + 2N + H], ``conv_w`` [K, C],
    ``conv_b`` [C], ``dt_bias``, ``A_log``, ``D`` [H], ``norm`` [I],
    ``w_out`` [I, D] (I = H * P inner channels, C = I + 2N conv
    channels). ``ssm`` [R, H * P, N] float32 and ``tail`` [R, (K-1) * C]
    are the rows' carried state. ``live`` [R] bool (None = all): a row
    that is not live gets its state back untouched. Q == 1 runs the
    one-token form, Q > 1 the chunk form. Returns
    ``(out [R, Q, D], ssm, tail)``.

    With ``layer`` given (:func:`step_in_kernel` said so), ``ssm`` is
    the whole stacked state [mamba layers, slots, H * P, N], the rows
    are its first R slots, and the one-token form is the kernel on
    layer ``layer`` of it, in place: what comes back is the stacked
    state.
    """
    rows, q_len, _ = h.shape
    heads, p, n = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    inner, conv_dim, k = cfg.ssm_inner, cfg.ssm_conv_dim, cfg.ssm_conv
    dtype = h.dtype
    f32 = jnp.float32

    proj = h @ w["w_in"].astype(dtype)
    z = proj[..., :inner]
    xbc = proj[..., inner:inner + conv_dim]
    dt = proj[..., inner + conv_dim:]

    # Causal depthwise conv: position t sees its own input and the
    # k - 1 before it, the first of a chunk those the tail carried.
    seen = jnp.concatenate(
        [tail.reshape(rows, k - 1, conv_dim).astype(dtype), xbc], axis=1)
    conv_w = w["conv_w"].astype(f32)
    conv = sum(seen[:, j:j + q_len].astype(f32) * conv_w[j]
               for j in range(k)) + w["conv_b"].astype(f32)
    new_tail = seen[:, q_len:].reshape(rows, (k - 1) * conv_dim)
    xbc = jax.nn.silu(conv)                              # float32 from here
    x = xbc[..., :inner].reshape(rows, q_len, heads, p)
    b_in = xbc[..., inner:inner + n]
    c_in = xbc[..., inner + n:]
    dt = jax.nn.softplus(dt.astype(f32) + w["dt_bias"])
    a_neg = -jnp.exp(w["A_log"])

    if layer is not None:
        from kvedge_tpu.ops import pallas_interpret

        y, new_ssm = ssm_step.ssm_step(
            ssm, layer, x[:, 0], b_in[:, 0], c_in[:, 0], dt[:, 0], a_neg,
            live, interpret=pallas_interpret())
        y = y[:, None]
    elif q_len == 1:
        y, new_ssm = _one_token(ssm, x[:, 0], b_in[:, 0], c_in[:, 0],
                                dt[:, 0], a_neg)
        y = y[:, None]
    else:
        ys, new_ssm = [], ssm
        for lo in range(0, q_len, cfg.ssm_chunk):
            hi = min(q_len, lo + cfg.ssm_chunk)
            y, new_ssm = jax.vmap(
                lambda s, *blk: _block(s, *blk, a_neg)
            )(new_ssm, x[:, lo:hi], b_in[:, lo:hi], c_in[:, lo:hi],
              dt[:, lo:hi])
            ys.append(y)
        y = jnp.concatenate(ys, axis=1)
    y = y + w["D"][None, None, :, None] * x
    gated = y.reshape(rows, q_len, inner) * jax.nn.silu(z.astype(f32))
    gated = gated * lax.rsqrt(
        jnp.mean(gated * gated, axis=-1, keepdims=True) + cfg.norm_eps)
    out = (gated * w["norm"]).astype(dtype) @ w["w_out"].astype(dtype)
    if live is not None:
        if layer is None:  # the kernel left those rows as they were
            new_ssm = jnp.where(live[:, None, None], new_ssm, ssm)
        new_tail = jnp.where(live[:, None], new_tail, tail)
    return out, new_ssm, new_tail.astype(tail.dtype)
