"""The delta-rule mixer of a patterned block: linear attention whose
state forgets per channel and is corrected, not only added to, in the
two forms serving needs.

Both forms share their projections and everything around the recurrence
(:func:`delta_mixer`; H heads, ``dk`` key and ``dv`` value channels a
head, ``h`` the normed input, the conv causal, depthwise, over each of
the three projections):

    q = l2norm(silu(conv(h W_q))) / sqrt(dk)    k = l2norm(silu(conv(h W_k)))
    v = silu(conv(h W_v))
    g = -exp(A_log) * softplus((h W_f1) W_f2 + dt_bias)    [H, dk], <= 0
    beta = 2 * sigmoid(h W_b)                              [H]
    out = (sigmoid((h W_g1) W_g2) * rmsnorm_head(o)) W_out

(``l2norm(x) = x / sqrt(sum(x^2) + 1e-6)`` over a head's channels; the
norm of ``o`` over each head's ``dv`` channels with one gain vector;
``beta`` reaches 2, so a state's eigenvalue along ``k`` reaches -1.)
Between them stands, a head at a time,

    S~ = diag(exp(g_t)) S_{t-1}          S: [dk, dv], float32
    u_t = beta_t (v_t - S~^T k_t)        what the state lacks of v_t at k_t
    S_t = S~ + k_t u_t^T
    o_t = S_t^T q_t

(a row's ``S`` is stored and passed as ``[H, dk, dv]``, in memory the
``[H * dk, dv]`` matrix a mamba layer keeps at these sizes. Stored flat,
every reshape between the matrix and the heads stood between the
update's operations and the compiler's fusion of them: the decode
step's ``k u^T`` had ``u`` broadcast to an array of the state's size, a
quarter of a gigabyte a layer at the benchmark's sizes, written out and
read back)

* the **one-token form** (a decode step, Q = 1, :func:`_one_token`) is
  those lines once for every row of the batch. ``o`` needs no read of
  its own: ``S_t^T q = S~^T q + (k . q) u``, so both reductions are
  taken from the decayed state together. Written in ``jax.numpy`` XLA
  still makes two passes of it, the reductions and then the update in
  place, because ``u`` needs ``S~^T k`` before ``S_t`` can be written;
  in a decode step or window on a TPU backend, at sizes it tiles, it is
  one pass in a kernel instead (ops/delta_step.py, handed the stacked
  state whole and updating it in place; :func:`step_in_kernel` decides,
  from the trace alone). ``_one_token`` stays the form of a one-token
  prefill piece, of every other backend and size, and what the kernel
  is held to;
* the **chunk form** (a prefill chunk, Q > 1, :func:`_block`) is the
  recurrence unrolled over a block of T positions from the carried
  ``S_0``: ``S_t = diag(exp G_t) S_0 + sum_{s<=t} diag(exp(G_t - G_s))
  k_s u_s^T`` with ``G`` the running sum of ``g``, which makes the
  ``u`` of a block the solution of a unit lower-triangular system,

      A_kk[t, s] = beta_t sum_c k_tc k_sc exp(G_tc - G_sc)      (s < t)
      (I + A_kk) U = beta * (V - (K * exp(G)) S_0)
      A_qk[t, s] = sum_c q_tc k_sc exp(G_tc - G_sc)             (s <= t)
      O = (Q * exp(G)) S_0 + A_qk U
      S_T = diag(exp(G_T)) S_0 + (K * exp(G_T - G))^T U

  Every exponent is a difference of a later running sum and an earlier
  one, never positive, so no decay, however strong, overflows; the
  price is the [T, T, dk] array of them a head. Blocks of
  ``cfg.ssm_chunk`` positions follow one another from the row's carried
  state. The blocking changes no result.

The state is float32 and so is everything between the conv and
``W_out``; the chunk form's small products and its solve are full
float32 (``Precision.HIGHEST``), for models/ssm.py's reason. The conv's
tail (its last ``ssm_conv - 1`` inputs) is kept in the compute dtype.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from kvedge_tpu.models import ssm
from kvedge_tpu.ops import delta_step

_HIGHEST = lax.Precision.HIGHEST


def conv_dim(cfg) -> int:
    """Channels the causal conv runs over: q | k | v."""
    return 2 * cfg.ssm_heads * cfg.ssm_state + cfg.ssm_inner


def step_in_kernel(cfg, slot, q_len: int) -> bool:
    """Whether this trace's one-token form is the kernel
    (ops/delta_step.py): ``ssm.step_in_kernel``'s question, a decode
    step or window on a TPU backend, at the sizes this kernel tiles.
    Decided from what the trace can see; there is no option."""
    return (slot is None and q_len == 1 and ssm._on_tpu()
            and delta_step.tiles(cfg.ssm_heads, cfg.ssm_state,
                                 cfg.ssm_head_dim))


def _one_token(S, q, k, v, g, beta):
    """S [R, H, dk, dv]; q, k, g [R, H, dk]; v [R, H, dv]; beta [R, H].
    Returns (o [R, H, dv], the new S)."""
    decayed = jnp.exp(g)[..., None] * S
    at_k = jnp.sum(decayed * k[..., None], axis=2)
    at_q = jnp.sum(decayed * q[..., None], axis=2)
    u = beta[..., None] * (v - at_k)
    S = decayed + k[..., None] * u[:, :, None, :]
    o = at_q + jnp.sum(k * q, axis=-1, keepdims=True) * u
    return o, S


def _block(S, q, k, v, g, beta):
    """One row, one block of T positions from state S [H, dk, dv]:
    q, k, g [T, H, dk]; v [T, H, dv]; beta [T, H]. Returns
    (o [T, H, dv], S)."""
    t = k.shape[0]
    G = jnp.cumsum(g, axis=0)                            # falling
    later = jnp.tril(jnp.ones((t, t), jnp.bool_))
    # exp(G_t - G_s) for s <= t, a channel at a time: never positive.
    span = jnp.exp(jnp.where(later[:, :, None, None],
                             G[:, None] - G[None, :], -jnp.inf))
    a_qk = jnp.sum(q[:, None] * k[None, :] * span, axis=-1)  # [T, S, H]
    a_kk = jnp.sum(k[:, None] * k[None, :] * span, axis=-1)
    a_kk = jnp.where(jnp.eye(t, dtype=jnp.bool_)[:, :, None], 0.0,
                     a_kk * beta[:, None, :])
    from_start = jnp.exp(G)
    rhs = beta[..., None] * (v - jnp.einsum(
        "thc,hcv->thv", k * from_start, S, precision=_HIGHEST))
    u = jax.scipy.linalg.solve_triangular(
        jnp.eye(t) + a_kk.transpose(2, 0, 1), rhs.transpose(1, 0, 2),
        lower=True, unit_diagonal=True)                  # [H, T, dv]
    o = (jnp.einsum("thc,hcv->thv", q * from_start, S, precision=_HIGHEST)
         + jnp.einsum("tsh,hsv->thv", a_qk, u, precision=_HIGHEST))
    S = (from_start[-1][:, :, None] * S
         + jnp.einsum("shc,hsv->hcv", k * jnp.exp(G[-1][None] - G), u,
                      precision=_HIGHEST))
    return o, S


def _l2norm(x):
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def delta_mixer(cfg, h, w: dict, state, tail, live=None, layer=None):
    """The mixer over normed activations ``h`` [R, Q, D] of R rows.

    ``w``: one layer's ``w_qkv`` [D, 2 H dk + H dv] (q | k | v),
    ``conv_w`` [K, C] over those C channels, ``w_low`` [D, 2 r + H]
    (the decay's and the gate's first factors and ``W_b``: f1 | g1 |
    b), ``w_f2`` [r, H dk], ``w_g2`` [r, H dv], ``A_log`` [H],
    ``dt_bias`` [H dk], ``norm`` [dv], ``w_out`` [H dv, D]. ``state``
    [R, H, dk, dv] float32 and ``tail`` [R, (K-1) * C] are the rows'
    carried state. ``live`` [R] bool (None = all): a row that is not
    live gets its state back untouched. Q == 1 runs the one-token
    form, Q > 1 the chunk form. Returns ``(out [R, Q, D], state,
    tail)``.

    With ``layer`` given (:func:`step_in_kernel` said so), ``state`` is
    the whole stacked state [delta layers, slots, H, dk, dv], the rows
    are its first R slots, and the one-token form is the kernel on
    layer ``layer`` of it, in place: what comes back is the stacked
    state.
    """
    rows, q_len, _ = h.shape
    heads, dv, dk = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    keys, channels, k_conv = heads * dk, conv_dim(cfg), cfg.ssm_conv
    rank = cfg.ssm_gate_rank
    dtype = h.dtype
    f32 = jnp.float32

    qkv = h @ w["w_qkv"].astype(dtype)
    low = h @ w["w_low"].astype(dtype)

    # Causal depthwise conv: position t sees its own input and the
    # k_conv - 1 before it, the first of a chunk those the tail carried.
    seen = jnp.concatenate(
        [tail.reshape(rows, k_conv - 1, channels).astype(dtype), qkv],
        axis=1)
    conv_w = w["conv_w"].astype(f32)
    conv = sum(seen[:, j:j + q_len].astype(f32) * conv_w[j]
               for j in range(k_conv))
    new_tail = seen[:, q_len:].reshape(rows, (k_conv - 1) * channels)
    qkv = jax.nn.silu(conv)                              # float32 from here
    q = _l2norm(qkv[..., :keys].reshape(rows, q_len, heads, dk)) * dk ** -0.5
    k = _l2norm(qkv[..., keys:2 * keys].reshape(rows, q_len, heads, dk))
    v = qkv[..., 2 * keys:].reshape(rows, q_len, heads, dv)

    slow = (low[..., :rank] @ w["w_f2"].astype(dtype)).astype(f32)
    g = (-jnp.exp(w["A_log"])[:, None]
         * jax.nn.softplus(slow + w["dt_bias"]).reshape(
             rows, q_len, heads, dk))
    beta = 2.0 * jax.nn.sigmoid(low[..., 2 * rank:].astype(f32))
    gate = jax.nn.sigmoid(
        (low[..., rank:2 * rank] @ w["w_g2"].astype(dtype)).astype(f32))

    if layer is not None:
        from kvedge_tpu.ops import pallas_interpret

        o, new_state = delta_step.delta_step(
            state, layer, q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0],
            live, interpret=pallas_interpret())
        o = o[:, None]
    elif q_len == 1:
        o, new_state = _one_token(state, q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                  beta[:, 0])
        o = o[:, None]
    else:
        os_, new_state = [], state
        for lo in range(0, q_len, cfg.ssm_chunk):
            hi = min(q_len, lo + cfg.ssm_chunk)
            o, new_state = jax.vmap(_block)(
                new_state, q[:, lo:hi], k[:, lo:hi], v[:, lo:hi],
                g[:, lo:hi], beta[:, lo:hi])
            os_.append(o)
        o = jnp.concatenate(os_, axis=1)
    o = o * lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                      + cfg.norm_eps) * w["norm"]
    out = ((gate * o.reshape(rows, q_len, heads * dv)).astype(dtype)
           @ w["w_out"].astype(dtype))
    if live is not None:
        if layer is None:  # the kernel left those rows as they were
            new_state = jnp.where(live[:, None, None, None], new_state,
                                  state)
        new_tail = jnp.where(live[:, None], new_tail, tail)
    return out, new_state, new_tail.astype(tail.dtype)
