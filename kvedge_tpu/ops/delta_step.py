"""A decode step's delta-rule update and both its reads in one pass over
the state.

For every decoding row and one delta layer, a head at a time
(models/delta.py has the equations and ``_one_token``, the same lines in
plain ``jax.numpy``):

    S~ = diag(exp g) S        at_k = S~^T k      at_q = S~^T q
    u  = beta (v - at_k)      S' = S~ + k u^T    o = at_q + (k . q) u

A row's state is ``[H, dk, dv]`` float32, 4 MB at 64 heads of 128 by
128, and a step's whole cost is moving it. ``u`` must be known before
``S'`` can be written, so no fusion of XLA's does the two in one: as
XLA compiles ``_one_token`` the state is read for the two reductions
and then read again and written by the update, 447 + 815 us a layer at
the benchmark's delta cell (PERF.md section 5, PR 36). Here a head's
``[dk, dv]`` tile stays in VMEM from the reductions to the write: the
state is read once and written once.

* **In place in the stacked state**, as ops/ssm_step.py is: the kernel
  is handed ``recurrent["ssm"]`` whole, ``[delta layers, slots, H, dk,
  dv]``, and the layer's index as a prefetched scalar, addresses
  ``state[layer, row, block of heads]`` through its block
  specifications and aliases the array to its output. No caller slices
  a layer out, nothing of the state's size stands beside it, and layers
  other than ``layer`` and slots past the batch's rows are never
  touched.
* **The grid** is (row, block of heads); a block is
  :func:`heads_block` heads (16, 1 MB, at 128 by 128), which Pallas
  double-buffers in and out.
* **Inside a tile** ``exp g``, ``k`` and ``q`` vary down the sublanes
  (``dk``) and ``v``, ``u`` and ``o`` along the lanes (``dv``): both
  reductions are sums down the sublanes, adds of whole registers and
  one short reduce, and the update is elementwise once the three
  ``dk``-vectors stand as columns. They arrive with ``dk`` along the
  lanes, a block's heads to a tile; :func:`_columns` turns them.
* **A row that is not decoding** (``live`` false: an empty slot, a
  half-prefilled row) is never fetched and never written: the grid
  walks a compacted list of the live rows, handed as a prefetched
  scalar, and the iterations left over stay on the block the last live
  one ended on, which Pallas neither fetches nor writes again. Its
  ``o`` is zeros and never used.

Everything is float32, ``exp g`` made outside as ``_one_token`` makes
it: only the order of the ``dk``-term sums can differ from XLA's.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_BLOCK_BYTES = 1 << 20  # of one fetched block, at most (four stand in VMEM)


def heads_block(heads: int, dk: int, dv: int) -> int:
    """Heads in one block: the largest power of two that divides
    ``heads`` and stays within ``_BLOCK_BYTES`` (16 at 128 by 128), at
    least one."""
    block = 1
    while (block * 2 * dk * dv * 4 <= _BLOCK_BYTES
           and heads % (block * 2) == 0):
        block *= 2
    return block


def tiles(heads: int, dk: int, dv: int) -> bool:
    """Whether the kernel takes a row's state of [heads, dk, dv]: a
    head's tile whole lanes wide and whole lanes deep (its ``dk``-vectors
    are turned a lane row at a time)."""
    return (heads > 0 and dk > 0 and dk % _LANES == 0
            and dv > 0 and dv % _LANES == 0)


def _column(line):
    """``line`` [1, dk], a ``dk``-vector along the lanes, as the column
    [dk, 128] with the vector down the sublanes, the same in every
    lane: the line repeated down a [128, dk] tile and turned on the
    XLU."""
    return jnp.broadcast_to(line, (_LANES, line.shape[1])).T


def _heads(eg_ref, k_ref, q_ref, v_ref, beta_ref, s_ref, o_ref, out_ref):
    """One block of heads of one row. ``s_ref``/``out_ref``
    [1, 1, heads, dk, dv] are the block of the state as stored and where
    it goes back; ``eg_ref`` (``exp g``), ``k_ref``, ``q_ref``
    [1, 1, heads, dk] and ``v_ref``, ``beta_ref`` (``beta`` along a
    head's lanes), ``o_ref`` [1, 1, heads, dv] hold a head to a line."""
    heads, dv = s_ref.shape[2], s_ref.shape[4]
    k, q = k_ref[0, 0], q_ref[0, 0]
    k_dot_q = jnp.sum(k * q, axis=-1, keepdims=True)         # [heads, 1]
    for h in range(heads):
        eg_col = _column(eg_ref[0, 0, h:h + 1])
        k_col, q_col = _column(k[h:h + 1]), _column(q[h:h + 1])
        for lo in range(0, dv, _LANES):
            lanes = pl.ds(lo, _LANES)
            decayed = eg_col * s_ref[0, 0, h, :, lanes]
            at_k = jnp.sum(decayed * k_col, axis=0, keepdims=True)
            at_q = jnp.sum(decayed * q_col, axis=0, keepdims=True)
            u = beta_ref[0, 0, h:h + 1, lanes] * (
                v_ref[0, 0, h:h + 1, lanes] - at_k)
            out_ref[0, 0, h, :, lanes] = decayed + k_col * u
            o_ref[0, 0, h:h + 1, lanes] = at_q + k_dot_q[h:h + 1] * u


def _kernel(layer_ref, rows_ref, count_ref, *refs):
    del layer_ref, rows_ref  # read by the block specifications
    s_ref, out_ref = refs[-3], refs[-1]

    @pl.when(pl.program_id(0) < count_ref[0])
    def _():
        _heads(*refs)

    # No row is live: the one block the grid stays on goes back as it
    # came, for Pallas writes the block it holds when the grid ends.
    @pl.when(count_ref[0] == 0)
    def _():
        out_ref[...] = s_ref[...]


# Jitted for its trace cache and inlined, as ssm_step is: a period's
# body holds one call for each of its delta layers, and every decode
# program of a server traces that body.
@functools.partial(jax.jit, static_argnames=("interpret",), inline=True)
def delta_step(state, layer, q, k, v, g, beta, live=None, *,
               interpret: bool = False):
    """One token's delta-rule step for the first R slots of layer
    ``layer``.

    ``state`` [layers, slots, H, dk, dv] float32, the whole stacked
    state, returned updated in place (a caller that donates it gets the
    same buffer back); ``layer`` an int32 scalar, traced or not; ``q``,
    ``k``, ``g`` [R, H, dk]; ``v`` [R, H, dv]; ``beta`` [R, H]; ``live``
    [R] bool (None = all). Returns ``(o [R, H, dv], state)``:
    ``models.delta._one_token`` on ``state[layer, :R]`` for the live
    rows, the others' state untouched and their ``o`` zeros.
    """
    rows, heads, dk = k.shape
    dv = v.shape[-1]
    if state.shape[2:] != (heads, dk, dv) or not tiles(heads, dk, dv):
        raise ValueError(
            f"delta_step does not tile a state of {state.shape[2:]} for "
            f"{heads} heads of {dk} by {dv}: models.delta._one_token "
            "takes it")
    if live is None:
        live = jnp.ones((rows,), jnp.bool_)
    block = heads_block(heads, dk, dv)
    blocks = heads // block
    f32 = jnp.float32

    # The live rows first, in their order; the iterations past them
    # repeat the last live row, and stay on its last block.
    order = jnp.argsort(jnp.logical_not(live), stable=True).astype(jnp.int32)
    count = jnp.sum(live, dtype=jnp.int32)
    order = jnp.where(jnp.arange(rows) < count, order,
                      order[jnp.maximum(count - 1, 0)])

    def at(r, j, layer, order, count):
        return order[r], jnp.where(r < count[0], j, blocks - 1)

    def lines(a, width):  # [R, H, width] -> a block's heads to a tile
        return a.astype(f32).reshape(rows, blocks, block, width)

    def per_head(width):
        return pl.BlockSpec((1, 1, block, width),
                            lambda r, j, *s: (*at(r, j, *s), 0, 0))

    in_state = pl.BlockSpec(
        (1, 1, block, dk, dv),
        lambda r, j, *s: (s[0][0], *at(r, j, *s), 0, 0))
    o, state = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(rows, blocks),
            in_specs=[per_head(dk), per_head(dk), per_head(dk),
                      per_head(dv), per_head(dv), in_state],
            out_specs=[per_head(dv), in_state],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((rows, blocks, block, dv), f32),
            jax.ShapeDtypeStruct(state.shape, state.dtype),
        ],
        # operand 8 (after the three prefetched scalars and the five
        # small arrays) is the state; output 1 is the state.
        input_output_aliases={8: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="delta_step",
    )(jnp.asarray(layer, jnp.int32).reshape(1), order, count.reshape(1),
      lines(jnp.exp(g), dk), lines(k, dk), lines(q, dk), lines(v, dv),
      lines(jnp.broadcast_to(beta[..., None], v.shape), dv), state)
    # A row the grid never came to has whatever its lines of ``o`` held.
    o = jnp.where(live[:, None, None], o.reshape(rows, heads, dv), 0.0)
    return o, state
