"""A decode step's SSM update and its read in one pass over the state.

For every decoding row and one mamba layer (models/ssm.py has the
equations and ``_one_token``, the same line in plain ``jax.numpy``):

    S' = exp(dt A) S + (dt x) (x) B          y = S' C

A row's state ``S`` is ``[H * P, N]`` float32, 4 MB at 128 heads of 64
with state 128, and a step's whole cost is moving it: as two XLA
fusions (the update written in place, then ``y`` as a reduction of its
own, which XLA does not put into the in-place update's fusion) the
state was read twice and written once a layer, 13.6 of a decode step's
27.7 ms at the benchmark's patterned cell (PERF.md section 5, PR 33).
Here it is read once and written once.

* **In place in the stacked state.** The kernel is handed
  ``recurrent["ssm"]`` whole, ``[mamba layers, slots, H * P, N]``, and
  the layer's index as a prefetched scalar, addresses
  ``state[layer, row, block]`` through its block specifications and
  aliases the array to its output: no caller slices a layer out (XLA
  would copy 268 MB out and in a layer; PR 25 found exactly that on the
  page pool) and nothing of the state's size stands beside it. Layers
  other than ``layer`` and slots past the batch's rows are never
  touched.
* **The grid** is (row, block of ``H * P``); a block is
  :func:`block_rows` rows of the state (2,048, 1 MB, at state 128),
  which Pallas double-buffers in and out: the next block streams in and
  the last one out while this one computes. On a v5e the stream is the
  whole cost: a kernel that only copies the blocks through takes 817 us
  a layer at the benchmark cell's shapes (268 MB in and 268 out: 657
  GB/s, the rate of XLA's own in-place update) with blocks of 2,048,
  4,096 or 8,192 rows alike (843 at 1,024), and this one 819 to 821
  (PERF.md section 5, PR 34).
* **Inside a block**, 128 rows of the state at a time, turned on the
  XLU so that the per-row factors lie along the lanes and ``y`` is a
  sum down the sublanes (:func:`_kernel`). A sum along the lanes of
  every register (``jnp.sum(..., axis=-1)``) did not hide under the
  stream (860 us); a product with ``C`` on the MXU did, and so did
  this, which alone needs no re-laid copy of ``decay``, ``dt x`` and
  ``y`` outside the kernel and gave XLA's bits on the chip.
* **A row that is not decoding** (``live`` false: an empty slot, a
  half-prefilled row) is copied through, so its state comes back bit
  for bit; its ``y`` is zeros and never used.

Everything is float32: only the order of the N-term sum for ``y`` can
differ from ``_one_token``'s.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_CHUNK = 128            # rows of the state worked on at a time
_BLOCK_BYTES = 1 << 20  # of one fetched block, at most (four stand in VMEM)


def block_rows(inner: int, state: int) -> int:
    """Rows of ``H * P`` in one block: the largest power-of-two number
    of chunks that divides ``inner`` and stays within ``_BLOCK_BYTES``
    (2,048 rows at state 128), at least one chunk."""
    rows = _CHUNK
    while (rows * 2 * state * 4 <= _BLOCK_BYTES
           and inner % (rows * 2) == 0):
        rows *= 2
    return rows


def tiles(inner: int, state: int) -> bool:
    """Whether the kernel takes a row's state of [inner, state]: its
    minor dimension whole lanes, its rows whole chunks."""
    return (state > 0 and state % 128 == 0
            and inner > 0 and inner % _CHUNK == 0)


def _kernel(layer_ref, live_ref, decay_ref, dtx_ref, b_ref, c_ref, s_ref,
            y_ref, o_ref):
    """One block of one row. ``s_ref``/``o_ref`` [1, 1, block, N] are the
    block of the state as stored and where it goes back; ``decay_ref``,
    ``dtx_ref`` and ``y_ref`` [1, 1, chunks, 128] hold one value for
    each of the block's rows, a chunk of 128 rows to a line, so that
    they are read and written as whole lanes; ``b_ref``/``c_ref``
    [1, 1, N] are the row's B and C.

    The per-row factors vary along the state's rows and B and C along
    its lanes. A chunk [128, N] is turned on the XLU so that its rows lie
    along the lanes: there ``decay`` and ``dt x`` are a line repeated
    down the sublanes, B and C a column repeated across the lanes (made
    once a block), the update is elementwise, and ``y`` is a sum down
    the sublanes, which is adds of whole registers and one short
    reduce, where a sum along the lanes of every register would be the
    cross-lane unit's work for each. The new chunk is turned back and
    stored."""
    del layer_ref  # read by the block specifications
    row = pl.program_id(0)
    n = s_ref.shape[-1]
    chunks = s_ref.shape[2] // _CHUNK

    @pl.when(live_ref[row] == 0)
    def _():
        o_ref[...] = s_ref[...]
        y_ref[...] = jnp.zeros(y_ref.shape, y_ref.dtype)

    @pl.when(live_ref[row] != 0)
    def _():
        b_col = jnp.broadcast_to(b_ref[0], (_CHUNK, n)).T   # [N, 128]
        c_col = jnp.broadcast_to(c_ref[0], (_CHUNK, n)).T
        for k in range(chunks):
            rows = pl.ds(k * _CHUNK, _CHUNK)
            turned = s_ref[0, 0, rows, :].T                  # [N, 128]
            new = (decay_ref[0, 0, k:k + 1, :] * turned
                   + b_col * dtx_ref[0, 0, k:k + 1, :])
            y_ref[0, 0, k:k + 1, :] = jnp.sum(new * c_col, axis=0,
                                              keepdims=True)
            o_ref[0, 0, rows, :] = new.T


# Jitted for its trace cache and inlined, as paged_decode_attention is:
# a period's body holds one call for each of its mamba layers, and
# every decode program of a server traces that body.
@functools.partial(jax.jit, static_argnames=("interpret",), inline=True)
def ssm_step(state, layer, x, B, C, dt, A, live=None, *,
             interpret: bool = False):
    """One token's SSM step for the first R slots of layer ``layer``.

    ``state`` [layers, slots, H * P, N] float32, the whole stacked
    state, returned updated in place (a caller that donates it gets the
    same buffer back); ``layer`` an int32 scalar, traced or not; ``x``
    [R, H, P]; ``B``, ``C`` [R, N]; ``dt`` [R, H]; ``A`` [H]; ``live``
    [R] bool (None = all). Returns ``(y [R, H, P] without the D term,
    state)``: ``models.ssm._one_token`` on ``state[layer, :R]`` for
    the live rows, the others' state untouched and their ``y`` zeros.
    """
    rows, heads, p = x.shape
    _, _, inner, n = state.shape
    if inner != heads * p or not tiles(inner, n):
        raise ValueError(
            f"ssm_step does not tile a state of [{inner}, {n}] for "
            f"{heads} heads of {p}: models.ssm._one_token takes it")
    if live is None:
        live = jnp.ones((rows,), jnp.bool_)
    block = block_rows(inner, n)
    chunks = block // _CHUNK
    f32 = jnp.float32

    def lines(a):  # [R, H * P] -> a chunk of rows to a line of lanes
        return a.astype(f32).reshape(rows, inner // block, chunks, _CHUNK)

    decay = jnp.repeat(jnp.exp(dt * A), p, axis=1)
    dtx = (dt[:, :, None] * x).reshape(rows, inner)
    per_row = pl.BlockSpec((1, 1, chunks, _CHUNK),
                           lambda r, j, *_: (r, j, 0, 0))
    per_lane = pl.BlockSpec((1, 1, n), lambda r, j, *_: (r, 0, 0))
    in_state = pl.BlockSpec(
        (1, 1, block, n), lambda r, j, layer, live: (layer[0], r, j, 0))
    y, state = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(rows, inner // block),
            in_specs=[per_row, per_row, per_lane, per_lane, in_state],
            out_specs=[per_row, in_state],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((rows, inner // block, chunks, _CHUNK), f32),
            jax.ShapeDtypeStruct(state.shape, state.dtype),
        ],
        # operand 6 (after the two prefetched scalars and the four
        # small arrays) is the state; output 1 is the state.
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="ssm_step",
    )(jnp.asarray(layer, jnp.int32).reshape(1), live.astype(jnp.int32),
      lines(decay), lines(dtx), B.astype(f32)[:, None, :],
      C.astype(f32)[:, None, :], state)
    return y.reshape(rows, heads, p), state
