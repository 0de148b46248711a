"""The held experts' feed-forward over the experts a token picked, and
no others.

For one layer's ``Eh`` held experts and ``N`` tokens (a decode batch, a
short prefill chunk), with ``gate`` [N, Eh] a token's gate for each
expert (zero where it did not pick it):

    out = sum over touched e of (hidden(x @ w_in[e]) * gate[:, e]) @ w_out[e]

models/moe.py's one product over all held experts reads every expert's
matrices whatever the routing, and at few tokens the matrices are what
the step costs: with 320 small experts, 8 a token and 64 rows a third
of the held experts get no pick in a step, and their 31 MB each are read
and multiplied by a gate of zero (PERF.md section 5, the delta cell).
Here the grid walks the list of touched experts, which arrives as a
prefetched scalar and is read by the block specifications: an expert
that is not on the list is never fetched.

* **In place in the stacked leaves**, as ops/delta_step.py is in the
  stacked state: the kernel is handed ``w_in`` [layers, Eh, D, F or 2F]
  and ``w_out`` [layers, Eh, F, D] whole and the layer's index as a
  prefetched scalar, so no caller slices a layer's experts out (1.26 GB
  at the delta cell) and nothing of their size stands beside them.
* **The grid** is (place in the list, tile of the expert's width ``F``):
  one expert's ``w_in`` is 21 MB at the delta cell, over any VMEM, so a
  step fetches the ``u`` columns and the ``g`` columns of a tile of
  :func:`width_tile` hidden units (``w_in`` is ``u | g`` when gated:
  the same array through two block specifications) and the tile's rows
  of ``w_out``, which Pallas double-buffers. Both products skip: the
  down product is a third of the bytes.
* **Places past the list's end** stay on the block the last touched
  expert ended on, which Pallas does not fetch again, and their body is
  skipped. With no expert touched the output is zeros.
* **Precision**: the matrices go to the MXU in the tokens' dtype (bf16
  on the chip, as they are stored), every product accumulates in float32, the
  activation and the gate are applied in float32 and rounded once, to
  the tokens' dtype, for the down product, and the sum over experts is
  one float32 accumulator [N, D] that lives in VMEM for the whole grid
  and is written once. Only the order of the float32 additions over
  experts differs from the one product's.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_SUBLANES = 16  # of a bf16 tile: the tokens are the products' rows
# One fetched tile of w_in's columns, at most (three tiles a step, each
# double-buffered, stand in VMEM). The size does not matter on the
# chip: at 40 experts of 4,096 x 1,280 and 64 tokens a layer reads
# 1,905 to 1,935 us with tiles of 256, 640 and 1,280 hidden units (2, 5
# and 10 MB; tools/expert_product_readings.py --tile-mb; PERF.md
# section 5, PR 45), so the one that needs least VMEM.
_TILE_BYTES = 4 << 20
_GATES = {"silu": jax.nn.silu, "relu": jax.nn.relu}


def hidden(u, g=None, gate: str = "silu"):
    """A feed-forward's hidden activation from the halves of its
    up-projection: ``gate(u) * g`` (``gate`` "silu", or "relu" for a
    ReGLU), or ``gelu(u)`` where there is no ``g``."""
    return jax.nn.gelu(u) if g is None else _GATES[gate](u) * g


def width_tile(d: int, f: int, itemsize: int = 2) -> int:
    """Hidden units in one tile: the largest divisor of ``f`` that is
    whole lanes and whose ``d`` columns stay within ``_TILE_BYTES``
    (512 of 1,280 cannot be, so 256 at 4,096 x 1,280 in bf16), at least
    one lane row."""
    best = _LANES
    for tile in range(_LANES, f + 1, _LANES):
        if f % tile == 0 and d * tile * itemsize <= _TILE_BYTES:
            best = tile
    return best


def tiles(n_tokens: int, d: int, f: int) -> bool:
    """Whether the kernel takes ``n_tokens`` tokens of width ``d`` and
    experts ``f`` wide: the tokens whole sublanes of a bf16 tile, both
    widths whole lanes."""
    return (n_tokens > 0 and n_tokens % _SUBLANES == 0
            and d > 0 and d % _LANES == 0 and f > 0 and f % _LANES == 0)


def _kernel(layer_ref, ids_ref, count_ref, x_ref, gate_ref, *refs,
            gate: str):
    del layer_ref  # read by the block specifications
    *w_in_refs, w_out_ref, out_ref = refs
    place, tile = pl.program_id(0), pl.program_id(1)

    @pl.when((place == 0) & (tile == 0))
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(place < count_ref[0])
    def _():
        x = x_ref[...]
        # The tokens' gates for this expert: its column of [N, Eh],
        # picked by a mask over the lanes and summed along them.
        expert = lax.broadcasted_iota(jnp.int32, gate_ref.shape, 1)
        column = jnp.sum(
            jnp.where(expert == ids_ref[place], gate_ref[...], 0.0),
            axis=1, keepdims=True)
        act = hidden(*(jnp.dot(x, w[...].astype(x.dtype),
                               preferred_element_type=jnp.float32)
                       for w in w_in_refs), gate=gate)
        out_ref[...] += jnp.dot((act * column).astype(x.dtype),
                                w_out_ref[...].astype(x.dtype),
                                preferred_element_type=jnp.float32)


# Jitted for its trace cache and inlined, as delta_step is: every layer
# of a period's body holds one call, and every program of a server
# whose shapes take the walk traces that body.
@functools.partial(jax.jit, static_argnames=("gated", "gate", "interpret"),
                   inline=True)
def expert_walk(x, gate_of, w_in, w_out, layer, touched, *, gated: bool,
                gate: str = "silu", interpret: bool = False):
    """The touched experts' part of the routed sum, for layer ``layer``.

    ``x`` [N, D]; ``gate_of`` [N, Eh] float32, a token's gate for each
    held expert, zero where it picked another; ``w_in`` [layers, Eh, D,
    2F] (``u | g``) when ``gated``, else [layers, Eh, D, F]; ``w_out``
    [layers, Eh, F, D]; ``layer`` an int32 scalar, traced or not;
    ``touched`` [Eh] bool, the experts to read. Returns [N, D] float32:
    the sum over the touched experts of ``(hidden(x @ w_in[layer, e]) *
    gate_of[:, e]) @ w_out[layer, e]`` (:func:`hidden`), which is the
    sum over all of them where ``gate_of`` is zero for the others.
    """
    n, d = x.shape
    held, f = w_out.shape[1], w_out.shape[2]
    if (w_in.shape[1:] != (held, d, (2 if gated else 1) * f)
            or w_out.shape[3] != d or gate_of.shape != (n, held)
            or not tiles(n, d, f)):
        raise ValueError(
            f"expert_walk does not tile {n} tokens of {d} over experts "
            f"{w_in.shape} and {w_out.shape}: models.moe's one product "
            "takes them")
    tile = width_tile(d, f, w_in.dtype.itemsize)
    n_tiles = f // tile

    # The touched experts first, in their order; the places past them
    # repeat the last touched one, and stay on its last tile.
    ids = jnp.argsort(jnp.logical_not(touched), stable=True).astype(jnp.int32)
    count = jnp.sum(touched, dtype=jnp.int32)
    ids = jnp.where(jnp.arange(held) < count, ids,
                    ids[jnp.maximum(count - 1, 0)])

    def at(i, t, layer, ids, count):
        return layer[0], ids[i], jnp.where(i < count[0], t, n_tiles - 1)

    def columns(half):  # a tile of w_in's columns, of u or of g
        def index(i, t, *s):
            layer, expert, t = at(i, t, *s)
            return layer, expert, 0, half * n_tiles + t
        return pl.BlockSpec((None, None, d, tile), index)

    def rows(i, t, *s):  # the tile's rows of w_out
        layer, expert, t = at(i, t, *s)
        return layer, expert, t, 0

    def whole(shape):
        return pl.BlockSpec(shape, lambda i, t, *s: (0, 0))

    halves = 2 if gated else 1
    fetched = 3 * d * tile * w_in.dtype.itemsize
    return pl.pallas_call(
        functools.partial(_kernel, gate=gate),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(held, n_tiles),
            in_specs=[whole((n, d)), whole((n, held)),
                      *(columns(half) for half in range(halves)),
                      pl.BlockSpec((None, None, tile, d), rows)],
            out_specs=whole((n, d)),
        ),
        out_shape=jax.ShapeDtypeStruct((n, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            # the fetched tiles twice, the tokens, the accumulator and
            # a product of its size, and room for the compiler's own
            vmem_limit_bytes=2 * fetched + 6 * n * d * 4 + (8 << 20)),
        interpret=interpret,
        name="expert_walk",
    )(jnp.asarray(layer, jnp.int32).reshape(1), ids, count.reshape(1),
      x, gate_of.astype(jnp.float32), *([w_in] * halves), w_out)
