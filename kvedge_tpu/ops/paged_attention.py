"""Paged-attention decode as a Pallas TPU kernel — bit-faithful to the
gather path.

Why: the paged decode step's einsum path materializes a per-sequence
contiguous view of the ENTIRE padded pool — ``pool[tables]`` gathers
``[B, max_pages x page, K, Dh]`` and attends over the padded maximum
(kvedge_tpu/models/kvcache.py ``_gathered``), so per-step HBM traffic
scales with the pool CAP, not the live content. At max_seq 1024 the
difference is invisible; at the long contexts the flash kernel exists
for (4k-8k+), a half-empty pool still pays full price every step —
exactly where vLLM-class paged attention earns its keep.

This kernel computes decode attention DIRECTLY over the block table,
in TWO PHASES so its numerics are the GATHER'S numerics, bitwise. It is
handed the pool as PagedState stores it, whole — [L, P, page, K*Dh],
the kv heads merged into the lane dim — and the layer's index as a
scalar-prefetch argument, and DMAs page ``tables[b, j]`` of layer ``l``
from where it lies (``hbm.at[l, tables[b, j]]``): no caller slices a
layer's slab out or reshapes it for the kernel's sake.

* grid = (batch,): ONE program per sequence, run in sequence, each
  doing work in proportion to its row's LIVE tokens (read from the
  scalar-prefetched positions). A row that is not decoding is handed
  over at a negative position: its program starts no copy, fills no
  scratch, runs no phase 2 and writes zeros (4.2 us for 64 such
  programs on a v5e; PERF.md section 5). Dead pages cost nothing — no
  DMA, no grid step.
* phase 1 streams the row's live pages in BLOCKS of several
  (:func:`block_pages`: 8 at 64 KB a page) through two slots of landing
  pads: all of a block's copies are started together, and the next
  block's before this block's compute, so 16 to 32 copies are in flight
  where a page-at-a-time double buffer had 2. The prefetch crosses the
  row boundary: behind a row's last block the next live row's first
  block is started, so it streams while this row runs phase 2 and no
  row but the first waits for its first page (the landing pads, the
  semaphores and the slot's parity are scratch, which outlives a
  program; JAX's own pallas/ops/tpu/paged_attention schedules its
  copies so). Per page the kernel performs ONLY the work whose
  rounding the gather makes visible:
  the fp32-accumulated score dot, the round to compute dtype, the
  dtype-domain scale division, and the causal mask — then parks the
  masked scores (upcast fp32, the gather's softmax input image) in a
  [H, S_cap] VMEM scratch and the page's V rows (dequantized for int8
  pools with the gather's exact elementwise formula) in a [S_cap,
  width] VMEM image. There is NO cross-page compute dependency, so the
  loop pipelines at max(DMA, dot) — unlike the retired online-softmax
  design, whose serial (m, l, acc) carry chained every page's exp/
  correction behind the previous page's.
* phase 2 is literally the gather's epilogue on the assembled row:
  ``jax.nn.softmax(scores_fp32, axis=-1).astype(dtype)`` followed by
  ONE flat fp32-accumulated dot against the V image over the full
  S_cap contraction. Score columns for dead pages are pre-filled with
  the same ``finfo(dtype).min`` the gather's mask writes, so they
  underflow to exactly +0.0 in the softmax; V rows beyond the live
  pages are exact zeros (a row zeroes what an earlier, longer row left
  past its own pages), so ``0 * 0`` pads the contraction
  with the same exact-zero terms the gather's ``w == 0`` rows
  contribute. Same values at the same positions, same shapes reduced
  over the same axis — a live row's output is BIT-IDENTICAL to the
  gather's (asserted exactly, not approximately, in
  tests/test_paged_attention.py under the interpreter, and by
  chip_smoke.py on the chip at heads of 64; at heads of 128 the two
  round the score scale at different points there,
  kvcache._use_paged_kernel). Phase 2 is sized by the cap and not by
  the row on purpose: sized by the row it was worth 3 of 102 us a
  layer at the benchmark cell's mix (PERF.md section 6, PR 31).
* one full-width dot scores every query head per page: q arrives
  PLACED — q2[h] carries head h's query in its kv head's Dh-slot,
  zeros elsewhere — so ``q2 @ page^T`` contracts over K*Dh and the
  zero slots kill cross-head terms exactly (adding fp32 zeros to the
  Dh-aligned partial sums changes no bits). The [H, width] output's
  per-head slot is extracted outside.

A layer bound to a window (``window`` > 0, a static argument) hands
the kernel a table that does not start at position 0: ``first`` [B]
is the position of each row's first table entry (a multiple of the
page; the pages before it were given back, models/kvcache.py), a row's
live pages are entries 0 to ``(position - first) // page``, a score
column's key position is ``first`` plus its place in the table, and the
mask is the gather's, ``q_pos - window < key_pos <= q_pos``: only the
oldest page's leading columns fall to its lower bound. A call with no
window is handed no ``first`` and traces none of this.

**The blocked form** (``blocked=True``, :func:`_decode_blocked_kernel`,
a call named ``paged_attention_blocked``) is for a table whose V image
does not fit the scratch whole (64 heads over 8,192 positions of a
1,024-wide pool: 2 MiB of scores, 16 MiB of image). Phase 1 streams the
row's K pages only and parks the masked, rounded scores whole, as
above; the softmax runs over the whole row, as above, into a scratch of
weights in the compute dtype; then the row's V pages stream through the
same landing pads, block by block, and each live page's ``w[:, page] @
V_page`` is summed in float32. Every K and V page of a live row is
still read from HBM once, a dead row still costs nothing, and behind a
row's last V block the next live row's first K block is in flight. What
differs from the whole form is the order of the float32 additions in
the V contraction (a page's partial products, then the pages), nothing
else: scores and weights are the gather's bit for bit, the output
agrees within float32 summation order. Which form a table takes is
static, by its shape (:func:`decode_scratch_form`); an int8 pool has no
blocked form.

The serving stack selects this kernel per ``TransformerConfig
.paged_attention`` ("auto" = kernel on TPU at long-context caps,
einsum gather elsewhere); prefill (multi-query) keeps the einsum
path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_SCALE_VMEM_BUDGET = 8 * 1024 * 1024  # bytes, BOTH scale arrays
_SCRATCH_VMEM_BUDGET = 12 * 1024 * 1024  # bytes, score + V-image scratch
_PAD_VMEM_BUDGET = 2 * 1024 * 1024  # bytes of it, the page landing pads
_MAX_BLOCK_PAGES = 8  # pages of one block: 16 K and V copies started together


def visible(key_pos, q_pos, window: int = 0):
    """Which keys a query sees, on absolute positions: the one
    statement of the mask, for the gather (models/kvcache.py) and the
    kernel below. Causal, and with ``window`` > 0 no further back than
    the last ``window`` positions, the query's own included:
    ``q_pos - window < key_pos <= q_pos``. ``window`` is static: with
    none, none of the bound is traced."""
    seen = key_pos <= q_pos
    if window:
        seen = seen & (key_pos > q_pos - window)
    return seen


def scales_fit_vmem(rows: int, kv_heads: int) -> bool:
    """Whether the int8 kernel variant can run: it maps BOTH whole
    scale arrays ([P, page, K] fp32 each, ``rows`` = P * page token
    rows per array) into VMEM alongside its page buffers. VMEM tiles
    the minor dim to 128 lanes, so a row of K scales occupies a whole
    128-lane row there — at K = 4 that is 32x the array's HBM size,
    which is what the v5e compiler charged when it refused a pool this
    function used to admit (tests/test_chip_compile.py). The policy
    lives here, next to the mechanism — callers route to the gather
    ("auto") or refuse loudly (forced "kernel") when this is False."""
    lanes = -(-kv_heads // 128) * 128
    return 2 * rows * lanes * 4 <= _SCALE_VMEM_BUDGET


def block_pages(max_pages: int, page: int, width: int,
                itemsize: int = 2, kinds: int = 2) -> int:
    """Pages of one fetched block: as many as the landing pads' share
    of the scratch budget holds twice over (two slots, of ``kinds``
    kinds of page: K and V) at this page's bytes, at most
    ``_MAX_BLOCK_PAGES`` and a row's cap, at least one. 8 at the 64 KB
    pages of a [128, 256] bf16 pool."""
    fit = _PAD_VMEM_BUDGET // (2 * kinds * page * width * itemsize)
    return max(1, min(fit, _MAX_BLOCK_PAGES, max_pages))


def decode_scratch_fits_vmem(max_pages: int, page: int, width: int,
                             n_heads: int) -> bool:
    """Whether the two-phase kernel's VMEM scratch fits: the fp32
    score rows ([H, S_cap]), the compute-dtype V image ([S_cap,
    width]), and the landing pads of two blocks of pages, K and V
    (:func:`block_pages`; counted at the compute dtype's 2 bytes, which
    an int8 pool's deeper blocks do not pass). Same contract as
    :func:`scales_fit_vmem`: "auto" routes over-cap pools to the
    gather; a forced "kernel" refuses loudly at call time."""
    s_cap = max_pages * page
    pads = 4 * block_pages(max_pages, page, width) * page * width * 2
    need = (n_heads * s_cap * 4      # scores, fp32
            + s_cap * width * 2      # V image, compute dtype (<= 2 B)
            + pads)
    return need <= _SCRATCH_VMEM_BUDGET


def blocked_block_pages(max_pages: int, page: int, width: int,
                        itemsize: int = 2) -> int:
    """Pages of one fetched block of the blocked form: its landing pads
    are two slots of one kind of page (K, then V), so a block is twice
    as deep as :func:`block_pages`' at the same budget."""
    return block_pages(max_pages, page, width, itemsize, kinds=1)


def blocked_scratch_fits_vmem(max_pages: int, page: int, width: int,
                              n_heads: int) -> bool:
    """Whether the blocked form's VMEM scratch fits: the fp32 score
    rows and the compute-dtype weights ([H, S_cap] each), the fp32
    accumulator [H, width] and the landing pads of two blocks of pages
    (:func:`blocked_block_pages`). 64 heads reach 16,384 positions, 28
    heads of a 512-wide pool 32,768."""
    s_cap = max_pages * page
    pads = 2 * blocked_block_pages(max_pages, page, width) * page * width * 2
    need = (n_heads * s_cap * (4 + 2)   # scores fp32, weights <= 2 B
            + n_heads * width * 4       # the accumulator
            + pads)
    return need <= _SCRATCH_VMEM_BUDGET


def decode_scratch_form(max_pages: int, page: int, width: int,
                        n_heads: int) -> str:
    """The form of the kernel a table of ``max_pages`` takes, by what
    its scratch holds: "whole" where scores and V image fit
    (:func:`decode_scratch_fits_vmem`), else "blocked" where scores and
    weights do (:func:`blocked_scratch_fits_vmem`), else "" (the
    gather). Static: a shape takes one form, and nothing chooses."""
    if decode_scratch_fits_vmem(max_pages, page, width, n_heads):
        return "whole"
    if blocked_scratch_fits_vmem(max_pages, page, width, n_heads):
        return "blocked"
    return ""


def _pages_of(pos_ref, first_ref, r, page: int, window: int):
    """Row r's live pages; none where its position is negative. In lax
    primitives, like the kernels' other bookkeeping: every jnp operator
    on a traced scalar is a nested jit, and a kernel's body is traced
    once for each decode-window program a server compiles or loads at
    start-up."""
    if window:
        return jax.lax.select(
            pos_ref[r] < 0, 0,
            jax.lax.div(pos_ref[r] - first_ref[r] + page, page))
    return jax.lax.max(jax.lax.div(pos_ref[r] + page, page), 0)


def _next_live(pos_ref, r, rows):
    """The first row after r that has pages, or ``rows``."""
    return jax.lax.while_loop(
        lambda x: jax.lax.bitwise_and(
            x < rows, pos_ref[jax.lax.min(x, rows - 1)] < 0),
        lambda x: x + 1, r + 1)


def _page_scores(q2, kj, key0, first, q_pos, scale, *, dtype, window: int,
                 divide: bool):
    """A page's masked score columns [H, page] float32, the gather's
    softmax input image: the fp32-accumulated dot, the round to the
    compute dtype, the scale in that dtype (``divide``: by sqrt(Dh);
    else times the block's multiplier), the mask on absolute positions
    (``key0`` the page's place in its table, ``first`` the table's first
    position under a window), the upcast."""
    s32 = jax.lax.dot_general(
        q2, kj,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [H, page] — exact per-head scores (zero slots add nothing)
    # Mirror the gather path's visible rounding: dtype scores, dtype
    # scale division, then the fp32 upcast its softmax does.
    s16 = s32.astype(dtype) / scale if divide else s32.astype(dtype) * scale
    key_pos = key0 + jax.lax.broadcasted_iota(jnp.int32, s16.shape, 1)
    if window:
        key_pos = key_pos + first
    s = jnp.where(visible(key_pos, q_pos, window), s16,
                  jnp.finfo(dtype).min)
    return s.astype(jnp.float32)


def _decode_flat_kernel(tables_ref, pos_ref, layer_ref, *rest,
                        page: int, width: int, dh: int, dtype,
                        quantized: bool, score_scale: float | None,
                        window: int = 0):
    """One program per SEQUENCE, two phases (module docstring).

    Layout: the pools arrive whole, [L, P, page, width] as PagedState
    stores them (width = K*Dh, the kv heads merged into the lane dim —
    TPU DMA slices need a 128-aligned minor dim, which [page, K, 64] is
    not), and stay in HBM: page j of row r is DMA'd from
    ``hbm.at[layer_ref[0], tables_ref[r, j]]``, so no layer's slab is
    ever sliced out of the pool for the kernel's sake. kbuf/vbuf
    [2, block, page, width] are two slots of landing pads in the POOL
    dtype (int8 pools stream as stored, half the DMA bytes); sems
    [2, 2] one DMA semaphore per (slot, k|v), which every copy of a
    block signals and every wait draws one page's worth from.
    ``scores`` [H, S_cap] fp32 and ``vimg`` [S_cap, width]
    compute-dtype hold the assembled row for phase 2. ``state_ref``
    (two int32 in SMEM) is what one row leaves for the next, scratch
    outliving a program and the grid running in sequence: [0] the slot
    the row's first block was fetched into, which is how a block
    started by one row is waited for by the next; [1] the pages of the
    V image that may hold an earlier row's values.
    For int8 pools the layer's per-(row, kv-head) scales ([P, page, K]
    fp32, a few MB whole in VMEM, indexed by page id) are widened across each
    head's Dh columns by a 0/1 dot and applied with the gather's exact
    dequant formula BEFORE any compute touches the page — from there
    the two variants share one body, which is how the int8 kernel
    bit-matches the int8 gather. With ``window`` a fourth prefetched
    scalar row, ``first_ref`` [B], leads ``rest`` (module docstring)."""
    if window:
        first_ref, q_ref, *rest = rest
    else:
        q_ref, *rest = rest
    if quantized:
        (scale_k_ref, scale_v_ref, k_hbm, v_hbm, o_ref,
         kbuf, vbuf, scores, vimg, sems, state_ref) = rest
    else:
        (k_hbm, v_hbm, o_ref,
         kbuf, vbuf, scores, vimg, sems, state_ref) = rest

    b = pl.program_id(0)
    rows = pl.num_programs(0)
    block = kbuf.shape[1]
    pages_of = functools.partial(_pages_of, pos_ref,
                                 first_ref if window else None,
                                 page=page, window=window)
    next_live = functools.partial(_next_live, pos_ref, rows=rows)

    def block_dmas(r, i, slot, act):
        """Start (or wait for) the copies of block i of row r into
        landing-pad slot ``slot``: its live pages only, K and V."""
        first = i * block

        def page(j, carry):
            for hbm, buf, which in ((k_hbm, kbuf, 0), (v_hbm, vbuf, 1)):
                act(pltpu.make_async_copy(
                    hbm.at[layer_ref[0], tables_ref[r, j]],
                    buf.at[slot, j - first], sems.at[slot, which]))
            return carry

        jax.lax.fori_loop(
            first, jax.lax.min(first + block, pages_of(r)), page, 0)

    def start(r, i, slot):
        block_dmas(r, i, slot, lambda copy: copy.start())

    def wait(r, i, slot):
        # Same refs and semaphore as the start: the descriptor
        # identifies the transfer.
        block_dmas(r, i, slot, lambda copy: copy.wait())

    n_pages = pages_of(b)

    def live_row():
        q_pos = pos_ref[b]
        n_blocks = jax.lax.div(n_pages + block - 1, block)
        slot0 = state_ref[0]
        # Where the next row's first block lands.
        state_ref[0] = jax.lax.rem(slot0 + n_blocks, 2)

        # The V image's rows past this row's pages are zeros: whatever
        # an earlier, longer row left there goes now, so that phase 2
        # pairs its zero weights with exact zeros (the gather's w == 0
        # rows meet its finite padded gather) without masking the whole
        # image, a cap-sized temporary, in every program.
        def zero_page(j, carry):
            vimg[pl.ds(j * page, page), :] = jnp.zeros((page, width), dtype)
            return carry

        jax.lax.fori_loop(n_pages, state_ref[1], zero_page, 0)
        state_ref[1] = n_pages

        q2 = q_ref[0]  # [H, width], zero outside each head's own slot
        # The gather's own arithmetic either way: scores divided by
        # sqrt(Dh), or, for a block that states its multiplier,
        # multiplied by that.
        scale = jnp.asarray(
            dh ** 0.5 if score_scale is None else score_scale, dtype)
        # Dead pages' score columns are never stored: pre-fill the whole
        # row with the exact fp32 image of the gather's masked entries
        # (finfo(dtype).min upcast), so phase 2's softmax sees the same
        # padded row the gather's does and underflows them to +0.0.
        scores[...] = jnp.full(
            scores.shape, jnp.finfo(dtype).min, jnp.float32
        )

        if quantized:
            kv = width // dh
            # [K, width] 0/1 widening map: column c of a page row belongs
            # to kv head c // dh, so ``scales @ widen`` broadcasts each
            # (row, head) scale across its Dh columns exactly (one nonzero
            # product per output element) — Mosaic-friendly where
            # column-slice + concat is not.
            widen = (
                jax.lax.broadcasted_iota(jnp.int32, (kv, width), 0)
                == jax.lax.broadcasted_iota(jnp.int32, (kv, width), 1) // dh
            ).astype(jnp.float32)

        def one_page(slot, first, j, carry):
            kj = kbuf[slot, j - first]  # [page, width], pool dtype
            vj = vbuf[slot, j - first]
            if quantized:
                pg = tables_ref[b, j]
                sk = jax.lax.dot_general(
                    scale_k_ref[pg], widen,
                    dimension_numbers=(((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )  # [page, width] fp32, each scale repeated across its Dh
                sv = jax.lax.dot_general(
                    scale_v_ref[pg], widen,
                    dimension_numbers=(((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                # The gather's _kv_dequantize, elementwise-identical:
                # int8 -> fp32 (exact), * fp32 scale, round to dtype.
                kj = (kj.astype(jnp.float32) * sk).astype(dtype)
                vj = (vj.astype(jnp.float32) * sv).astype(dtype)
            scores[:, pl.ds(j * page, page)] = _page_scores(
                q2, kj, j * page, first_ref[b] if window else None, q_pos,
                scale, dtype=dtype, window=window,
                divide=score_scale is None)
            vimg[pl.ds(j * page, page), :] = vj
            return carry

        after = next_live(b)

        def one_block(i, carry):
            slot = jax.lax.rem(slot0 + i, 2)

            # What is fetched while this block computes: this row's next
            # block, or, behind its last, the next live row's first — so
            # that row starts on pages that arrived during this row's
            # phase 2.
            last = i + 1 == n_blocks
            then = jax.lax.select(last, after, b)

            @pl.when(then < rows)
            def _():
                start(then, jax.lax.select(last, 0, i + 1), 1 - slot)

            wait(b, i, slot)
            first = i * block
            jax.lax.fori_loop(
                first, jax.lax.min(first + block, n_pages),
                functools.partial(one_page, slot, first), 0)
            return carry

        jax.lax.fori_loop(0, n_blocks, one_block, 0)

        # Phase 2: the gather's epilogue on the assembled row. Same
        # function, same fp32 values, same reduced-axis length — the
        # weights round to dtype exactly as the gather's do.
        w = jax.nn.softmax(scores[...], axis=-1).astype(dtype)
        o_ref[0] = jax.lax.dot_general(
            w, vimg[...],
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).astype(o_ref.dtype)  # [H, width]; head slots extracted outside

    @pl.when(b == 0)
    def _():
        # Nothing is in flight yet: the first live row's first block.
        # And the V image may hold anything, up to the cap.
        state_ref[0] = 0
        state_ref[1] = scores.shape[1] // page
        first = next_live(-1)

        @pl.when(first < rows)
        def _():
            start(first, 0, 0)

    @pl.when(n_pages == 0)
    def _():
        # A dead row: no copy, no scratch, no phase 2.
        o_ref[0] = jnp.zeros(o_ref.shape[1:], o_ref.dtype)

    pl.when(n_pages > 0)(live_row)


def _decode_blocked_kernel(tables_ref, pos_ref, layer_ref, *rest,
                           page: int, width: int, dh: int, dtype,
                           score_scale: float | None, window: int = 0):
    """The blocked form (module docstring): one program per sequence;
    the row's K pages, then its V pages, stream block by block through
    ``buf`` [2, block, page, width], two slots of landing pads with a
    DMA semaphore each (``sems`` [2]). A row makes as many K blocks as V
    blocks, so every row's first K block lands in slot 0: that is how a
    block started behind one row's last V block is waited for by the
    next. ``scores`` [H, S_cap] fp32 and ``wts`` [H, S_cap] in the
    compute dtype hold the row between the phases, ``acc`` [H, width]
    fp32 the V contraction's sum."""
    first_ref = None
    if window:
        first_ref, *rest = rest
    q_ref, k_hbm, v_hbm, o_ref, buf, scores, wts, acc, sems = rest

    b = pl.program_id(0)
    rows = pl.num_programs(0)
    block = buf.shape[1]
    pages_of = functools.partial(_pages_of, pos_ref, first_ref,
                                 page=page, window=window)

    def block_dmas(hbm, r, i, slot, act):
        """Start (or wait for) the copies of block i of row r's pages
        in ``hbm`` into landing-pad slot ``slot``: its live pages only."""
        first = i * block

        def one(j, carry):
            act(pltpu.make_async_copy(
                hbm.at[layer_ref[0], tables_ref[r, j]],
                buf.at[slot, j - first], sems.at[slot]))
            return carry

        jax.lax.fori_loop(
            first, jax.lax.min(first + block, pages_of(r)), one, 0)

    def start(hbm, r, i, slot):
        block_dmas(hbm, r, i, slot, lambda copy: copy.start())

    def wait(hbm, r, i, slot):
        # Same refs and semaphore as the start: the descriptor
        # identifies the transfer.
        block_dmas(hbm, r, i, slot, lambda copy: copy.wait())

    n_pages = pages_of(b)

    def live_row():
        q_pos = pos_ref[b]
        n_blocks = jax.lax.div(n_pages + block - 1, block)
        q2 = q_ref[0]  # [H, width], zero outside each head's own slot
        scale = jnp.asarray(
            dh ** 0.5 if score_scale is None else score_scale, dtype)
        # Dead pages' score columns are never stored (the whole form's
        # pre-fill, for the same softmax over the same padded row).
        scores[...] = jnp.full(
            scores.shape, jnp.finfo(dtype).min, jnp.float32)
        after = _next_live(pos_ref, b, rows)

        def pages_in(i, one_page):
            first = i * block
            jax.lax.fori_loop(first, jax.lax.min(first + block, n_pages),
                              functools.partial(one_page, first), 0)

        def k_block(i, carry):
            slot = jax.lax.rem(i, 2)
            last = i + 1 == n_blocks

            # Fetched while this block computes: the next K block or,
            # behind the last, the first V block, which then streams
            # under the softmax.
            @pl.when(jnp.logical_not(last))
            def _():
                start(k_hbm, b, i + 1, 1 - slot)

            @pl.when(last)
            def _():
                start(v_hbm, b, 0, 1 - slot)

            wait(k_hbm, b, i, slot)

            def one_page(first, j, carry):
                scores[:, pl.ds(j * page, page)] = _page_scores(
                    q2, buf[slot, j - first], j * page,
                    first_ref[b] if window else None, q_pos, scale,
                    dtype=dtype, window=window, divide=score_scale is None)
                return carry

            pages_in(i, one_page)
            return carry

        jax.lax.fori_loop(0, n_blocks, k_block, 0)

        # The gather's softmax on the assembled row: same function, same
        # fp32 values, same reduced-axis length, the same rounding.
        wts[...] = jax.nn.softmax(scores[...], axis=-1).astype(dtype)
        acc[...] = jnp.zeros(acc.shape, jnp.float32)

        def v_block(i, carry):
            slot = jax.lax.rem(n_blocks + i, 2)
            last = i + 1 == n_blocks

            # Behind the last V block the next live row's first K block
            # (into slot 0: 1 - slot there), so no row but the first
            # waits for its first page.
            @pl.when(jnp.logical_not(last))
            def _():
                start(v_hbm, b, i + 1, 1 - slot)

            @pl.when(jax.lax.bitwise_and(last, after < rows))
            def _():
                start(k_hbm, after, 0, 1 - slot)

            wait(v_hbm, b, i, slot)

            def one_page(first, j, carry):
                # Live pages only: a dead page's weights are zeros, and
                # what its landing pad holds is anything.
                acc[...] += jax.lax.dot_general(
                    wts[:, pl.ds(j * page, page)], buf[slot, j - first],
                    dimension_numbers=(((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                return carry

            pages_in(i, one_page)
            return carry

        jax.lax.fori_loop(0, n_blocks, v_block, 0)
        o_ref[0] = acc[...].astype(o_ref.dtype)  # head slots taken outside

    @pl.when(b == 0)
    def _():
        # Nothing is in flight yet: the first live row's first K block.
        first = _next_live(pos_ref, -1, rows)

        @pl.when(first < rows)
        def _():
            start(k_hbm, first, 0, 0)

    @pl.when(n_pages == 0)
    def _():
        # A dead row: no copy, no scratch, no phase 2.
        o_ref[0] = jnp.zeros(o_ref.shape[1:], o_ref.dtype)

    pl.when(n_pages > 0)(live_row)


# Jitted for its trace cache alone, and inlined so that the enclosing
# program is what it was: every decode-window program of a bucket (three
# for each of seven buckets at the benchmark cell's start-up) calls this
# with the same shapes, and tracing the kernel's body is the costliest
# part of lowering one.
@functools.partial(jax.jit,
                   static_argnames=("interpret", "score_scale", "window",
                                    "blocked"),
                   inline=True)
def paged_decode_attention(q, pool_k, pool_v, tables, q_positions,
                           layer, *, scale_k=None, scale_v=None,
                           interpret: bool = False,
                           score_scale: float | None = None,
                           first=None, window: int = 0,
                           blocked: bool = False):
    """Decode attention over layer ``layer`` of a paged KV pool,
    block-table-indexed.

    q [B, H, Dh] (post-rotary, ONE query token per sequence, kv-major
    head layout: head h = kv_head * group + g — split_qkv's layout);
    pool_k/pool_v [L, P, page, K*Dh], the whole pool as PagedState holds
    it (kv heads merged K-major into the lane dim); ``layer`` an int32
    scalar, traced or not; tables [B, max_pages] int32; q_positions [B]
    int32 (row b attends key positions 0..q_positions[b], whose K/V —
    including the current token's — are already scattered; a NEGATIVE
    position marks a row that is not decoding: nothing of its table is
    read and its output is zeros).
    ``scale_k``/``scale_v`` ([L, P, page, K] fp32) mark an int8 pool:
    the kernel streams pages as stored and dequantizes in VMEM with the
    gather's exact formula. ``score_scale`` multiplies the scores
    (None = divide them by sqrt(Dh)). ``window`` > 0 bounds a query to
    the last ``window`` key positions, its own included, over a table
    whose first entry holds position ``first[b]`` ([B] int32, multiples
    of the page): row b attends key positions ``max(first[b],
    q_positions[b] - window + 1)`` to ``q_positions[b]``.
    ``blocked`` takes the blocked form (module docstring), for a table
    whose V image does not fit the scratch: the same scores and
    weights, the V contraction summed page by page.
    Returns [B, H, Dh], a live row's
    BIT-IDENTICAL to the gather path's decode attention. DMA cost and
    program time scale with the LIVE rows' page counts; the pool itself
    is neither sliced nor reshaped.
    """
    batch, h, dh = q.shape
    _, _, page, width = pool_k.shape
    _, max_pages = tables.shape
    if width % dh:
        raise ValueError(
            f"pool width {width} is not a whole number of heads of "
            f"{dh} (q is [B, H, Dh], the pools [L, P, page, K*Dh])"
        )
    if bool(window) != (first is not None):
        raise ValueError(
            "a window and the position of each row's first table entry "
            f"go together: window = {window}, first "
            f"{'given' if first is not None else 'not given'}")
    kv = width // dh
    group = h // kv
    s_cap = max_pages * page
    quantized = scale_k is not None
    if width % 128 and not interpret:
        raise ValueError(
            f"paged decode kernel needs kv_heads * d_head to be a "
            f"multiple of 128 (TPU DMA lane alignment), got {kv} x {dh} "
            f"= {width}; use paged_attention='gather' for this shape"
        )
    if page % 128 and not interpret:
        raise ValueError(
            f"paged decode kernel needs the page size to be a multiple "
            f"of 128 (page j's score columns land at lane offset "
            f"j * page, which Mosaic requires tile-aligned), got "
            f"{page}; use paged_attention='gather' for this pool"
        )
    if blocked and quantized:
        raise ValueError(
            "the paged decode kernel's blocked form has no int8 "
            "variant; use paged_attention='gather' for this pool")
    if not interpret and not (
            blocked_scratch_fits_vmem if blocked
            else decode_scratch_fits_vmem)(max_pages, page, width, h):
        raise ValueError(
            f"paged decode kernel scratch (fp32 scores [{h}, {s_cap}] "
            + ("and weights" if blocked
               else f"+ V image [{s_cap}, {width}]")
            + ") exceeds the VMEM budget; "
            f"use paged_attention='gather' for this pool geometry"
        )

    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    # Placed queries: head h = k'*group + g occupies columns
    # [k'*Dh, (k'+1)*Dh), zeros elsewhere — the full-width dot then
    # yields exactly the per-head scores (zero slots contribute nothing
    # in fp32 accumulation).
    head_slot = jnp.arange(h) // group                 # [H] kv index
    col_slot = jnp.arange(width) // dh                 # [width]
    place = (head_slot[:, None] == col_slot[None, :])  # [H, width]
    q2 = jnp.where(place[None], jnp.tile(q, (1, 1, kv)), 0)

    q_spec = pl.BlockSpec((1, h, width), lambda b, *_: (b, 0, 0))
    pool_specs = [
        pl.BlockSpec(memory_space=pl.ANY),  # pools stay in HBM;
        pl.BlockSpec(memory_space=pl.ANY),  # the kernel DMAs pages
    ]
    in_specs = [q_spec, *pool_specs]
    args = (q2, pool_k, pool_v)
    if blocked:
        block = blocked_block_pages(max_pages, page, width,
                                    pool_k.dtype.itemsize)
        scratch = [
            pltpu.VMEM((2, block, page, width), pool_k.dtype),
            pltpu.VMEM((h, s_cap), jnp.float32),   # score rows
            pltpu.VMEM((h, s_cap), q.dtype),       # their softmax
            pltpu.VMEM((h, width), jnp.float32),   # the V contraction
            pltpu.SemaphoreType.DMA((2,)),
        ]
        kernel = functools.partial(_decode_blocked_kernel, page=page,
                                   width=width, dh=dh, dtype=q.dtype,
                                   score_scale=score_scale)
    else:
        block = block_pages(max_pages, page, width, pool_k.dtype.itemsize)
        scratch = [
            pltpu.VMEM((2, block, page, width), pool_k.dtype),
            pltpu.VMEM((2, block, page, width), pool_v.dtype),
            pltpu.VMEM((h, s_cap), jnp.float32),   # phase-2 score rows
            pltpu.VMEM((s_cap, width), q.dtype),   # phase-2 V image
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((2,), jnp.int32),       # what a row leaves the next
        ]
        if quantized:
            # The layer's scale arrays ride whole in VMEM (a few MB) and
            # are indexed by page id — no extra DMA machinery.
            in_specs = [q_spec,
                        pl.BlockSpec(memory_space=pltpu.VMEM),
                        pl.BlockSpec(memory_space=pltpu.VMEM),
                        *pool_specs]
            args = (q2,
                    scale_k[layer[0]].astype(jnp.float32),
                    scale_v[layer[0]].astype(jnp.float32), pool_k, pool_v)
        kernel = functools.partial(
            _decode_flat_kernel, page=page, width=width, dh=dh,
            dtype=q.dtype, quantized=quantized, score_scale=score_scale,
        )
    scalars = (tables.astype(jnp.int32), q_positions.astype(jnp.int32),
               layer)
    if window:
        scalars += (first.astype(jnp.int32),)
        kernel = functools.partial(kernel, window=window)
    args = scalars + args

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(batch,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, h, width), lambda b, *_: (b, 0, 0)),
        scratch_shapes=scratch,
    )
    out_wide = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((batch, h, width), q.dtype),
        # Rows run one after another on one core: a row's last block
        # starts the next live row's first copies (scratch carries them).
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        # A capture tells the two forms apart by the call's name.
        name="paged_attention_blocked" if blocked else "paged_attention",
    )(*args)
    # Each head's own Dh-slot of the [H, width] output.
    out = jnp.take_along_axis(
        out_wide.reshape(batch, h, kv, dh),
        head_slot[None, :, None, None], axis=2,
    )[:, :, 0]
    return out
