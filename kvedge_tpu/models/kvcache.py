"""Paged KV cache: fixed-size pages + block tables for ragged serving.

The contiguous cache (models/decode.py) assumes one uniform-length batch.
Serving wants many sequences of different lengths sharing one memory pool —
the paged-attention scheme: K/V live in fixed-size **pages** out of a global
pool, and each sequence owns an ordered **block table** of page indices.
Admitting a sequence allocates pages; finishing one frees them; fragmentation
is bounded by the page size.

TPU-first shape discipline:

* The pool ``[L, P, page, K*Dh]`` and block tables ``[B, max_pages]`` are
  **static**; growth happens by table entries, never by reshaping arrays —
  nothing retraces as sequences come and go.
* The pool is never taken apart: the layer loop carries it whole, each
  layer scatters its new rows in place (``pool.at[l, page, offset]``) and
  reads either through the decode kernel, which DMAs pages out of
  ``pool[l]`` where they lie, or by one gather (``pool[l, tables]``) —
  batched ops XLA lowers to dynamic gathers, no per-sequence Python.
* Allocation policy (free lists, admission) is host-side Python — it is
  control plane, runs once per request, and must not live inside ``jit``.

Attention math (grouped einsum, fp32 softmax) matches decode.py exactly, so
paged and contiguous decoding agree bit-for-bit on the same prompts.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools

import jax
import jax.numpy as jnp

from kvedge_tpu.models.transformer import (
    TransformerConfig,
    _rmsnorm,
    _rotary,
    split_qkv,
    stacked_layer_params,
    tied_readout,
)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class PagedState:
    """Device-side paged cache state (a pytree; host policy lives in
    :class:`PagedKVCache`).

    The pools are stored as the decode kernel reads them: the kv heads
    merged K-major into the lane dimension, ``[L, P, page, K*Dh]``
    (column ``k * Dh + d`` is head k's element d), so a page is one
    contiguous 128-aligned DMA and no program relays a layer's slab
    before attending over it. What leaves the device keeps the
    per-head shape ``[L, n, page, K, Dh]`` (snapshots, swaps, the
    prefix cache's files): :func:`_gather_pages_impl` and
    :func:`_scatter_pages_impl` reshape at that boundary, where the
    arrays are a few pages.

    ``scale_k``/``scale_v`` ([L, P, page, K] fp32) exist only for an
    int8-quantized pool (``kv_dtype="int8"``): each token row of each
    kv head carries one scale — the standard per-token KV quantization
    — and the pools hold ``round(x / scale)`` int8. None (the bf16
    default) keeps every compiled program identical to the
    pre-quantization ones (None is an empty pytree node).
    """

    pool_k: jax.Array   # [L, P, page, K*Dh]
    pool_v: jax.Array   # [L, P, page, K*Dh]
    tables: jax.Array   # [B, max_pages] int32 page ids (0 = also a real page;
                        # entries past a sequence's page count are unused)
    lengths: jax.Array  # [B] int32 valid positions per sequence
    scale_k: "jax.Array | None" = None  # [L, P, page, K] fp32 (int8 only)
    scale_v: "jax.Array | None" = None
    # A patterned block's state of the second kind (models/hybrid.py
    # fresh_recurrent): per recurrent layer and SLOT (not bucket row:
    # it cannot be rebuilt from the host as tables are) the float32
    # state and the conv's tail, and the window's expert-pick counters.
    # The pool then holds the full attention layers only ("attention"
    # in the pattern; the "window" layers' live below). None otherwise.
    recurrent: "dict | None" = None
    # A block with layers bound to a window (``"window"`` in
    # ``cfg.layer_pattern``) keeps those layers' keys and values in a
    # pool of their own, ``[window layers, P_w, page, K*Dh]``, under a
    # table of their own, ``[B, cap]``: a row holds the pages that carry
    # its last ``cfg.attention_window`` positions and has given the
    # rest back, so its table starts at position ``win_first[b]`` (a
    # multiple of the page) and not at 0. The pool above then holds the
    # full layers only. None otherwise, and every program is the
    # program it was.
    win_pool_k: "jax.Array | None" = None  # [Lw, P_w, page, K*Dh]
    win_pool_v: "jax.Array | None" = None
    win_tables: "jax.Array | None" = None  # [B, cap] int32 page ids
    win_first: "jax.Array | None" = None   # [B] int32 positions

    @property
    def page_size(self) -> int:
        return self.pool_k.shape[2]

    @property
    def max_seq(self) -> int:
        return self.tables.shape[1] * self.page_size


_KV_QMAX = 127.0


def _kv_quantize(x):
    """Per-row symmetric int8: x [..., Dh] -> (int8 [..., Dh],
    fp32 scale [...]). amax/127 scaling; the epsilon floor keeps an
    all-zero row (fresh pool) from dividing by zero."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.maximum(amax / _KV_QMAX, 1e-8)
    q = jnp.round(xf / scale[..., None])
    return q.astype(jnp.int8), scale


def _kv_dequantize(q, scale, dtype):
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


class PagedCacheError(RuntimeError):
    pass


_PAGED_KERNEL_AUTO_MIN_SEQ = 2048


def _use_paged_kernel(cfg: TransformerConfig, page_size: int,
                      width: int, max_pages: int | None = None) -> str:
    """Resolve ``cfg.paged_attention`` at trace time (page_size/width/
    max_pages are static pool-shape facts under jit), for one kind of
    attention layer, to the kernel's form or "" for the gather: "whole"
    where the table's scores and V image fit the kernel's scratch,
    "blocked" where only its scores do and the V pages stream through
    in blocks (ops/paged_attention.decode_scratch_form: a shape takes
    one form, and no option chooses). So a block whose full layers'
    table does not fit whole (64 heads over 8,192 positions of a
    1,024-wide pool) keeps working in proportion to live tokens, where
    it used to fall to the gather, which reads the cap; its window
    layers, a few pages a row, take the whole form beside it.
    In detail: ``max_pages`` is the width of that kind's table (a
    full layer's spans ``max_seq``, a window layer's the window and one
    advance: PagedKVCache.window_cap), so a block with both kinds
    settles each apart, by its own scratch. "auto" picks the
    Pallas block-table kernel where it wins: TPU, long-context caps
    (max_seq >= 2048), page_size % 128 == 0 (each page's score columns
    land at lane offset j * page in the kernel's phase-2 scratch, which
    Mosaic requires tile-aligned — and a copy is a page: at 16-token
    pages 4 KB copies lost to XLA's bulk gather when they were made
    one at a time, and were not measured again since they are made in
    blocks), kv_heads*d_head % 128 == 0 (TPU DMA lane
    alignment; MHA at one kv head takes the gather), and the two-phase
    kernel's VMEM scratch fitting the budget (over-cap pools route to
    the gather). The kernel works in proportion to live tokens: a row
    that is not decoding is handed over at position -1 and costs
    nothing, live pages arrive in blocks with the next live row's
    first block already in flight (ops/paged_attention.py). For a live
    row it stages the gather's own rounded score rows
    and runs the same softmax and one flat V contraction, so its
    scores and weights are the gather's bit for bit on any backend;
    the V contraction is the same products summed in fp32, but as one
    [H, S] x [S, K*Dh] dot where the gather has a [G, S] x [S, Dh] dot
    per kv head, and a backend may order those two sums differently.
    Compiled for the chip it does not: chip_smoke.py FAILS unless, on
    the TPU, the kernel's live rows at the 209M widths equal the
    gather's in every bit (query positions 127/128/129, 1023/1024 on
    the edge of a fetched block, and 2047, between two dead rows that
    must come back as zeros) and an "auto" and a
    "gather" server return the same greedy streams — so there "auto"
    is a routing choice, not a numerics one, for as long as that check
    passes (TPU v5 lite, PR 31: 0 of 6,144 live outputs differ). That is
    heads of 64, whose score scale 1/8 divides exactly; at heads of 128
    (24 query / 2 kv: the benchmark's widths) the two paths round the
    division by sqrt(128) at different points and 10,407 of 15,360
    outputs differ by one or two bf16 steps on the chip, before the
    pool was carried whole as after (PR 25, PERF.md section 6). XLA:CPU
    does order them differently: under the interpreter one output of
    1,536, a sum that cancels to 4e-6, lands one bf16 step from the
    gather's in one pinned case (ROADMAP D6).
    Short-context pools keep the gather only because the kernel's DMA
    loop has nothing to win there. Either choice can be forced with
    "kernel"/"gather"; cfg is a static jit argument, so changing the
    choice retraces rather than silently reusing a cached program.
    Multi-process (slice) pools never auto-pick the kernel: it has no
    partitioning rule, so tracing it over a sharded pool would poison
    the first decode step on a real slice — SlicePagedKVCache
    additionally pins its cfg to "gather" so even a forced "kernel"
    cannot reach a sharded trace. What this function cannot see at
    trace time — one process whose arrays span several chips — is
    settled before the pool is built, by
    :func:`settle_paged_attention`."""
    if cfg.paged_attention == "gather":
        return ""
    from kvedge_tpu.ops.paged_attention import decode_scratch_form

    if max_pages is None:
        max_pages = -(-cfg.max_seq // max(page_size, 1))
    form = decode_scratch_form(max_pages, page_size, width, cfg.n_heads)
    if cfg.paged_attention == "kernel":
        return form or "whole"  # what fits neither refuses at the call
    if (jax.default_backend() == "tpu"
            and jax.process_count() == 1
            and cfg.max_seq >= _PAGED_KERNEL_AUTO_MIN_SEQ
            and page_size % 128 == 0
            and width % 128 == 0):
        return form
    return ""


def settle_paged_attention(cfg: TransformerConfig,
                           params) -> TransformerConfig:
    """``cfg`` as a pool over ``params`` may trace it: the half of the
    kernel-or-gather decision that depends on where arrays live, which
    :func:`_use_paged_kernel` cannot observe under jit.

    Mosaic lowers a kernel only into a program for ONE device. A jitted
    decode program runs on every device its arguments are placed on,
    sharded or merely replicated alike, and for more than one the
    lowering refuses: "Mosaic kernels cannot be automatically
    partitioned" (tests/test_chip_compile.py compiles both placements
    for a described v5e:2x2 and gets exactly that). So over params that
    span several devices "auto" means the gather, and a forced "kernel"
    is refused here, at construction, like the forced kernel whose
    scales do not fit (PagedKVCache.__init__) — not downgraded. The
    repair that would let the kernel run there is a shard_map over the
    mesh's ``model`` axis on KV heads (ROADMAP S9)."""
    spans = max(
        (len(leaf.sharding.device_set)
         for leaf in jax.tree_util.tree_leaves(params)
         if hasattr(leaf, "sharding")),
        default=1,
    )
    if spans == 1 or cfg.paged_attention == "gather":
        return cfg
    if cfg.paged_attention == "kernel":
        raise ValueError(
            f"paged_attention='kernel' is forced, but the params are "
            f"placed over {spans} devices and the Pallas decode kernel "
            f"cannot be partitioned; use 'auto' or 'gather' on a mesh "
            f"of more than one chip")
    return dataclasses.replace(cfg, paged_attention="gather")


class PagedKVCache:
    """Host-side pool manager wrapping a :class:`PagedState`.

    ``slots`` is the max concurrent sequences (the batch dim of every step).
    Unused slots keep ``lengths == 0`` and are masked out of attention.
    """

    # A window can be waited for (:meth:`await_window`) and a first
    # token picked and kept on the device (:meth:`pick_first`) with no
    # read through the pool: the serving layer waits with its work lock
    # released. A slice cache reads through its op stream, under the
    # lock like every op: False there.
    unlocked_reads = True

    def __init__(self, cfg: TransformerConfig, *, slots: int, pages: int,
                 page_size: int = 16, max_pages_per_seq: int | None = None,
                 kv_dtype: str = "", min_bucket: int = 0,
                 window_advance: int = 0):
        from kvedge_tpu.models.moe import warn_if_train_serve_divergence

        cfg.validate()
        warn_if_train_serve_divergence(cfg)
        if kv_dtype not in ("", "int8"):
            raise ValueError(
                f"kv_dtype must be '' (the compute dtype) or 'int8', "
                f"got {kv_dtype!r}"
            )
        self.cfg = cfg
        self.slots = slots
        self.num_pages = pages
        self.page_size = page_size
        # Bucketed compile cache (capacity scaling): host bookkeeping is
        # always ``slots``-sized, but the DEVICE batch dim (tables,
        # lengths — the only arrays that carry it; the page pool is
        # slot-count-independent) is ``self.bucket``: a power of two
        # from ``min_bucket`` up, capped at ``slots``. jit keys on array
        # shapes, so every program compiles once per bucket and
        # admissions within a bucket ride the dead-row masks with zero
        # retraces; :meth:`set_bucket` steps the batch dim at quiescent
        # points. ``min_bucket=0`` disables bucketing (bucket pinned to
        # ``slots`` — the pre-bucketing behavior, and REQUIRED for the
        # slice cache, whose broadcast op stream fixes payload shapes
        # at ``slots``).
        if min_bucket < 0:
            raise ValueError(f"min_bucket must be >= 0, got {min_bucket}")
        self.min_bucket = min(min_bucket, slots) if min_bucket else 0
        self.bucket = self.bucket_for(0)
        self.max_pages_per_seq = (
            max_pages_per_seq or -(-cfg.max_seq // page_size)
        )
        # int8 KV (kv_dtype="int8"): pools hold per-row-quantized int8
        # with fp32 scales riding alongside (PagedState docstring) —
        # the HBM bill per cached token drops ~2x (Dh bytes + 4 vs
        # 2*Dh), which doubles servable context/slots on the same pool
        # budget. Quantization is LOSSY (bounded by one int8 step per
        # row amax): decode tokens may diverge from the bf16 pool at
        # near-ties, which is why it is an explicit operator opt-in
        # ([payload] serving_kv_dtype), never a default.
        self.kv_quantized = kv_dtype == "int8"
        if self.kv_quantized and cfg.paged_attention == "kernel":
            from kvedge_tpu.ops.paged_attention import scales_fit_vmem

            if not scales_fit_vmem(pages * page_size, cfg.kv_heads):
                # A forced kernel that cannot run must refuse at
                # construction, not silently degrade to the cap-sized
                # gather at the long-context shapes the force exists
                # for.
                raise ValueError(
                    "paged_attention='kernel' with int8 KV needs both "
                    "scale arrays to fit the kernel's VMEM budget; "
                    f"this pool ({pages} pages x {page_size} x "
                    f"{cfg.kv_heads} kv heads) exceeds it — shrink the "
                    "pool/page geometry or use 'auto'/'gather'"
                )
        dtype = jnp.int8 if self.kv_quantized else jnp.dtype(cfg.dtype)
        shape = (cfg.kv_layers, pages, page_size, cfg.kv_heads * cfg.d_head)
        # The window layers' pool (PagedState.win_pool_k; None of this
        # where the block has no such layer). ``window_advance`` is the
        # most positions a row moves on between two givings-back (a
        # prefill chunk, a decode window; 0 = up to ``max_seq``), which
        # with the window bounds the pages a row ever holds:
        # :attr:`window_cap`, the width of its table. The pool holds
        # every slot's cap, so it never holds an admission back.
        self.window = cfg.attention_window if cfg.window_layers else 0
        self.window_cap = 0
        self.num_window_pages = 0
        if self.window:
            if self.kv_quantized:
                raise ValueError(
                    "kv_dtype = 'int8' with 'window' layers in "
                    "layer_pattern: the window layers' pool is held in "
                    "the compute dtype only")
            self.window_cap = min(
                self.max_pages_per_seq,
                -(-(self.window + (window_advance or cfg.max_seq))
                  // page_size) + 1)
            self.num_window_pages = slots * self.window_cap
        self._wfree: list[int] = list(range(self.num_window_pages))[::-1]
        self._wpages_of: dict[int, list[int]] = {}
        self._wfirst = [0] * slots  # position of each table's first page
        self._host_wtables = [[0] * self.window_cap for _ in range(slots)]
        self.window_pages_released = 0
        self.state = self._init_state(shape, dtype)
        # What the window programs counted of the routed experts' picks
        # (a patterned block): [all, on held experts, each held expert,
        # held experts with a pick by layer and step], summed at harvest
        # from what each window returns beside its tokens
        # (``_picks_of``: the windows not harvested yet).
        self.expert_picks = None
        self._picks_of: dict = {}
        # The (layer, held expert, step) matrices those windows read: a
        # window whose program walks the touched experts
        # (hybrid.walks_touched, static for a compiled program) reads
        # the triples it counted as touched, any other every held
        # expert of every routed layer at every step.
        self.expert_reads = 0
        if cfg.layer_pattern:
            import numpy as _np

            self.expert_picks = _np.zeros(3 + cfg.held_experts, _np.int64)
        # A phase of the serving layer's clock around the state reset
        # of an admission (``admit/state_reset``), when it has one, and
        # around giving window pages back after a prefill chunk
        # (``admit/window_release``).
        self.reset_phase = contextlib.nullcontext
        self.window_phase = contextlib.nullcontext
        self._free: list[int] = list(range(pages))[::-1]  # pop() -> lowest last
        self._pages_of: dict[int, list[int]] = {}
        self._host_tables = [
            [0] * self.max_pages_per_seq for _ in range(slots)
        ]
        self._host_lengths = [0] * slots
        # Page reference counts (prefix sharing): a page may be held by
        # several slots' tables at once (read-only shared prompt
        # prefixes) and/or by the serving layer's prefix registry
        # (retain_pages). A page returns to the free list only when its
        # count reaches zero. Pages on the free list carry count 0.
        self._refs = [0] * pages
        # Optional callback (serving layer): registry pins live outside
        # every request's worst-case reservation, so an allocation that
        # finds the free list short asks the owner to reclaim pins
        # before failing. Signature: pressure_relief(needed) -> bool.
        self.pressure_relief = None
        # Device-resident last-token carry for the overlap pipeline:
        # (produced tokens [n, slots], n) of the most recent
        # dispatch_window*. Window N+1's input row is carry[0][n-1] —
        # sliced on device, so dispatching N+1 never forces N's result
        # to the host.
        self._carry = None
        # Memoized host->device uploads for the small per-dispatch
        # operand rows (active mask, per-row caps, stop tokens): in
        # pipeline steady state these repeat verbatim window after
        # window, and re-uploading them cost a device_put per operand
        # per dispatch — pure boundary overhead the rung-16 model
        # charges to R. Keyed by the operand's raw bytes; cleared with
        # the carries (drop_carry) so a revived/reformed pool never
        # reuses arrays from torn-down device state.
        self._dev_memo: dict = {}
        # Each slot's pending first token, on the device ([slots] int32;
        # :meth:`pick_first` writes a row's, a window's input takes it
        # where the host states ``FIRST_ON_DEVICE``). Scratch between
        # requests: a row is read only after its pick wrote it.
        self._firsts = self._init_firsts()

    def _init_firsts(self):
        return jnp.zeros((self.slots,), jnp.int32)

    def _dev_const(self, kind: str, arr):
        """Device copy of a small host operand, reused while its bytes
        are unchanged (see ``_dev_memo``). ``arr`` must be a concrete
        ndarray — callers normalize dtype first so equal content hits
        regardless of the caller's input type."""
        key = arr.tobytes()
        hit = self._dev_memo.get(kind)
        if hit is not None and hit[0] == key:
            return hit[1]
        dev = jnp.asarray(arr)
        self._dev_memo[kind] = (key, dev)
        return dev

    def _init_state(self, shape, dtype) -> PagedState:
        """Fresh zeroed device state. The slice-serving subclass
        (runtime/sliceserve.py) overrides this to create GLOBAL arrays
        over a multi-host mesh; everything above is host bookkeeping
        that neither knows nor cares where the pools live."""
        def scale():
            # Two DISTINCT arrays: the jitted steps donate the whole
            # state, and donating one buffer twice is an error.
            return (jnp.zeros(shape[:-1] + (self.cfg.kv_heads,),
                              jnp.float32)
                    if self.kv_quantized else None)

        return PagedState(
            pool_k=jnp.zeros(shape, dtype),
            pool_v=jnp.zeros(shape, dtype),
            tables=jnp.zeros((self.bucket, self.max_pages_per_seq),
                             jnp.int32),
            lengths=jnp.zeros((self.bucket,), jnp.int32),
            scale_k=scale(),
            scale_v=scale(),
            recurrent=self._init_recurrent(),
            **self._init_window(shape, dtype),
        )

    def _init_window(self, shape, dtype) -> dict:
        """The window layers' zeroed pool and table (nothing where the
        block has no such layer)."""
        if not self.window:
            return {}
        wshape = (self.cfg.window_layers, self.num_window_pages) + shape[2:]
        return dict(
            win_pool_k=jnp.zeros(wshape, dtype),
            win_pool_v=jnp.zeros(wshape, dtype),
            win_tables=jnp.zeros((self.bucket, self.window_cap), jnp.int32),
            win_first=jnp.zeros((self.bucket,), jnp.int32),
        )

    def _init_recurrent(self):
        if not self.cfg.layer_pattern:
            return None
        from kvedge_tpu.models.hybrid import fresh_recurrent

        return fresh_recurrent(self.cfg, self.slots)

    # ---- bucketed device batch dim --------------------------------------

    def bucket_for(self, n: int) -> int:
        """The smallest bucket that holds ``n`` rows: powers of two from
        ``min_bucket`` up, capped at ``slots`` (the top bucket is
        ``slots`` itself even when that is not a power of two). With
        bucketing disabled the only bucket is ``slots``."""
        if not self.min_bucket:
            return self.slots
        b = self.min_bucket
        while b < n and b < self.slots:
            b *= 2
        return min(b, self.slots)

    def rows_in_use(self) -> int:
        """1 + the highest admitted slot (0 when empty): the smallest
        device batch dim that still covers every live row — what the
        serving layer's bucket step-down must not shrink below."""
        return max(self._pages_of, default=-1) + 1

    def set_bucket(self, n: int) -> None:
        """Resize the DEVICE batch dim to bucket ``n`` (a quiescent-point
        operation: no window carry may be in flight — the serving
        loop collapses its pipeline to a boundary first). The page pool
        never moves; only tables/lengths rebuild from the host mirrors,
        so the resize is a host->device upload of two small arrays and
        the next program traces once for the new shape. Any device
        carry is dropped (the pipeline restarts from host tokens, which
        the overlap path already proves bit-identical)."""
        if n == self.bucket:
            return
        if not self.min_bucket:
            raise PagedCacheError(
                "bucketing is disabled on this cache (min_bucket=0); "
                "the device batch dim is pinned to slots"
            )
        if n != self.bucket_for(n) or n < self.min_bucket or n > self.slots:
            raise PagedCacheError(
                f"bucket {n} is not on this cache's ladder "
                f"(powers of two from {self.min_bucket} capped at "
                f"{self.slots})"
            )
        top = max(self._pages_of, default=-1)
        if top >= n:
            raise PagedCacheError(
                f"slot {top} is admitted but bucket {n} holds rows "
                f"0..{n - 1} — release or migrate it first"
            )
        self.drop_carry()
        self.bucket = n
        self._sync()

    # ---- control plane (host) -------------------------------------------

    def free_pages(self) -> int:
        return len(self._free)

    def free_window_pages(self) -> int:
        return len(self._wfree)

    def window_pages_held(self, slot: int) -> int:
        """Pages of the window layers' pool in ``slot``'s table."""
        return len(self._wpages_of.get(slot, ()))

    def page_accounting(self) -> dict:
        """Full-pool page census for the conservation audit
        (``serving_debug_pages`` and the chaos soak's invariant 1).
        Every page is either on the free list (ref 0) or referenced by
        some holder — a slot table or a registry pin, both of which live
        inside slot page lists and therefore inside ``live``.
        Conservation holds iff
        ``free + live == pages_total`` with no duplicate free entries,
        no negative refcounts, and no page both free and referenced.
        Pure host bookkeeping: no device work, safe at any boundary."""
        free_set = set(self._free)
        window = {}
        if self.window:
            # The window layers' pool: a page is on its free list or in
            # exactly one row's table (nothing shares a window page).
            held = [p for pages in self._wpages_of.values() for p in pages]
            window = {
                "window_free": len(self._wfree),
                "window_live": len(set(held)),
                "window_pages_total": self.num_window_pages,
                "window_free_dup": len(self._wfree) - len(set(self._wfree)),
                "window_held_dup": len(held) - len(set(held)),
                "window_free_live": len(set(self._wfree) & set(held)),
                "window_over_cap": sum(
                    1 for pages in self._wpages_of.values()
                    if len(pages) > self.window_cap),
            }
        return {
            **window,
            "free": len(self._free),
            "live": sum(1 for r in self._refs if r > 0),
            "pages_total": self.num_pages,
            "free_dup": len(self._free) - len(free_set),
            "neg_refs": sum(1 for r in self._refs if r < 0),
            "free_live": sum(
                1 for p in free_set if self._refs[p] > 0
            ),
        }

    def occupancy(self) -> dict:
        """Cheap pool-occupancy gauges for the rung-25 timeline ring:
        unlike :meth:`page_accounting` (a full census for the
        conservation audit) this is O(slots) attribute reads, safe to
        sample at every quiescent boundary. ``hbm_bytes_used`` prices
        live pages at the pool's per-page K+V footprint (scale slabs
        included for int8 pools)."""
        live = self.num_pages - len(self._free)
        page_bytes = 0
        state = self.state
        if state is not None and state.pool_k is not None:
            for arr in (state.pool_k, state.pool_v):
                page_bytes += arr.nbytes // max(1, self.num_pages)
            if state.scale_k is not None:
                for arr in (state.scale_k, state.scale_v):
                    page_bytes += arr.nbytes // max(1, self.num_pages)
        return {
            "pages_total": self.num_pages,
            "pages_live": live,
            "pages_free": len(self._free),
            "slots_admitted": len(self._pages_of),
            "bucket": self.bucket,
            "hbm_bytes_used": live * page_bytes,
        }

    def is_admitted(self, slot: int) -> bool:
        return slot in self._pages_of

    def slot_pages(self, slot: int) -> list[int]:
        """The slot's current page list (a copy — callers registering
        prefix pins must not alias the live allocation list)."""
        return list(self._pages_of[slot])

    def slot_length(self, slot: int) -> int:
        """The slot's committed host-mirror length: positions
        ``[0, slot_length)`` hold valid K/V for tokens 0..length-1 of
        prompt + generated, which is what makes finish-time prefix
        registration exact."""
        if slot not in self._pages_of:
            raise PagedCacheError(f"slot {slot} is not admitted")
        return self._host_lengths[slot]

    def page_refcount(self, page: int) -> int:
        """Current reference count of ``page`` (host bookkeeping only —
        the chaos soak's refcount-aware conservation check reads it)."""
        return self._refs[page]

    def retain_pages(self, pages: list[int]) -> None:
        """Take an extra reference on ``pages`` (the serving layer's
        prefix registry pins cached-prefix pages with this so releasing
        the request that wrote them does not free them)."""
        for page in pages:
            if self._refs[page] < 1:
                raise PagedCacheError(
                    f"page {page} is free — cannot retain K/V that no "
                    "longer exists"
                )
            self._refs[page] += 1

    def release_pages(self, pages: list[int]) -> None:
        """Drop a reference taken with :meth:`retain_pages`."""
        for page in pages:
            self._unref(page)

    def _unref(self, page: int) -> None:
        self._refs[page] -= 1
        if self._refs[page] < 0:
            raise PagedCacheError(f"page {page} over-released")
        if self._refs[page] == 0:
            self._free.append(page)

    def admit(self, slot: int, prompt_len: int,
              shared_pages: tuple[int, ...] = ()) -> None:
        """Reserve pages for a prompt landing in ``slot``.

        ``shared_pages`` (prefix sharing) prepends already-written,
        read-only pages holding the prompt's cached prefix: the slot's
        table starts with them (reference counts bumped — they are
        never written by this slot, because prefill starts at the
        shared token count and decode writes past the prompt), and only
        the remainder allocates from the free list.
        """
        if slot in self._pages_of:
            raise PagedCacheError(f"slot {slot} already admitted")
        if slot >= self.bucket:
            raise PagedCacheError(
                f"slot {slot} is outside the current device bucket "
                f"({self.bucket} rows) — step the bucket up first"
            )
        total = -(-prompt_len // self.page_size) or 1
        needed = total - len(shared_pages)
        if needed < 0:
            raise PagedCacheError(
                f"{len(shared_pages)} shared pages exceed the prompt's "
                f"{total}-page footprint"
            )
        if total > self.max_pages_per_seq:
            raise PagedCacheError(
                f"prompt of {prompt_len} needs {total} pages > "
                f"max_pages_per_seq={self.max_pages_per_seq}"
            )
        if needed > len(self._free) and not (
            self.pressure_relief and self.pressure_relief(needed)
        ):
            raise PagedCacheError(
                f"pool exhausted: need {needed} pages, {len(self._free)} free"
            )
        self.retain_pages(list(shared_pages))
        fresh = []
        for _ in range(needed):
            page = self._free.pop()
            self._refs[page] += 1
            fresh.append(page)
        self._pages_of[slot] = list(shared_pages) + fresh
        row = self._host_tables[slot]
        for i, page in enumerate(self._pages_of[slot]):
            row[i] = page
        self._host_lengths[slot] = prompt_len
        if self.window:
            # No window page yet: a prefill chunk brings its own
            # (prefill_chunk), a resumed row its snapshot's (swapin_slot).
            self._wpages_of[slot] = []
            self._wfirst[slot] = 0
        self._sync()
        if self.state.recurrent is not None and "ssm" in self.state.recurrent:
            # Whoever had the slot left its state there (release needs
            # nothing): a row starts from zeros, or from what
            # swapin_row writes over them.
            with self.reset_phase():
                self._device_reset_row(slot)

    def _device_reset_row(self, slot: int) -> None:
        """Device seam: zero one slot's recurrent state."""
        from kvedge_tpu.models.hybrid import reset_rows

        self.state = dataclasses.replace(
            self.state, recurrent=reset_rows(
                self.state.recurrent, jnp.asarray(slot, jnp.int32)))

    def grow(self, slot: int) -> bool:
        """Ensure the slot can hold one more token, allocating a page at a
        page boundary. Returns True iff a page was allocated — the caller
        (:meth:`step`) must :meth:`_sync` before the next device step when
        any table changed; stale device tables would scatter the new token
        into another sequence's page."""
        return self.grow_to(slot, 1)

    def grow_to(self, slot: int, n: int) -> bool:
        """Ensure the slot can hold ``n`` more tokens (the device-side
        decode window's scatters land at positions length..length+n-1),
        allocating pages as needed. Early allocation is safe by the
        serving layer's admission discipline: every request's worst-case
        page budget is reserved up front, so pages pulled here were
        already accounted for. Returns True iff any page was allocated
        (caller must :meth:`_sync`)."""
        if slot not in self._pages_of:
            raise PagedCacheError(f"slot {slot} is not admitted")
        length = self._host_lengths[slot]
        pages = self._pages_of[slot]
        grew = False
        while length + n > len(pages) * self.page_size:
            if len(pages) == self.max_pages_per_seq:
                raise PagedCacheError(f"slot {slot} hit max_pages_per_seq")
            if not self._free and not (
                self.pressure_relief and self.pressure_relief(1)
            ):
                raise PagedCacheError("pool exhausted mid-decode")
            page = self._free.pop()
            self._refs[page] += 1
            pages.append(page)
            self._host_tables[slot][len(pages) - 1] = page
            grew = True
        if self.window:
            # What lies wholly behind the window of the next query goes
            # first, so a row that advances by no more than
            # ``window_advance`` between two calls never passes its cap.
            grew |= bool(self._window_trim(slot, length))
            grew |= self._window_grow(slot, length + n)
        return grew

    # ---- the window layers' pages (host) --------------------------------

    def _window_grow(self, slot: int, upto: int) -> bool:
        """Pages for the window layers' keys at positions below
        ``upto``, appended to the slot's table. True iff any was."""
        pages = self._wpages_of[slot]
        first, grew = self._wfirst[slot], False
        while first + len(pages) * self.page_size < upto:
            if len(pages) == self.window_cap:
                raise PagedCacheError(
                    f"slot {slot} would hold more than its cap of "
                    f"{self.window_cap} window pages: it advanced by "
                    "more than the window_advance the pool was sized for")
            page = self._wfree.pop()
            self._host_wtables[slot][len(pages)] = page
            pages.append(page)
            grew = True
        return grew

    def _window_trim(self, slot: int, next_pos: int) -> int:
        """Give back the slot's window pages on which every position
        lies more than ``window - 1`` behind ``next_pos``, the next
        query's: no query of the row sees them again. Nothing is
        copied: the table shifts and its first position moves on.
        Returns the pages given back (the caller syncs)."""
        pages = self._wpages_of[slot]
        drop = min(len(pages),
                   max(0, (next_pos - self.window + 1) // self.page_size
                       - self._wfirst[slot] // self.page_size))
        if not drop:
            return 0
        self._wfree.extend(pages[:drop])
        del pages[:drop]
        self._wfirst[slot] += drop * self.page_size
        self._host_wtables[slot] = (
            pages + [0] * (self.window_cap - len(pages)))
        self.window_pages_released += drop
        return drop

    def release_window_pages(self, slots) -> int:
        """Give back, for each of ``slots`` (rows whose harvested
        window moved them on), the window pages its next query no
        longer sees; one upload of the table if any went. Returns the
        pages given back."""
        if not self.window:
            return 0
        dropped = 0
        for slot in slots:
            if slot in self._wpages_of:
                dropped += self._window_trim(slot, self._host_lengths[slot])
        if dropped:
            self._sync_window()
        return dropped

    def release(self, slot: int) -> None:
        """Finish a sequence: drop its references (pages free at 0)."""
        if slot not in self._pages_of:
            raise PagedCacheError(f"slot {slot} is not admitted")
        for page in self._pages_of.pop(slot):
            self._unref(page)
        self._host_tables[slot] = [0] * self.max_pages_per_seq
        self._host_lengths[slot] = 0
        if self.window:
            self._wfree.extend(self._wpages_of.pop(slot))
            self._host_wtables[slot] = [0] * self.window_cap
            self._wfirst[slot] = 0
        self._sync()

    def _sync(self) -> None:
        b = self.bucket
        self.state = dataclasses.replace(
            self.state,
            tables=jnp.asarray(self._host_tables[:b], jnp.int32),
            lengths=jnp.asarray(self._host_lengths[:b], jnp.int32),
        )
        if self.window:
            self._sync_window()

    def _sync_window(self) -> None:
        """The window layers' table and each row's first position, from
        the host's mirrors."""
        b = self.bucket
        self.state = dataclasses.replace(
            self.state,
            win_tables=jnp.asarray(self._host_wtables[:b], jnp.int32),
            win_first=jnp.asarray(self._wfirst[:b], jnp.int32),
        )

    # ---- data plane (device) --------------------------------------------

    def snapshot_pages(self, ids: list[int]):
        """DEVICE copies of the K/V data in ``ids``: two fresh arrays
        ``[L, n, page, K, Dh]`` (one gather per pool). The split that
        lets the periodic dump hold the serving lock only for the
        gather dispatch: the fresh arrays are immune to the decode
        step's buffer donation, so the (much slower) device->host
        transfer happens OUTSIDE the lock without racing a step that
        would invalidate the pool buffers.

        An int8 pool snapshots AS STORED (int8 values + fp32 scales —
        a 2-or-4 tuple): dequantizing on device would make the
        device->host transfer ~4x the bytes the pool actually holds,
        on exactly the configs int8 exists to relieve.
        :meth:`snapshot_to_host` dequantizes host-side, so the
        persistence FILE format stays kv_dtype-agnostic — a dump taken
        from an int8 server loads into a bf16 one and vice versa
        (write_pages re-quantizes on the way in), at the cost of one
        extra quantization round trip whose error is bounded by one
        int8 step of the row's amax."""
        return _gather_pages_impl(
            self.state, jnp.asarray(ids, jnp.int32), self.cfg.kv_heads
        )

    @staticmethod
    def snapshot_to_host(snapshot):
        """Host fp32 ``(k, v)`` from a :meth:`snapshot_pages` tuple —
        the transfer (compact, as-stored) then the dequant (host-side,
        cheap numpy)."""
        import numpy as np

        if len(snapshot) == 2:
            k, v = (np.asarray(x, np.float32) for x in snapshot)
            return k, v
        k, v, sk, sv = (np.asarray(x) for x in snapshot)
        return (k.astype(np.float32) * sk[..., None].astype(np.float32),
                v.astype(np.float32) * sv[..., None].astype(np.float32))

    def read_pages(self, ids: list[int]):
        """Host fp32 copies of the K/V data in ``ids``: two arrays
        ``[L, n, page, K, Dh]`` (dequantized for int8 pools). One
        gather + transfer per array — the prefix-persistence dump path
        (models/serving.py)."""
        return self.snapshot_to_host(self.snapshot_pages(ids))

    def write_pages(self, ids: list[int], k_vals, v_vals) -> None:
        """Scatter K/V data ([L, n, page, K, Dh]) into pages ``ids`` —
        ONE batched device update per pool (a per-page loop would copy
        the whole pool once per page). The persistence load path; the
        caller owns allocation/refcounts for these pages. Values arrive
        unquantized (see snapshot_pages); an int8 pool re-quantizes
        them per row here."""
        if self.kv_quantized:
            k_q, k_s = _kv_quantize(jnp.asarray(k_vals, jnp.float32))
            v_q, v_s = _kv_quantize(jnp.asarray(v_vals, jnp.float32))
            arrays = (k_q, v_q, k_s, v_s)
        else:
            dtype = self.state.pool_k.dtype
            arrays = (jnp.asarray(k_vals, dtype), jnp.asarray(v_vals, dtype))
        self.state = _scatter_pages_impl(
            self.state, jnp.asarray(ids, jnp.int32), arrays
        )

    # ---- preemptive swap (scheduler layer, SERVING.md rung 17) ----------

    def _device_swapout(self, ids: list[int]):
        """Device seam: gather pages ``ids`` AS STORED (fresh arrays,
        immune to the decode steps' buffer donation). The slice cache
        overrides this to broadcast an OP_SWAPOUT so followers replay
        the gather in the totally-ordered op stream."""
        return _gather_pages_impl(
            self.state, jnp.asarray(ids, jnp.int32), self.cfg.kv_heads
        )

    def swapout_pages(self, ids: list[int]) -> tuple:
        """Host copies of pages ``ids`` EXACTLY as the pool stores them
        (2-tuple ``(k, v)`` for a bf16 pool, 4-tuple with the fp32
        scale slabs for int8) — the preemption snapshot. Unlike
        :meth:`read_pages`/:meth:`write_pages` (the persistence pair,
        which dequantize/re-quantize and accept one int8 step of
        error), a swap round trip must be BIT-identical: a preempted
        request's resumed token stream is pinned equal to the
        never-preempted one, so the pool bytes go to host verbatim and
        come back verbatim via :meth:`swapin_pages`."""
        import numpy as np

        return tuple(np.asarray(x) for x in self._device_swapout(ids))

    def _device_swapin(self, ids: list[int], arrays: tuple) -> None:
        """Device seam: scatter as-stored ``arrays`` into pages ``ids``
        (one batched update per pool). Slice cache broadcasts."""
        self.state = _scatter_pages_impl(
            self.state, jnp.asarray(ids, jnp.int32),
            tuple(jnp.asarray(a) for a in arrays),
        )

    def swapin_pages(self, ids: list[int], arrays: tuple) -> None:
        """Write a :meth:`swapout_pages` snapshot back into pages
        ``ids`` (freshly allocated by the resume path's re-admission —
        the caller owns allocation/refcounts). Verbatim: no dtype
        conversion happens in either direction."""
        if len(arrays) != (4 if self.kv_quantized else 2):
            raise PagedCacheError(
                f"swap snapshot carries {len(arrays)} arrays; this "
                f"pool needs {4 if self.kv_quantized else 2} "
                "(kv_dtype mismatch between swap-out and swap-in?)"
            )
        self._device_swapin(ids, arrays)

    def row_state_bytes(self) -> int:
        """Bytes of one slot's recurrent state (0 where none is kept):
        what :meth:`swapout_row` adds to a swap snapshot."""
        rec = self.state.recurrent
        n = 0
        if rec is not None and "ssm" in rec:
            n = (rec["ssm"].nbytes + rec["conv"].nbytes) // self.slots
        if self.window:
            # at most a cap's worth of window pages travels with a row
            pool = self.state.win_pool_k
            n += 2 * self.window_cap * (pool.nbytes // pool.shape[1])
        return n

    def swapout_row(self, slot: int) -> tuple:
        """Host copies of slot ``slot``'s recurrent state exactly as
        stored (``()`` for a block that keeps none): what travels with
        :meth:`swapout_pages`' arrays when a row is preempted or
        journaled, and comes back through :meth:`swapin_slot`. A row is
        never resumed on a zero state: its state is its past, as its
        pages are."""
        import numpy as np

        out = ()
        rec = self.state.recurrent
        if rec is not None and "ssm" in rec:
            from kvedge_tpu.models.hybrid import gather_rows

            out = tuple(np.asarray(a) for a in gather_rows(
                rec, jnp.asarray(slot, jnp.int32)))
        if self.window:
            # The window layers' pages that hold the row's positions up
            # to its length, as stored, and where its table starts: a
            # row comes back to the window it left with.
            first = self._wfirst[slot]
            n = -(-(self._host_lengths[slot] - first) // self.page_size)
            ids = jnp.asarray(self._wpages_of[slot][:max(n, 0)], jnp.int32)
            out += (np.asarray(first, np.int32),) + tuple(
                np.asarray(a) for a in _gather_window_pages(
                    self.state, ids, self.cfg.kv_heads))
        return out

    def swapin_slot(self, slot: int, arrays: tuple,
                    skip_pages: int = 0) -> None:
        """Write a swap snapshot back into freshly admitted ``slot``:
        :meth:`swapout_pages`' arrays into its pages from
        ``skip_pages`` on and, where the snapshot carries them,
        :meth:`swapout_row`'s into its recurrent state."""
        n = 4 if self.kv_quantized else 2
        self.swapin_pages(self.slot_pages(slot)[skip_pages:], arrays[:n])
        rec = self.state.recurrent
        row = 2 if rec is not None and "ssm" in rec else 0
        if len(arrays) != n + row + (3 if self.window else 0):
            raise PagedCacheError(
                f"swap snapshot carries {len(arrays)} arrays; this block "
                f"needs {n + row + (3 if self.window else 0)}: a row's "
                "pages, its recurrent state where it keeps one and its "
                "window layers' pages where it has them (a row cannot "
                "resume on a zero state)")
        if row:
            from kvedge_tpu.models.hybrid import scatter_rows

            self.state = dataclasses.replace(
                self.state, recurrent=scatter_rows(
                    rec, jnp.asarray(slot, jnp.int32),
                    *(jnp.asarray(a) for a in arrays[n:n + row])))
        if self.window:
            first, keys, values = arrays[n + row:]
            self._wfree.extend(self._wpages_of[slot])
            self._wpages_of[slot] = []
            self._wfirst[slot] = int(first)
            self._window_grow(
                slot, int(first) + keys.shape[1] * self.page_size)
            self.state = _scatter_window_pages(
                self.state,
                jnp.asarray(self._wpages_of[slot], jnp.int32),
                (jnp.asarray(keys), jnp.asarray(values)))
            self._sync_window()

    def cow_page(self, slot: int, index: int) -> int | None:
        """Copy-on-write divergence for table position ``index`` of
        ``slot``: when the page there is SHARED (refcount > 1 — a
        cached-prefix page other holders still read), copy its K/V into
        a fresh page on device and repoint only this slot's table at
        the copy, so the slot's upcoming writes (the partial last page
        of a shared prefix fills in during prefill/decode) cannot
        corrupt co-holders. Returns the new page id, or None when the
        slot already owns the page exclusively (no copy, no cost).

        The copy is a single device-side page copy (``_device_cow`` —
        the slice cache overrides it to broadcast an OP_COWP so
        followers replay the same copy in the totally-ordered op
        stream); no bytes cross the host. The source keeps the
        remaining holders' references; the copy starts at refcount 1
        owned by the slot. Allocation may invoke pressure relief —
        safe at the admission call site because the matched registry
        entry's pages are also held by this slot's table, so evicting
        the entry cannot free the source mid-copy."""
        if slot not in self._pages_of:
            raise PagedCacheError(f"slot {slot} is not admitted")
        pages = self._pages_of[slot]
        if not 0 <= index < len(pages):
            raise PagedCacheError(
                f"slot {slot} holds {len(pages)} pages — no index {index}"
            )
        src = pages[index]
        if self._refs[src] <= 1:
            return None
        if not self._free and not (
            self.pressure_relief and self.pressure_relief(1)
        ):
            raise PagedCacheError("pool exhausted: no page for COW copy")
        dst = self._free.pop()
        self._refs[dst] += 1
        self._device_cow(src, dst)
        pages[index] = dst
        self._host_tables[slot][index] = dst
        self._unref(src)
        self._sync()
        return dst

    def _device_cow(self, src: int, dst: int) -> None:
        """Device seam: copy page ``src``'s slabs into ``dst`` (K, V,
        and int8 scale slabs when quantized). Slice cache broadcasts."""
        self.state = _cow_page_impl(
            self.state,
            jnp.asarray(src, jnp.int32), jnp.asarray(dst, jnp.int32),
        )

    def allocate_pinned_page(self) -> int:
        """Take one page off the free list with refcount 1, owned by the
        caller (the persistence loader's registry pins — there is no
        slot whose reservation covers them). Raises when the pool is
        exhausted; the loader checks ``free_pages`` first and never
        invokes pressure relief (loading cache must not evict cache)."""
        if not self._free:
            raise PagedCacheError("pool exhausted: no page to pin")
        page = self._free.pop()
        self._refs[page] += 1
        return page

    def prefill(self, params: dict, slot: int, prompt) -> jax.Array:
        """Feed a 1D prompt into ``slot`` (after :meth:`admit`).

        Prefill is per-sequence (prompts arrive one request at a time in
        serving); the batched hot path is :meth:`step`. Returns the
        last-position logits [V].
        """
        (prompt_len,) = prompt.shape
        if prompt_len != self._host_lengths[slot]:
            raise PagedCacheError(
                f"admit({slot}) reserved {self._host_lengths[slot]} positions, "
                f"prefill got {prompt_len}"
            )
        return self.prefill_chunk(params, slot, prompt, 0)

    def prefill_chunk(self, params: dict, slot: int, tokens,
                      offset: int) -> jax.Array:
        """Feed ``tokens`` into ``slot`` at absolute position ``offset``.

        The chunked-prefill granule (models/serving.py): a long prompt
        lands in fixed-size chunks so (a) XLA compiles one program per
        CHUNK length, not per prompt length — a bounded compile surface
        under arbitrary operator traffic — and (b) the serving loop can
        run batched decode steps for in-flight requests between chunks
        instead of blocking every co-tenant for one admission's whole
        prefill. Causality across chunks is free: earlier chunks'
        K/V are already scattered into the slot's pages, and the gather
        masks on absolute positions. Returns the chunk's last-position
        logits [V] (only the final chunk's matter to the caller).
        """
        (n,) = tokens.shape
        if offset + n > self._host_lengths[slot]:
            raise PagedCacheError(
                f"chunk [{offset}, {offset + n}) exceeds slot {slot}'s "
                f"admitted length {self._host_lengths[slot]}"
            )
        if not self.window:
            return self._device_prefill(params, tokens, slot, offset)
        # The window layers' pages for this chunk, then, behind it, the
        # pages its last query no longer sees: a prompt of any length
        # holds no more than the cap.
        if self._window_grow(slot, offset + n):
            self._sync_window()
        logits = self._device_prefill(params, tokens, slot, offset)
        with self.window_phase():
            if self._window_trim(slot, offset + n):
                self._sync_window()
        return logits

    def _device_prefill(self, params, tokens, slot: int, offset: int):
        """Device seam: run the prefill kernel and advance state."""
        logits, self.state = _paged_prefill(
            params, self.state, tokens, slot, self.cfg, offset
        )
        return logits

    def _step_slots(self, active) -> list[int]:
        """Admitted slots this step advances. ``active`` (bool [slots])
        restricts to the caller's in-flight set — the serving loop
        passes it so a HALF-PREFILLED co-tenant (admitted, tables live,
        chunks still landing) is neither grown, scattered into, nor
        length-advanced by interleaved decode steps. None = every
        admitted slot (the pre-chunking behavior)."""
        if active is None:
            return list(self._pages_of)
        return [s for s in self._pages_of if active[s]]

    @staticmethod
    def _active_array(state: PagedState, active):
        import numpy as _np

        if active is None:
            return state.lengths > 0
        return jnp.asarray(_np.asarray(active, bool))

    def step(self, params: dict, tokens, active=None) -> jax.Array:
        """One batched decode step over every active slot.

        ``tokens`` is [slots] int32; inactive slots' outputs are garbage
        (masked sequences) and their lengths do not advance. Returns
        logits [slots, V].
        """
        slots = self._step_slots(active)
        grew = False
        for slot in slots:
            grew |= self.grow(slot)
        if grew:
            # Device tables are stale only when a page was allocated; the
            # steady-state token step pays no host->device re-upload.
            self._sync()
        logits = self._device_step(params, tokens, active)
        # The device state already advanced active slots' lengths (the
        # active mask in _paged_decode_step); just mirror on the host —
        # tables only change in admit/grow/release, which sync themselves.
        for slot in slots:
            self._host_lengths[slot] += 1
        return logits

    def _device_step(self, params, tokens, active):
        """Device seam: one batched decode step over current state."""
        logits, self.state = _paged_decode_step(
            params, self.state, tokens, self.cfg,
            self._active_array(self.state, active),
        )
        return logits

    # ---- overlapped (double-buffered) windows ---------------------------

    def _window_caps(self, n_steps: int, steps_left) -> "np.ndarray":
        import numpy as _np

        if steps_left is None:
            return _np.full((self.bucket,), n_steps, _np.int32)
        caps = _np.minimum(
            _np.asarray(steps_left, _np.int64), n_steps
        )
        return _np.maximum(caps, 0).astype(_np.int32)

    def dispatch_window(self, params, tokens, n_steps: int, active=None,
                        steps_left=None, stop_tokens=None):
        """Enqueue ``n_steps`` greedy decode steps as ONE program,
        WITHOUT forcing its result.

        The per-token host round trip is the paged path's tax: page
        tables only change at page boundaries, so between boundaries the
        decode loop is a pure device-side recurrence — scan it. Pages
        for the whole window are allocated up front (legal because the
        serving layer reserves each request's worst-case budget at
        admission) and the greedy argmax feeds back inside the scan.
        ``tokens`` is [slots] int32 (each active slot's pending token);
        row ``i`` of the result is the token produced by feeding row
        ``i-1`` (row 0 fed ``tokens``). The produced tokens come back
        as an unforced device value (JAX async dispatch — the program
        is queued, the host keeps running) to be forced later with
        :meth:`harvest_window`. Because the device stream executes
        in order, a second dispatch may be enqueued before the first is
        harvested; ``tokens=None`` feeds the previous dispatch's final
        token row (the device-resident carry), so no host round trip
        separates back-to-back windows. The choice is per row: an
        entry of ``tokens`` of -1 takes that row of the carry, one of
        ``FIRST_ON_DEVICE`` the row's pending first token
        (:meth:`pick_first`, which the host has not read either) and
        every other entry is fed as given, joined on the device
        (:func:`_join_carry`), so a row that sat out the window in
        flight (a newcomer) enters the next one beside rows whose
        tokens the host has not seen.

        ``steps_left`` [slots] int32 is each row's remaining decode
        budget (None = no cap): row b advances ``min(n_steps,
        steps_left[b])`` steps and then freezes on device (see
        :func:`_paged_decode_window_capped_impl`), which is what makes
        a speculatively dispatched window safe. Pages and host lengths
        advance by each row's TRUE advance, never the full window.

        ``stop_tokens`` [slots] int32 (None = no stops) rides the scan
        as per-row stop-token detection; the harvested result carries
        the packed ``[fin, stop_at]`` bookkeeping rows (rung 23).
        """
        import numpy as _np

        slots = self._step_slots(active)
        caps = self._window_caps(n_steps, steps_left)
        if stop_tokens is None:
            stop_tokens = _np.full(self.bucket, -1, _np.int32)
        grew = False
        for slot in slots:
            if caps[slot] > 0:
                grew |= self.grow_to(slot, int(caps[slot]))
        if grew:
            self._sync()
        toks = self._device_window_dispatch(
            params, tokens, n_steps, active, caps, stop_tokens
        )
        for slot in slots:
            self._host_lengths[slot] += int(caps[slot])
        return toks

    def dispatch_window_sampled(self, params, tokens, n_steps: int,
                                active, key_data, base_steps, temps,
                                top_ps, sampled_mask, steps_left=None,
                                stop_tokens=None):
        """Mixed greedy/sampled :meth:`dispatch_window` (same carry,
        cap, growth, and stop-token discipline; see
        :func:`_paged_decode_window_sampled_capped_impl` for the
        key-schedule argument). All per-row sampling inputs are host
        arrays ([B]-shaped; ``key_data`` [B, 2] uint32)."""
        import numpy as _np

        slots = self._step_slots(active)
        caps = self._window_caps(n_steps, steps_left)
        if stop_tokens is None:
            stop_tokens = _np.full(self.bucket, -1, _np.int32)
        grew = False
        for slot in slots:
            if caps[slot] > 0:
                grew |= self.grow_to(slot, int(caps[slot]))
        if grew:
            self._sync()
        toks = self._device_window_sampled_dispatch(
            params, tokens, n_steps, active, key_data, base_steps,
            temps, top_ps, sampled_mask, caps, stop_tokens,
        )
        for slot in slots:
            self._host_lengths[slot] += int(caps[slot])
        return toks

    def lower_decode_window(self, params, n_steps: int):
        """Lower, without running, the capped greedy window this pool
        dispatches, at its live shapes and placements.

        What the device is given is otherwise invisible from outside:
        "auto" attention, the interpret switch and XLA's own sharding
        all resolve at trace time. chip_smoke.py reads the result's
        ``as_text()`` for the Pallas kernel's ``tpu_custom_call`` and
        compiles it for the collectives of a sharded pool. Shapes and
        placements only (an uncommitted array, like freshly synced
        tables, goes where jit would put it), so a state the decode
        thread has since donated is no obstacle.
        """
        a_params, a_state = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(
                a.shape, a.dtype,
                sharding=a.sharding if a.committed else None),
            (params, self.state),
        )

        def row(dtype):
            return jax.ShapeDtypeStruct((self.bucket,), dtype)

        return _paged_decode_window_capped.lower(
            a_params, a_state, row(jnp.int32), self.cfg, n_steps,
            row(jnp.bool_), row(jnp.int32), row(jnp.int32),
        )

    def pick_first(self, logits, slot: int, sampling=None):
        """Pick a request's first token from its last prefill chunk's
        ``logits`` [V] on the device, and keep it there: greedy, or
        for ``sampling`` (raw key data [2] uint32, temperature, top_p)
        with ``fold_in(seed, 0)`` and the nucleus filter, as
        ``decode.generate`` picks token 0. Nothing is read: the token
        goes into the row of first tokens at ``slot``, from where the
        row's first window takes it (an input entry of
        ``FIRST_ON_DEVICE``), and is returned as a device scalar for
        the host to read once a window that carried the row is
        harvested. One program a sampling mode: it depends on the
        vocabulary's width and on ``slots``."""
        import numpy as _np

        if sampling is None:
            self._firsts, token = _pick_first_greedy(
                logits, self._firsts, _np.int32(slot))
        else:
            key_data, temperature, top_p = sampling
            self._firsts, token = _pick_first_sampled(
                logits, self._firsts, _np.int32(slot),
                _np.asarray(key_data, _np.uint32),
                _np.float32(temperature), _np.float32(top_p))
        return token

    def await_window(self, handle) -> None:
        """Block until the device has finished a dispatched window and
        its block is on the host, touching nothing of the pool: the
        serving loop calls this with its work lock released, and then
        :meth:`harvest_window`, which no longer waits, with the lock
        held."""
        import numpy as _np

        _np.asarray(handle)
        picks, _ = self._picks_of.get(id(handle), (None, None))
        if picks is not None:
            _np.asarray(picks)

    def harvest_window(self, handle):
        """Force a dispatched window's tokens to the host
        ([n_steps + 2, slots] int32: the produced tokens plus the
        packed ``[fin, stop_at]`` finish-bookkeeping rows). Blocks
        until the device finishes that window — ideally while a later
        window is already queued behind it (the overlap)."""
        import numpy as _np

        picks, reads = self._picks_of.pop(id(handle), (None, None))
        if picks is not None:
            picks = _np.asarray(picks)
            self.expert_picks += picks
            self.expert_reads += int(picks[-1]) if reads is None else reads
        return _np.asarray(handle)

    def _note_window(self, out, n_steps: int):
        """Keep a dispatched window's carry; a patterned block's window
        returns its pick counters beside its tokens, kept until the
        tokens are harvested."""
        if self.state.recurrent is not None:
            from kvedge_tpu.models import hybrid

            toks, picks = out
            # What the window read, where that is not what it touched.
            self._picks_of[id(toks)] = (
                picks, None if hybrid.walks_touched(self.cfg, self.bucket)
                else n_steps * hybrid.expert_reads_per_step(self.cfg))
        else:
            toks = out
        self._carry = (toks, n_steps)
        return toks

    def _host_tokens(self, tokens) -> "np.ndarray":
        """A window's input row as the host states it: [bucket] int32,
        an entry of -1 standing for the carry's (``None``: all of
        them) and one of ``FIRST_ON_DEVICE`` for the row's pending
        first token."""
        import numpy as _np

        if tokens is None:
            return _np.full((self.bucket,), -1, _np.int32)
        return _np.asarray(tokens, _np.int32)

    def _with_carry(self, tokens):
        """``tokens`` (on the device) with every entry of -1 replaced
        by the carry's (the last token row of the window dispatched
        before this one) and every ``FIRST_ON_DEVICE`` by the row's
        pending first token: neither may the host have read yet."""
        if self._carry is None:
            raise PagedCacheError(
                "no window in flight to carry tokens from — the first "
                "window of a pipeline must pass explicit tokens"
            )
        block, n = self._carry
        return _join_carry(block, tokens, self._firsts, n - 1)

    def _window_tokens(self, tokens, memo: str):
        """Device seam's input row: the host's, joined on the device
        with the pending first tokens (the first window of a pipeline)
        or with those and the carry. A row the host states whole goes
        through the first join too: the window programs then take
        their tokens from a program's result whoever states them, and
        are lowered once a window length, not once more for an array
        fresh from the host (which a warm-up whose newcomers' tokens
        are all on the device would never have dispatched). A pipeline
        whose rows stand states the same row window after window
        (every live row the carry's), and it rides the memo."""
        host = self._host_tokens(tokens)
        dev = self._dev_const(memo, host)
        if (host != -1).all():
            return _join_firsts(dev, self._firsts)
        return self._with_carry(dev)

    def drop_carry(self) -> None:
        """Forget the device-resident carry (recovery: a revived pool
        restarts its pipeline from host tokens)."""
        self._carry = None
        self._picks_of.clear()
        # The operand memo holds device arrays from the same stream
        # the carries rode — a revived pool must re-upload.
        self._dev_memo.clear()

    def _device_window_dispatch(self, params, tokens, n_steps: int,
                                active, steps_left, stop_tokens):
        """Device seam: enqueue a capped greedy window (no read)."""
        import numpy as _np

        toks_in = self._window_tokens(tokens, "w_toks")
        # Steady-state pipelining redispatches with identical mask/
        # caps/stops rows — the memo turns three device_puts per
        # window into zero (host-path elimination, rung 26).
        act = (self._active_array(self.state, active)
               if active is None else
               self._dev_const("w_act", _np.asarray(active, bool)))
        out, self.state = _paged_decode_window_capped(
            params, self.state, toks_in, self.cfg, n_steps,
            act,
            self._dev_const("w_caps",
                            _np.asarray(steps_left, _np.int32)),
            self._dev_const("w_stops",
                            _np.asarray(stop_tokens, _np.int32)),
        )
        return self._note_window(out, n_steps)

    def _device_window_sampled_dispatch(self, params, tokens,
                                        n_steps: int, active, key_data,
                                        base_steps, temps, top_ps,
                                        sampled_mask, steps_left,
                                        stop_tokens):
        """Device seam: enqueue a capped mixed window (no read)."""
        import numpy as _np

        toks_in = self._window_tokens(tokens, "ws_toks")
        # key_data/base_steps change every window (positions advance);
        # the mask/sampling-constant/cap rows repeat in steady state
        # and ride the memo like the greedy dispatch's.
        act = (self._active_array(self.state, active)
               if active is None else
               self._dev_const("ws_act", _np.asarray(active, bool)))
        out, self.state = _paged_decode_window_sampled_capped(
            params, self.state, toks_in, self.cfg, n_steps,
            act,
            jnp.asarray(_np.asarray(key_data, _np.uint32)),
            jnp.asarray(_np.asarray(base_steps, _np.int32)),
            self._dev_const("ws_temps",
                            _np.asarray(temps, _np.float32)),
            self._dev_const("ws_topps",
                            _np.asarray(top_ps, _np.float32)),
            self._dev_const("ws_smask",
                            _np.asarray(sampled_mask, bool)),
            self._dev_const("ws_caps",
                            _np.asarray(steps_left, _np.int32)),
            self._dev_const("ws_stops",
                            _np.asarray(stop_tokens, _np.int32)),
        )
        return self._note_window(out, n_steps)


# ---- jitted kernels ------------------------------------------------------

# Retrace telemetry: each impl body notes a trace event when Python
# actually runs it — which under jit happens ONLY at trace time (a jit
# cache hit replays the compiled program without touching the Python
# body). The capacity tests pin "admissions within a bucket cause zero
# recompiles" on the delta of this counter, and it covers the slice
# path too (runtime/sliceserve.py re-jits these same impl functions).
_TRACE_EVENTS: dict = {"total": 0}


def trace_count() -> int:
    """Total paged-program trace events since import (monotonic)."""
    return _TRACE_EVENTS["total"]


def _note_trace(name: str) -> None:
    _TRACE_EVENTS["total"] += 1
    _TRACE_EVENTS[name] = _TRACE_EVENTS.get(name, 0) + 1


def _per_head(pages, kv_heads: int):
    """[L, n, page, K*Dh] as it leaves the device: [L, n, page, K, Dh]."""
    return pages.reshape(*pages.shape[:3], kv_heads, -1)


def _merged(pages):
    """[L, n, page, K, Dh] as the pool holds it: [L, n, page, K*Dh]."""
    return pages.reshape(*pages.shape[:3], -1)


def _gather_pages_impl(state: PagedState, idx, kv_heads: int):
    """Pages ``idx`` of every pool slab, values as stored: a 2-or-4
    tuple of fresh ``[L, n, page, K, Dh]`` / ``[L, n, page, K]`` arrays
    (the pool's merged ``K*Dh`` lane dimension is split here, on the
    few pages gathered — what leaves the device keeps the per-head
    shape, PagedState docstring). Shared by the snapshot and swap-out
    seams (plain dispatch) and the slice cache's jitted replicated
    gather (runtime/sliceserve.py jits it with ``out_shardings``
    replicated, so the leader can read the swap snapshot host-side
    while followers hold the same bytes)."""
    out = [_per_head(state.pool_k[:, idx], kv_heads),
           _per_head(state.pool_v[:, idx], kv_heads)]
    if state.scale_k is not None:
        out += [state.scale_k[:, idx], state.scale_v[:, idx]]
    return tuple(out)


def _scatter_pages_impl(state: PagedState, idx, arrays) -> PagedState:
    """Scatter as-stored ``arrays`` (a :func:`_gather_pages_impl`
    tuple, ``[L, n, page, K, Dh]`` pools) into pages ``idx`` — ONE
    batched update per slab, no dtype conversion (the swap-in path's
    bit-exactness contract); the heads merge into the pool's lane
    dimension here. Shared by the single-host seams (swap-in,
    write_pages) and the slice cache's jitted donating scatter."""
    fields = dict(
        pool_k=state.pool_k.at[:, idx].set(_merged(arrays[0])),
        pool_v=state.pool_v.at[:, idx].set(_merged(arrays[1])),
    )
    if state.scale_k is not None:
        fields.update(
            scale_k=state.scale_k.at[:, idx].set(arrays[2]),
            scale_v=state.scale_v.at[:, idx].set(arrays[3]),
        )
    return dataclasses.replace(state, **fields)


@functools.partial(jax.jit, static_argnames=("kv_heads",))
def _gather_window_pages(state: PagedState, idx, kv_heads: int):
    """Pages ``idx`` of the window layers' pool, as stored: fresh
    ``[Lw, n, page, K, Dh]`` keys and values (:func:`_gather_pages_impl`
    for the second pool)."""
    return (_per_head(state.win_pool_k[:, idx], kv_heads),
            _per_head(state.win_pool_v[:, idx], kv_heads))


@functools.partial(jax.jit, donate_argnums=(0,))
def _scatter_window_pages(state: PagedState, idx, arrays) -> PagedState:
    """Write :func:`_gather_window_pages`' arrays into pages ``idx`` of
    the window layers' pool, verbatim."""
    return dataclasses.replace(
        state,
        win_pool_k=state.win_pool_k.at[:, idx].set(_merged(arrays[0])),
        win_pool_v=state.win_pool_v.at[:, idx].set(_merged(arrays[1])))


def _cow_page_impl(state: PagedState, src, dst) -> PagedState:
    """Copy page ``src`` into page ``dst`` across every pool slab — the
    COW divergence primitive. Bytes move device-to-device as stored
    (no dequantization, no reshape: a page is a page in either layout;
    int8 scale slabs ride along), so a diverged
    copy is bit-identical to its source. ``src``/``dst`` arrive as
    traced int32 scalars: the slice cache jits this impl once and
    every (src, dst) pair replays the same compiled program."""
    fields = dict(
        pool_k=state.pool_k.at[:, dst].set(state.pool_k[:, src]),
        pool_v=state.pool_v.at[:, dst].set(state.pool_v[:, src]),
    )
    if state.scale_k is not None:
        fields.update(
            scale_k=state.scale_k.at[:, dst].set(state.scale_k[:, src]),
            scale_v=state.scale_v.at[:, dst].set(state.scale_v[:, src]),
        )
    return dataclasses.replace(state, **fields)


def _gathered(pools, layer, tables, kv: int, dtype):
    """Layer ``layer``'s pages -> per-sequence contiguous
    [B, S_max, K, Dh] views (dequantized to ``dtype`` when the pool is
    int8): ONE gather per pool, ``pool[layer, tables]``, and what it
    gathered split back into heads — the pool itself is not touched."""
    pool_k, pool_v, scale_k, scale_v = pools
    batch, max_pages = tables.shape
    span = max_pages * pool_k.shape[2]

    def view(pool):  # [B, max_pages, page, K*Dh] -> [B, S_max, K, Dh]
        return pool[layer, tables].reshape(batch, span, kv, -1)

    k, v = view(pool_k), view(pool_v)
    if scale_k is not None:
        k = _kv_dequantize(
            k, scale_k[layer, tables].reshape(batch, span, kv), dtype)
        v = _kv_dequantize(
            v, scale_v[layer, tables].reshape(batch, span, kv), dtype)
    return k, v


def _scatter_rows(pool, scales, layer, page_idx, offset, rows):
    """Write token rows ``rows`` [N, K, Dh] into layer ``layer`` of the
    whole pool [L, P, page, K*Dh], IN PLACE on the layer loop's carry:
    row n lands at ``pool[layer, page_idx[n], offset[n]]``; a
    ``page_idx`` past the pool is dropped. ``scales`` non-None = int8
    pool: each row quantizes per (n, head) and its scale scatters
    alongside. Returns ``(pool, scales)``."""
    if scales is not None:
        rows, row_scale = _kv_quantize(rows)
        scales = scales.at[layer, page_idx, offset].set(
            row_scale, mode="drop")
    rows = rows.reshape(rows.shape[0], -1)
    return pool.at[layer, page_idx, offset].set(rows, mode="drop"), scales


def _scatter_token(pool, scales, layer, tables, lengths, kv_new, active,
                   first=None):
    """Write one [B, K, Dh] token row into each sequence's current page.

    The target of row b is page ``tables[b, lengths[b] // page]``,
    offset ``lengths[b] % page`` of ``pool[layer]`` (``first`` [B],
    where the table starts at that position and not at 0: entry
    ``(lengths[b] - first[b]) // page``). Inactive slots
    (empty table rows would alias page 0) are routed out-of-bounds and
    dropped. Returns ``(pool, scales)`` (:func:`_scatter_rows`).
    """
    pages, page = pool.shape[1:3]
    entry = (lengths if first is None else lengths - first) // page
    page_idx = jnp.take_along_axis(
        tables, entry[:, None], axis=1
    )[:, 0]                                   # [B] page ids
    page_idx = jnp.where(active, page_idx, pages)  # OOB => dropped
    return _scatter_rows(pool, scales, layer, page_idx, lengths % page,
                         kv_new)


def _paged_attend_layer(cfg: TransformerConfig, state: PagedState, x,
                        layer_params, layer, pools, q_positions, slot=None):
    """Shared block body. x: [B, Q, D]; q_positions: [B, Q] absolute
    positions of the new tokens. ``pools``, ``layer`` and ``slot`` are
    :func:`_paged_attention`'s; returns the block's output and the
    updated pools."""
    if cfg.n_experts:
        w_qkv, w_out, router, w_up, w_down, ln_attn, ln_mlp = layer_params
    else:
        w_qkv, w_out, w_up, w_down, ln_attn, ln_mlp = layer_params
    dtype = x.dtype
    attended, pools = _paged_attention(
        cfg, state, _rmsnorm(x, ln_attn), w_qkv, w_out, layer, pools,
        q_positions, slot)
    x = x + attended

    normed = _rmsnorm(x, ln_mlp)
    if cfg.n_experts:
        from kvedge_tpu.models.moe import routed_ffn_block

        x = x + routed_ffn_block(
            normed, router, w_up, w_down, top_k=cfg.expert_top_k
        )
    else:
        x = x + jax.nn.gelu(normed @ w_up.astype(dtype)) @ w_down.astype(dtype)
    return x, pools


def _paged_attention(cfg: TransformerConfig, state: PagedState, normed,
                     w_qkv, w_out, layer, pools, q_positions, slot=None,
                     w_gate=None, window: int = 0, qk_norm=None):
    """The attention mixer of every paged program, over normed
    activations [B, Q, D]; q_positions: [B, Q] absolute
    positions of the new tokens. ``pools`` is the WHOLE pool
    ``(pool_k, pool_v, scale_k, scale_v)`` as the layer loop carries
    it, ``layer`` this block's index into it: the block writes its new
    rows at ``[layer, page, offset]`` and reads ``pool[layer]`` where
    it lies; it returns the mixer's output (what the residual stream
    adds) and the updated pools. ``state`` supplies tables
    and lengths only. ``slot`` non-None = single-sequence
    prefill (B == 1 view of that slot).
    ``cfg.rotary`` false leaves q and k as projected (no positional
    encoding; the rotary base is ``cfg.rope_theta``). ``window`` > 0
    makes this a layer bound to a window: ``pools`` is then the window
    layers' pool, read and written through ``state.win_tables``, whose
    row b starts at position ``state.win_first[b]``; q and k are always
    rotated; and a query sees the last ``window`` key positions, its
    own included (``ops.paged_attention.visible``, the one statement
    of the mask, here and in the kernel).
    ``cfg.attention_multiplier`` non-zero scales the scores
    by it and not by 1/sqrt(Dh). ``w_gate`` [D, H*Dh], where the layer
    has one, gates what was attended, channel by channel, before the
    output projection: ``(sigmoid(normed @ w_gate) * attended) @
    w_out``."""
    batch, q_len, _ = normed.shape
    h, kv, dh = cfg.n_heads, cfg.kv_heads, cfg.d_head
    group = h // kv
    dtype = normed.dtype
    new_pool_k, new_pool_v, new_scale_k, new_scale_v = pools
    quantized = new_scale_k is not None
    page = new_pool_k.shape[2]
    if window:
        all_tables, all_first = state.win_tables, state.win_first
    else:
        all_tables, all_first = state.tables, None

    q, k, v = split_qkv(cfg, normed @ w_qkv.astype(dtype))
    if qk_norm is not None:
        q = _rmsnorm(q, qk_norm[0], cfg.norm_eps)
        k = _rmsnorm(k, qk_norm[1], cfg.norm_eps)

    def gated(attended):
        if w_gate is None:
            return attended
        gate = jax.nn.sigmoid(
            (normed @ w_gate.astype(dtype)).astype(jnp.float32))
        return attended * gate.astype(dtype)

    # rotary wants [T]-shaped positions; rows share a position vector only
    # in prefill (B=1). Decode rows each carry their own positions:
    # apply per-row via vmap.
    if not (cfg.rotary or window):
        pass
    elif slot is None:
        rot = jax.vmap(lambda t, p: _rotary(t[None], p, cfg.rope_theta)[0])
        q = rot(q, q_positions)
        k = rot(k, q_positions)
    else:
        q = _rotary(q, q_positions[0], cfg.rope_theta)
        k = _rotary(k, q_positions[0], cfg.rope_theta)

    if slot is None:
        tables, first, lengths = all_tables, all_first, state.lengths
        active = lengths > 0
        # One scatter per query offset (static q_len): row b's token i
        # lands at position lengths[b] + i.
        for i in range(q_len):
            new_pool_k, new_scale_k = _scatter_token(
                new_pool_k, new_scale_k, layer, tables, lengths + i,
                k[:, i], active, first,
            )
            new_pool_v, new_scale_v = _scatter_token(
                new_pool_v, new_scale_v, layer, tables, lengths + i,
                v[:, i], active, first,
            )
    else:
        # Prefill: scatter q_len rows of one slot at their ABSOLUTE
        # positions (chunked prefill passes an offset, so a chunk's
        # positions are offset..offset+q_len-1; the first/whole-prompt
        # chunk starts at zero).
        tables = all_tables[slot][None]
        first = None if all_first is None else all_first[slot][None]
        positions = q_positions[0]
        page_idx = tables[0][
            (positions if first is None else positions - first[0]) // page]
        offset = positions % page
        new_pool_k, new_scale_k = _scatter_rows(
            new_pool_k, new_scale_k, layer, page_idx, offset, k[0])
        new_pool_v, new_scale_v = _scatter_rows(
            new_pool_v, new_scale_v, layer, page_idx, offset, v[0])

    # int8 pools use the kernel too (pages stream AS STORED — half the
    # DMA bytes — with scales folded in post-dot), as long as both
    # whole scale arrays fit the kernel's VMEM budget. "auto" routes
    # oversized pools to the gather; a FORCED kernel that cannot run
    # refuses loudly (PagedKVCache.__init__ rejects it up front; this
    # trace-time raise is the defense for direct kernel callers). Only
    # traces the kernel could actually take refuse: prefill (slot set,
    # q_len > 1) always runs the gather, so raising there would kill
    # legitimate programs a forced-kernel pool still needs.
    kernel_eligible = slot is None and q_len == 1
    if quantized:
        from kvedge_tpu.ops.paged_attention import scales_fit_vmem

        scale_rows = new_pool_k.shape[1] * page  # per layer: P * page
        scales_fit = scales_fit_vmem(scale_rows, kv)
        if (kernel_eligible and cfg.paged_attention == "kernel"
                and not scales_fit):
            raise ValueError(
                "paged_attention='kernel' forced but the int8 scale "
                f"arrays ({scale_rows * kv} fp32 elements x2) exceed "
                "the kernel's VMEM budget — shrink the pool/page "
                "geometry or use 'auto'/'gather'"
            )
    else:
        scales_fit = True
    form = (_use_paged_kernel(cfg, page, kv * dh, max_pages=tables.shape[1])
            if kernel_eligible and scales_fit else "")
    if form == "blocked" and quantized and cfg.paged_attention != "kernel":
        form = ""  # the blocked form has no int8 variant: the gather
    if form:
        # Single-query decode (steps and windows): attention directly
        # over the block table — K/V pages stream up to each row's LIVE
        # length out of pool[layer] through the Pallas kernel; neither
        # the layer's slab nor the padded pool view is ever
        # materialized (ops/paged_attention.py). A row that is not
        # decoding (an empty slot of the bucket, or a half-prefilled
        # one, which carries its final length) is handed over at
        # position -1: the kernel reads nothing for it and returns
        # zeros, where the gather attends over whatever its table holds.
        from kvedge_tpu.ops import pallas_interpret
        from kvedge_tpu.ops.paged_attention import paged_decode_attention

        att = paged_decode_attention(
            q[:, 0], new_pool_k, new_pool_v, tables,
            jnp.where(active, q_positions[:, 0], -1),
            layer, scale_k=new_scale_k, scale_v=new_scale_v,
            interpret=pallas_interpret(),
            score_scale=cfg.attention_multiplier or None,
            **(dict(first=first, window=window) if window else {}),
            **(dict(blocked=True) if form == "blocked" else {}),
        )  # [B, H, Dh], kv-major head layout — same as the einsum's
        out = gated(att.reshape(batch, 1, h * dh)) @ w_out.astype(dtype)
    else:
        gk, gv = _gathered(
            (new_pool_k, new_pool_v, new_scale_k, new_scale_v),
            layer, tables, kv, dtype,
        )
        qg = q.reshape(batch, q_len, kv, group, dh)
        scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, gk)
        if cfg.attention_multiplier:
            scores = scores * jnp.asarray(cfg.attention_multiplier, dtype)
        else:
            scores = scores / (dh ** 0.5)
        from kvedge_tpu.ops.paged_attention import visible

        key_pos = jnp.arange(gk.shape[1])[None, None, :]
        if window:  # the table's entries hold positions from ``first`` on
            key_pos = key_pos + first[:, None, None]
        allowed = visible(key_pos, q_positions[:, :, None],
                          window)  # [B, Q, S]
        scores = jnp.where(
            allowed[:, None, None], scores, jnp.finfo(dtype).min
        )
        weights = jax.nn.softmax(
            scores.astype(jnp.float32), axis=-1
        ).astype(dtype)
        attended = jnp.einsum("bkgqs,bskd->bqkgd", weights, gv)
        out = (gated(attended.reshape(batch, q_len, h * dh))
               @ w_out.astype(dtype))
    return out, (new_pool_k, new_pool_v, new_scale_k, new_scale_v)


def _run_paged(cfg, params, state, x, q_positions, slot=None):
    """The layer loop of every paged program. The pool rides the carry
    WHOLE, beside the activations; only the layer's weights and its
    index are scanned over. As ``xs``/``ys`` of the scan each layer's
    slab was sliced out of the stacked pool and stacked back into a new
    one (and the new one copied into the enclosing window's carry): at
    a 1.6 GB pool that was 14 of a decode step's 23 ms (PERF.md §5).
    A patterned block's recurrent state rides it the same way, and the
    scan is over the pattern's periods (:func:`_run_paged_pattern`).
    Returns the logits and the state's new fields (:func:`_with_pools`).
    """
    if cfg.layer_pattern:
        return _run_paged_pattern(cfg, params, state, x, q_positions, slot)

    def body(carry, xs):
        x, pools = carry
        layer_params, layer = xs
        return _paged_attend_layer(
            cfg, state, x, layer_params, layer, pools,
            q_positions, slot,
        ), None

    (x, new_pools), _ = jax.lax.scan(
        body,
        (x, (state.pool_k, state.pool_v, state.scale_k, state.scale_v)),
        (stacked_layer_params(params, cfg),
         jnp.arange(cfg.n_layers, dtype=jnp.int32)),
    )
    x = _rmsnorm(x, params["ln_final"])
    logits = tied_readout(x[:, -1], params["embedding"])
    return logits, _pool_fields(new_pools)


def _run_paged_pattern(cfg, params, state, x, q_positions, slot):
    """:func:`_run_paged` for a block with a layer pattern
    (models/hybrid.py has the block and its equations): the attention
    layers through :func:`_paged_attention` on the pool, which holds
    only them, the layers bound to a window through the same function
    on the window layers' pool, the recurrent layers on
    ``state.recurrent``. A batched row
    that is not decoding (length 0 in ``state``, as the decode step
    masks it) gets its recurrent state back untouched."""
    from kvedge_tpu.models import hybrid

    def attend(normed, w, layer, pool, kind):
        return _paged_attention(
            cfg, state, normed, w["w_qkv"], w["w_out"], layer, pool,
            q_positions, slot, w.get("w_gate"),
            window=cfg.attention_window if kind == "window" else 0,
            qk_norm=(w["q_norm"], w["k_norm"]) if cfg.qk_norm else None)

    pools = {"attention": (state.pool_k, state.pool_v, state.scale_k,
                           state.scale_v)}
    if state.win_pool_k is not None:
        pools["window"] = (state.win_pool_k, state.win_pool_v, None, None)
    x, pools, recurrent = hybrid.run_layers(
        cfg, params, x, pools, state.recurrent, attend, slot,
        None if slot is not None else state.lengths > 0)
    x = _rmsnorm(x, params["ln_final"], cfg.norm_eps)
    # a head of its own where the tree has one, [V, D] as the embedding
    logits = tied_readout(
        x[:, -1], params.get("head", params["embedding"])
    ) / cfg.logits_scaling
    fields = _pool_fields(pools["attention"], recurrent=recurrent)
    if "window" in pools:
        fields.update(win_pool_k=pools["window"][0],
                      win_pool_v=pools["window"][1])
    return logits, fields


def _embed(cfg: TransformerConfig, params: dict, tokens):
    """The residual stream's first value, in the compute dtype."""
    x = params["embedding"][tokens].astype(jnp.dtype(cfg.dtype))
    if cfg.embedding_multiplier != 1.0:
        x = x * jnp.asarray(cfg.embedding_multiplier, x.dtype)
    return x


def _pool_fields(pools, **more) -> dict:
    """The state's fields a layer loop's carried 4-tuple stands for."""
    new_k, new_v, new_sk, new_sv = pools
    return dict(pool_k=new_k, pool_v=new_v, scale_k=new_sk,
                scale_v=new_sv, **more)


def _with_pools(state: PagedState, carried: dict, **extra) -> PagedState:
    """A state whose pools/scales and, for a patterned block, recurrent
    state and window layers' pools are replaced by ``carried`` (what
    ``_run_paged`` returns beside the logits), plus any other field."""
    return dataclasses.replace(state, **carried, **extra)


def _paged_prefill_impl(params: dict, state: PagedState, prompt, slot,
                        cfg: TransformerConfig, offset=0):
    # ``slot`` and ``offset`` are traced (they are only ever indices),
    # so XLA compiles one program per CHUNK length, not one per
    # (slot, offset, length) triple.
    _note_trace("prefill")
    x = _embed(cfg, params, prompt)[None]  # [1, T, D]
    q_positions = (offset + jnp.arange(prompt.shape[0]))[None]
    logits, pools = _run_paged(
        cfg, params, state, x, q_positions, slot
    )
    return logits[0], _with_pools(state, pools)


_paged_prefill = functools.partial(
    jax.jit, static_argnames=("cfg",), donate_argnums=(1,)
)(_paged_prefill_impl)


def _decode_step_core(params: dict, state: PagedState, tokens,
                      cfg: TransformerConfig, active):
    """One batched decode step (traceable body shared by the jitted
    single step and the windowed scan — the two must stay the same
    program so windowed and per-step decode agree token for token).
    ``active`` [B] bool gates the scatter and the length advance —
    lengths>0 is NOT sufficient once chunked prefill exists (a
    half-prefilled slot is admitted with its final length but must not
    be touched by decode)."""
    _note_trace("decode_step")
    x = _embed(cfg, params, tokens)[:, None]  # [B, 1, D]
    q_positions = state.lengths[:, None]  # [B, 1]
    masked = dataclasses.replace(
        state, lengths=jnp.where(active, state.lengths, 0)
    )
    logits, pools = _run_paged(cfg, params, masked, x, q_positions)
    return logits, _with_pools(
        state, pools,
        lengths=state.lengths + active.astype(jnp.int32),
    )


_paged_decode_step = functools.partial(
    jax.jit, static_argnames=("cfg",), donate_argnums=(1,)
)(_decode_step_core)


# An entry of a window's input row that stands for the row's pending
# first token, picked and kept on the device (``pick_first``); -1
# stands for the carry's.
FIRST_ON_DEVICE = -2


@jax.jit
def _join_firsts(tokens, firsts):
    """``tokens`` [B] int32 with every ``FIRST_ON_DEVICE`` replaced by
    that row of ``firsts`` [slots] (None: a pool that keeps none, and
    nothing to replace). One small program a bucket."""
    if firsts is None:
        return tokens
    return jnp.where(tokens == FIRST_ON_DEVICE,
                     firsts[:tokens.shape[0]], tokens)


@functools.partial(jax.jit, static_argnames=("row",))
def _join_carry(block, tokens, firsts, row: int):
    """A window's input tokens on the device: row ``row`` of the window
    before it (``block``: its harvest block, [steps + 2, B], perhaps
    still running) where ``tokens`` [B] int32 is -1, the row's pending
    first token where it is ``FIRST_ON_DEVICE``
    (:func:`_join_firsts`), ``tokens`` itself elsewhere. One small
    program a (window length, bucket): it stands where the eager slice
    of that row stood, and every overlapped dispatch runs it, newcomer
    or none, so a warm-up that overlaps two windows has compiled what
    an admission needs."""
    return jnp.where(tokens == -1, block[row],
                     _join_firsts(tokens, firsts))


def _put_first(firsts, slot, token):
    return firsts.at[slot].set(token), token


@jax.jit
def _pick_first_greedy(logits, firsts, slot):
    """The greedy pick of :meth:`PagedKVCache.pick_first`."""
    return _put_first(firsts, slot,
                      jnp.argmax(logits, axis=-1).astype(jnp.int32))


@jax.jit
def _pick_first_sampled(logits, firsts, slot, key_data, temperature,
                        top_p):
    """The sampled pick of :meth:`PagedKVCache.pick_first`: token 0 of
    the request's key schedule, through the filter every later token
    goes through."""
    from kvedge_tpu.models.decode import row_sample_keys, sample_token

    keys = row_sample_keys(jax.random.wrap_key_data(key_data[None]), 0)
    return _put_first(firsts, slot, sample_token(
        logits[None], keys, temperature, top_p)[0])


def _picks_zeroed(state: PagedState) -> PagedState:
    """A window counts its own expert picks: a patterned block's
    counters start each window at zero (what prefill chunks added
    since the last one is dropped; ``stats()`` counts decode steps)."""
    if state.recurrent is None:
        return state
    zeros = jnp.zeros_like(state.recurrent["picks"])
    return dataclasses.replace(
        state, recurrent={**state.recurrent, "picks": zeros})


def _window_result(produced, state: PagedState, active, steps_left,
                   n_steps: int, stop_at):
    """What a capped window hands the host: the produced tokens with
    the packed ``[fin, stop_at]`` rows and, for a patterned block, the
    window's expert-pick counters beside them."""
    fin = jnp.where(
        stop_at > 0, 2,
        jnp.where(active & (steps_left <= n_steps), 1, 0),
    ).astype(jnp.int32)
    produced = jnp.concatenate(
        [produced, fin[None], stop_at[None]], axis=0
    )
    if state.recurrent is None:
        return produced
    return produced, state.recurrent["picks"]


def _paged_decode_window_capped_impl(params: dict, state: PagedState,
                                     tokens, cfg: TransformerConfig,
                                     n_steps: int, active, steps_left,
                                     stop_tokens):
    """``n_steps`` decode steps with greedy feedback in one program,
    with PER-SLOT stop detection in the scan carry.

    The scan carries (state, pending token); each step feeds the pending
    token and emits its greedy successor. Inactive slots produce garbage
    tokens that are never read (their scatters drop, their lengths hold).

    The decode loop (serving.py) dispatches window N+1 before the host
    has harvested window N, so the host cannot shrink the window to the
    tightest slot's remaining budget. Instead each row carries its own
    budget cap: ``steps_left`` [B] int32 is how many steps row b may still
    decode, and the per-step done flag ``i >= steps_left[b]`` freezes a
    finished row — its length holds and its K/V scatters drop (the
    same ``active`` gate chunked prefill relies on), so a speculatively
    dispatched window can never scribble past a stop the host hasn't
    seen yet. A frozen row keeps re-emitting its final token; the host
    truncates its stream at the true stop when it harvests
    (row b's real tokens are produced[:steps_left[b]]).

    Finish bookkeeping rides the carry (SERVING.md rung 23):
    ``stop_tokens`` [B] int32 is each row's stop token (-1 = none;
    argmax can never produce -1, so stop-free traffic is bit-identical
    by construction). The window tracks ``stop_at`` [B] — the first
    1-based live step whose produced token equals the row's stop
    (0 = no hit) — and the result packs TWO extra rows onto the
    produced tokens: ``produced[n_steps] = fin`` (0 = window-capped,
    1 = froze in-window on its per-slot cap, 2 = stop token hit) and
    ``produced[n_steps + 1] = stop_at``. One device->host transfer
    hands the host every finish decision, so the boundary sweep does
    O(finishes) work instead of scanning the bucket. A stop hit does
    NOT freeze the row on device — its remaining in-window steps decode
    garbage within its already-granted cap (writes stay inside reserved
    pages, lengths advance exactly as the host pre-booked) and the host
    truncates the emission at ``stop_at``; the row's slot releases at
    harvest, which zeroes the length either way.
    """
    _note_trace("window_capped")

    def body(carry, i):
        state, toks, stop_at = carry
        live = active & (i < steps_left)
        logits, state = _decode_step_core(params, state, toks, cfg, live)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        nxt = jnp.where(live, nxt, toks)
        stop_at = jnp.where(
            live & (stop_at == 0) & (nxt == stop_tokens), i + 1, stop_at
        )
        return (state, nxt, stop_at), nxt

    stop0 = jnp.zeros(tokens.shape[0], jnp.int32)
    (state, _, stop_at), produced = jax.lax.scan(
        body, (_picks_zeroed(state), tokens, stop0), jnp.arange(n_steps)
    )
    return _window_result(produced, state, active, steps_left, n_steps,
                          stop_at), state


_paged_decode_window_capped = functools.partial(
    jax.jit, static_argnames=("cfg", "n_steps"), donate_argnums=(1,)
)(_paged_decode_window_capped_impl)


def _paged_decode_window_sampled_capped_impl(
        params: dict, state: PagedState, tokens,
        cfg: TransformerConfig, n_steps: int, active, key_data,
        base_steps, temps, top_ps, sampled_mask, steps_left,
        stop_tokens):
    """Mixed greedy/sampled window with the per-slot done flag of
    :func:`_paged_decode_window_capped_impl`.

    The per-token sampling key is ``fold_in(row_seed, t)`` with ``t`` a
    pure function of the request's emitted count — host-known at
    dispatch — so the whole key schedule rides the scan as
    ``base_steps + i``. Each step applies the SAME nucleus filter and
    categorical draw as ``decode.generate`` (decode.sample_token), then
    selects sampled vs greedy per row by ``sampled_mask``: one host
    round trip serves a window of sampled tokens exactly as it does
    greedy ones, token for token what per-step sampling would emit.
    Frozen rows' draws are computed and discarded (their outputs are
    never read and their state never advances). ``key_data`` is raw
    uint32 key data ([B, 2] for threefry), wrapped on device — raw data
    crosses process boundaries (the slice op-stream) where typed key
    arrays cannot. Packs the same ``[fin, stop_at]`` finish-bookkeeping
    rows onto the produced tokens as the greedy capped window."""
    _note_trace("window_sampled_capped")
    keys = jax.random.wrap_key_data(key_data)

    def body(carry, i):
        state, toks, stop_at = carry
        live = active & (i < steps_left)
        logits, state = _decode_step_core(params, state, toks, cfg,
                                          live)
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        from kvedge_tpu.models.decode import sample_token

        step_keys = jax.vmap(jax.random.fold_in)(keys, base_steps + i)
        sampled = sample_token(
            logits, step_keys, temps[:, None], top_ps[:, None]
        )
        nxt = jnp.where(sampled_mask, sampled, greedy).astype(jnp.int32)
        nxt = jnp.where(live, nxt, toks)
        stop_at = jnp.where(
            live & (stop_at == 0) & (nxt == stop_tokens), i + 1, stop_at
        )
        return (state, nxt, stop_at), nxt

    stop0 = jnp.zeros(tokens.shape[0], jnp.int32)
    (state, _, stop_at), produced = jax.lax.scan(
        body, (_picks_zeroed(state), tokens, stop0), jnp.arange(n_steps)
    )
    return _window_result(produced, state, active, steps_left, n_steps,
                          stop_at), state


_paged_decode_window_sampled_capped = functools.partial(
    jax.jit, static_argnames=("cfg", "n_steps"), donate_argnums=(1,)
)(_paged_decode_window_sampled_capped_impl)
