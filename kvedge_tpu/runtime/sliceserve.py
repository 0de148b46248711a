"""Cross-host continuous batching: the paged scheduler on a multi-host slice.

The design of record from SERVING.md ("Left on the table" — now built):
the control plane is NOT distributed. Admission, slot assignment, block
tables, reservations, and the prefix trie stay host metadata on the
leader (process 0), exactly as they are single-host; followers only ever
execute the *device program* with the leader's inputs. Concretely, a
:class:`SlicePagedKVCache` on the leader broadcasts each device call —
table sync, prefill chunk, decode step, decode window — as a fixed-shape
header plus its inputs, then every process executes the SAME jitted
kernel on global arrays, so XLA's collectives span the slice exactly as
they do in multi-host training. The follower side is
:func:`follow_paged`: a loop that receives ops and replays them.

Why this is sound:

* **Total order.** Every cache-state mutation in the serving layer
  serializes on the server lock (SERVING.md invariant 5), so the
  leader's broadcasts form one totally-ordered op stream; the follower
  replays it in order. There is no second broadcaster by construction.
* **Followers hold no host state.** Free lists, refcounts, LRU stamps,
  reservations — none of it is replicated (the LRU clock isn't even
  deterministic across hosts). The follower's device state evolves
  identically because the device inputs — tables, lengths, tokens,
  masks — arrive by value in the op stream.
* **Windows amortize the broadcast like they amortize RTT.** Between
  page boundaries the decode loop dispatches one WINDOW op per
  ``page_size`` greedy tokens; the cross-host control traffic rides the
  same cadence as the single-host loop's host reads.
* **Failure is bounded, and no longer always fatal.** A follower that
  dies used to leave the leader blocked in a collective forever,
  holding the server's work lock. Every leader-side op now runs
  through a :class:`~kvedge_tpu.runtime.failures.DeadlineRunner` with
  compile-aware budgets: a wedged op is orphaned on the op thread and
  surfaces as a typed
  :class:`~kvedge_tpu.runtime.failures.SliceFollowerLost`, the op
  stream latches dead, and the serving layer degrades (poisons
  in-flight requests, refuses new ones, keeps ``close()`` bounded).
  The recovery supervisor (runtime/recovery.py, SERVING.md rung 15)
  then tries to heal in place: :meth:`SlicePagedKVCache.reform`
  installs a fresh op stream and runs a deadline-bounded barrier SYNC
  that a re-entered follower replays as its first op. Only when
  reformation keeps failing does the old story — reschedule the slice
  — take over. A full follower *state machine* (rejoin mid-stream at
  an arbitrary op) remains rejected; rejoin at the reformation
  barrier is the one boundary cheap enough to keep.

The reference has no serving and no multi-host anything (SURVEY.md §0,
§5); this module is the last rung of the serving ladder this repo
climbs on top of the reference's deployment story.
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np

from kvedge_tpu.runtime.failures import (
    DeadlineRunner,
    DeviceOpTimeout,
    OpBudgets,
    SliceFollowerLost,
)
from kvedge_tpu.models.kvcache import (
    PagedCacheError,
    PagedKVCache,
    PagedState,
    _cow_page_impl,
    _decode_step_core,
    _gather_pages_impl,
    _paged_decode_window_capped_impl,
    _paged_decode_window_sampled_capped_impl,
    _paged_prefill_impl,
    _scatter_pages_impl,
)

# Op codes (header[0]). STOP ends the follower loop. WINDOWP/WSAMPLEP
# are the decode-window pair: dispatched WITHOUT reading
# the result, so the leader can broadcast window N+1 while window N is
# still executing — followers likewise replay the dispatch and never
# block on a result (they never read tokens at all). The numbering is
# wire protocol within a run, not across versions: leader and followers
# boot from one image, so a code means what this file says on every
# process of a slice.
(OP_STOP, OP_SYNC, OP_PREFILL, OP_STEP, OP_WINDOWP, OP_WSAMPLEP,
 OP_SWAPOUT, OP_SWAPIN, OP_MULTI, OP_COWP) = range(10)
_HEADER_LEN = 4  # [op, a, b, c] — meanings per op below.

# Human names for follower-side replay spans (runtime/tracing.py).
_OP_NAMES = {
    OP_STOP: "stop", OP_SYNC: "sync", OP_PREFILL: "prefill",
    OP_STEP: "step", OP_WINDOWP: "windowp",
    OP_WSAMPLEP: "wsamplep", OP_SWAPOUT: "swapout",
    OP_SWAPIN: "swapin", OP_MULTI: "multi", OP_COWP: "cowp",
}

# Ops whose payloads may ride a coalesced OP_MULTI frame (SERVING.md
# rung 23): the deferred table sync and swap-in that precede a window
# dispatch at a page boundary, plus the pipelined dispatches
# themselves. Every one of these has payload shapes fully derivable
# from its own [op, a, b, c] header, which is what lets the follower
# carve a packed frame without any out-of-band shape agreement.
_COALESCABLE = frozenset((
    OP_SYNC, OP_SWAPIN, OP_WINDOWP, OP_WSAMPLEP, OP_COWP,
))


def _slice_kernels(mesh, cfg, quantized: bool = False):
    """The paged kernels re-jitted with pinned output shardings: the
    K/V pools shard over the ``model`` axis on their merged ``K*Dh``
    lane dim — K-major, so a chip's columns are whole kv heads, the
    same bytes a kv-heads dim would give it (the per-token K/V a
    model-sharded layer produces is already head-sharded, so scatters
    stay local and no host ever materializes the whole pool), falling
    back to replication when the heads don't
    divide; logits/tokens/tables pin REPLICATED so each process reads
    them from its own addressable shard (``addressable_data(0)``) with
    no extra collective. Compiled programs are the single-host impl
    functions unchanged — the exactness argument is structural, not
    re-proven."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    rep = NamedSharding(mesh, P())
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    model = axis_sizes.get("model", 1)
    head_sharded = model > 1 and cfg.kv_heads % model == 0
    pool_sh = (
        NamedSharding(mesh, P(None, None, None, "model"))
        if head_sharded else rep
    )
    # int8 scales [L, P, page, K] shard with the pool's kv heads.
    scale_sh = (
        (NamedSharding(mesh, P(None, None, None, "model"))
         if head_sharded else rep)
        if quantized else None
    )
    state_sh = PagedState(
        pool_k=pool_sh, pool_v=pool_sh, tables=rep, lengths=rep,
        scale_k=scale_sh, scale_v=scale_sh,
    )
    prefill = jax.jit(
        _paged_prefill_impl, static_argnames=("cfg",),
        donate_argnums=(1,), out_shardings=(rep, state_sh),
    )
    step = jax.jit(
        _decode_step_core, static_argnames=("cfg",),
        donate_argnums=(1,), out_shardings=(rep, state_sh),
    )
    window_capped = jax.jit(
        _paged_decode_window_capped_impl,
        static_argnames=("cfg", "n_steps"), donate_argnums=(1,),
        out_shardings=(rep, state_sh),
    )
    wsample_capped = jax.jit(
        _paged_decode_window_sampled_capped_impl,
        static_argnames=("cfg", "n_steps"), donate_argnums=(1,),
        out_shardings=(rep, state_sh),
    )
    # Preemptive swap (SERVING.md rung 17): the gather pins REPLICATED
    # outputs — an all-gather over the model-sharded pool dims, so the
    # leader can host-read the as-stored page bytes; the scatter takes
    # replicated page bytes back into the sharded pools (each process
    # keeps its own head shard of the update). No dtype conversion in
    # either — the swap path's bit-exactness contract.
    swap_gather = jax.jit(
        _gather_pages_impl, static_argnames=("kv_heads",),
        out_shardings=rep,
    )
    swap_scatter = jax.jit(
        _scatter_pages_impl, donate_argnums=(0,), out_shardings=state_sh,
    )
    # COW divergence (SERVING.md rung 24): one device-side page copy
    # per (src, dst) pair, traced ONCE — the pair arrives as a traced
    # [2] int32 array so every copy replays the same program. Each
    # process copies its own head shard; nothing crosses hosts.
    cow = jax.jit(
        _cow_pair_core, donate_argnums=(0,), out_shardings=state_sh,
    )
    return (rep, state_sh, prefill, step, window_capped,
            wsample_capped, swap_gather, swap_scatter, cow)


def _cow_pair_core(state, pair):
    """Header-derived form of :func:`_cow_page_impl` for the op
    stream: ``pair = [src, dst]`` rides the broadcast as one array."""
    return _cow_page_impl(state, pair[0], pair[1])


class SlicePagedKVCache(PagedKVCache):
    """A :class:`PagedKVCache` whose device calls span a multi-host mesh.

    Constructed identically on EVERY process (the zeroed global state
    and the jitted kernels are collective creations, so construction
    order is part of the protocol). On the leader it is handed to a
    regular :class:`~kvedge_tpu.models.serving.PagedGenerationServer`
    and behaves like any cache — all the host bookkeeping of the base
    class runs as-is; only the device seams broadcast first. On
    followers, :func:`follow_paged` drives :meth:`_follow_op` until the
    leader broadcasts STOP.

    Single-process meshes work too (broadcast_one_to_all degenerates to
    a copy), which is how tests/test_sliceserve.py pins leader-path
    token equality against the plain cache without subprocesses.
    """

    def __init__(self, cfg, *, slots: int, pages: int, page_size: int,
                 mesh, max_pages_per_seq: int | None = None,
                 kv_dtype: str = "", op_budgets: OpBudgets | None = None):
        import jax

        # Slice pools always use the gather path: the Pallas kernel has
        # no partitioning rule, so tracing it over a model-sharded pool
        # would poison the first decode step on a real slice. Pinned
        # here (every process constructs the same cfg, so the pin is
        # part of the protocol) rather than left to _use_paged_kernel's
        # per-trace heuristics — even an explicit "kernel" override is
        # downgraded, and __init__'s forced-kernel VMEM refusal never
        # fires spuriously for a slice cache.
        cfg = dataclasses.replace(cfg, paged_attention="gather")
        self.mesh = mesh
        (self._rep, self._state_sh, self._k_prefill, self._k_step,
         self._k_window_capped, self._k_wsample_capped,
         self._k_swapout, self._k_swapin,
         self._k_cow) = _slice_kernels(
             mesh, cfg, quantized=kv_dtype == "int8"
         )
        self._is_leader = jax.process_index() == 0
        self._stopped = False
        # Coalesced slice broadcasts (SERVING.md rung 23): leader-side
        # buffer of (header, payload, exec) triples for ops whose
        # broadcast may be deferred to the next dispatch seam, where
        # everything pending goes out as ONE framed OP_MULTI — a table
        # sync or swap-in at a page boundary no longer pays its own
        # pair of collectives. Counters are plain observability.
        self._pending_ops: list = []
        self.coalesced_flushes = 0
        self.coalesced_ops = 0
        # Per-op broadcast attribution (SERVING.md rung 25): cumulative
        # wall time each op KIND spent in the header+payload broadcast
        # and collective execution, keyed by the op name ("sync",
        # "windowp", ..., "multi" for coalesced frames). Plain dict of
        # [count, total_ms] mutated only by the leader's op thread
        # under the serving work lock; rendered in /metrics as the
        # labelled kvedge_serve_device_ms_broadcast_total family.
        self.op_broadcast_ms: dict[str, list] = {}
        # Leader-side watchdog over the op stream (header send,
        # broadcast, exec): a wedged collective surfaces as a typed
        # SliceFollowerLost instead of an eternal hang holding the
        # server's work lock. Followers run a bounded rejoin loop
        # (runtime/workload.py) before giving up and letting the pod
        # die. The budgets object is kept: reform() builds each
        # replacement runner over the SAME instance, so compiled-key
        # knowledge survives — a program compiled before the failure
        # keeps its steady budget after the heal.
        self._op_budgets = op_budgets if op_budgets is not None else OpBudgets()
        self._ops = DeadlineRunner(
            self._op_budgets, failure=SliceFollowerLost,
            name="kvedge-slice-ops",
        )
        super().__init__(
            cfg, slots=slots, pages=pages, page_size=page_size,
            max_pages_per_seq=max_pages_per_seq, kv_dtype=kv_dtype,
        )

    # Every read of this pool goes through the op stream (a flush, a
    # deadline), which the server's work lock serializes: the serving
    # layer reads its windows and first tokens with the lock held, and
    # no first token stays on the device (a prefill chunk's logits are
    # read back by the op that made them).
    unlocked_reads = False

    def _init_firsts(self):
        return None

    # ---- refused host I/O ------------------------------------------------

    def snapshot_pages(self, ids):
        """Prefix-cache persistence is single-host only: the inherited
        implementation would run a leader-only computation on a global
        array — a collective the followers never join (wedge or crash).
        The refusal lives here, with the API, not just at the workload
        call-site guard."""
        raise PagedCacheError(
            "prefix-cache persistence is not supported on a slice cache"
        )

    def read_pages(self, ids):
        raise PagedCacheError(
            "prefix-cache persistence is not supported on a slice cache"
        )

    def write_pages(self, ids, k_vals, v_vals):
        raise PagedCacheError(
            "prefix-cache persistence is not supported on a slice cache"
        )

    # ---- global-array plumbing ------------------------------------------

    def _init_state(self, shape, dtype) -> PagedState:
        """Zeroed state as GLOBAL arrays: a collective jit execution
        (every process runs it at construction)."""
        import jax
        import jax.numpy as jnp

        slots, mpps = self.slots, self.max_pages_per_seq
        quantized = self.kv_quantized

        def scale():
            return (jnp.zeros(shape[:-1] + (self.cfg.kv_heads,),
                              jnp.float32)
                    if quantized else None)

        return jax.jit(
            lambda: PagedState(
                pool_k=jnp.zeros(shape, dtype),
                pool_v=jnp.zeros(shape, dtype),
                tables=jnp.zeros((slots, mpps), jnp.int32),
                lengths=jnp.zeros((slots,), jnp.int32),
                scale_k=scale(),
                scale_v=scale(),
            ),
            out_shardings=self._state_sh,
        )()

    def _global(self, arr: np.ndarray):
        """A replicated global array from identical per-process data."""
        import jax

        return jax.make_array_from_process_local_data(self._rep, arr)

    def _global_const(self, kind: str, arr: np.ndarray):
        """Memoized :meth:`_global` for the pipelined window seams'
        small operand rows (mask/caps/stops), which repeat verbatim
        between steady-state redispatches — every process (leader and
        follower alike) skips the per-window global-array construction
        on a byte-identical repeat. Shares the base class's
        ``_dev_memo`` store, so ``drop_carry`` (and through it
        ``reform``) invalidates it with the carries — a re-formed mesh
        never sees globals built on the dead one."""
        key = arr.tobytes()
        hit = self._dev_memo.get(kind)
        if hit is not None and hit[0] == key:
            return hit[1]
        dev = self._global(arr)
        self._dev_memo[kind] = (key, dev)
        return dev

    @staticmethod
    def _read(arr) -> np.ndarray:
        """Host copy of a replicated global array (local shard only)."""
        return np.asarray(arr.addressable_data(0))

    def _bcast(self, tree):
        from jax.experimental import multihost_utils

        return multihost_utils.broadcast_one_to_all(
            tree, is_source=self._is_leader
        )

    def _send_header(self, op: int, a: int = 0, b: int = 0, c: int = 0):
        hdr = np.array([op, a, b, c], np.int64)
        self._bcast(hdr)

    # ---- coalesced multi-op broadcasts (SERVING.md rung 23) --------------

    def _queue_op(self, hdr: tuple, payload: tuple, exec_thunk) -> None:
        """Buffer one coalescable op. The payload arrays MUST be
        snapshots (never views of live host bookkeeping): the
        broadcast is deferred to the next flush, and the serving layer
        keeps mutating ``_host_tables``/``_host_lengths`` in between."""
        self._pending_ops.append((
            np.array(hdr, np.int64),
            tuple(np.ascontiguousarray(a) for a in payload),
            exec_thunk,
        ))

    def _flush_ops(self, key: tuple | None = None,
                   budget_s: float | None = None):
        """Broadcast + execute everything pending, in queue order.

        One buffered op goes out exactly as it always did — its own
        header + payload pair, wire-identical to the pre-coalescing
        protocol. Two or more pack into a single OP_MULTI frame: one
        header (a = op count, b = frame bytes) and ONE uint8 payload
        broadcast carrying each op's [op, a, b, c] header followed by
        its raw array bytes; the follower re-derives every shape from
        the embedded headers (:meth:`_multi_templates`) and replays
        through the same exec path as the bare branches. Execution
        (leader-side jit enqueue) happens AFTER the frame broadcast,
        in op order, so the collective order every process sees is
        identical to the unbatched stream. Returns the LAST op's exec
        result (the dispatch that forced the flush)."""
        if not self._pending_ops:
            return None
        ops, self._pending_ops = self._pending_ops, []
        if key is None:
            key = ("multi", len(ops))

        if len(ops) == 1:
            hdr, payload, exec_thunk = ops[0]

            def op():
                self._bcast(hdr)
                self._bcast(payload)
                return exec_thunk()

            return self._traced_run(key, op, budget_s=budget_s)

        frame = np.frombuffer(
            b"".join(
                hdr.tobytes() + b"".join(a.tobytes() for a in payload)
                for hdr, payload, _ in ops
            ),
            np.uint8,
        )

        def op():
            self._send_header(OP_MULTI, len(ops), frame.shape[0])
            self._bcast(frame)
            out = None
            for _, _, exec_thunk in ops:
                out = exec_thunk()
            return out

        self.coalesced_flushes += 1
        self.coalesced_ops += len(ops)
        return self._traced_run(key, op, budget_s=budget_s)

    def _discard_pending_ops(self) -> None:
        """Drop buffered ops without broadcasting (stop/reform): the
        followers are released or rejoining at a barrier SYNC that
        re-syncs tables anyway — replaying onto a dead or reset stream
        would wedge or double-apply."""
        self._pending_ops.clear()

    def _multi_templates(self, op: int, a: int, b: int, c: int) -> tuple:
        """(shape, dtype) per payload array for a coalescable op, as a
        pure function of its header — the single source of truth for
        both the bare zero-template broadcasts and OP_MULTI frame
        carving, so the two wire forms can never drift apart."""
        n = self.slots
        if op == OP_SYNC:
            return (((n, self.max_pages_per_seq), np.int32),
                    ((n,), np.int32))
        if op == OP_SWAPIN:
            return tuple(
                (arr.shape, arr.dtype) for arr in self._swap_templates(a)
            )
        if op == OP_COWP:
            # a = src, b = dst (redundantly carried in the [2] int32
            # payload so the jitted copy replays one traced program).
            return (((2,), np.int32),)
        if op == OP_WINDOWP:
            # a = n_steps, b = carry flag.
            return (((n,), np.int32), ((n,), bool), ((n,), np.int32),
                    ((n,), np.int32))
        if op == OP_WSAMPLEP:
            # a = n_steps, b = key-data width, c = carry flag.
            return (((n,), np.int32), ((n,), bool), ((n, b), np.uint32),
                    ((n,), np.int32), ((n,), np.float32),
                    ((n,), np.float32), ((n,), bool), ((n,), np.int32),
                    ((n,), np.int32))
        raise PagedCacheError(f"op {op} is not coalescable")

    def _replay_packed(self, params, op: int, a: int, b: int, c: int,
                       payload: list) -> None:
        """Follower: replay one coalescable op through the SAME exec
        seams the bare branches use — a frame-carried op and a bare op
        are indistinguishable past this point."""
        if op == OP_SYNC:
            self._apply_sync(payload[0], payload[1])
        elif op == OP_SWAPIN:
            self._exec_swapin(payload[0], tuple(payload[1:]))
        elif op == OP_COWP:
            self._exec_cow(np.asarray(payload[0]))
        elif op == OP_WINDOWP:
            self._exec_window_pipelined(
                params, *payload, n_steps=a, carry=bool(b))
        elif op == OP_WSAMPLEP:
            self._exec_window_sampled_pipelined(
                params, *payload, n_steps=a, carry=bool(c))
        else:  # pragma: no cover - _multi_templates already refused
            raise PagedCacheError(f"op {op} is not coalescable")

    # ---- leader-side device seams (base-class host logic unchanged) -----

    def _traced_run(self, key: tuple, op, budget_s: float | None = None):
        """One leader-side op through the deadline runner, stamped as a
        per-op broadcast span (cat "slice") when the serving layer
        shared a tracer (``cache.tracer``, runtime/tracing.py). The
        span covers header send + payload broadcast + the collective's
        execution — the seam where a slow or lost follower shows up, so
        a stalled slice is attributable to the op that stalled it.
        Tracer or not, the per-op-kind cumulative bill
        (``op_broadcast_ms``, rung 25) always accrues: two
        perf_counter stamps and a dict bump, the same always-on cost
        contract as the serving layer's stage histograms."""
        tr = getattr(self, "tracer", None)
        if tr is not None and self._ops.tracer is None:
            # Lazy share (also re-shares after reform() swaps in a
            # fresh runner): a timeout's "op-timeout" instant lands in
            # the same timeline as the op spans it interrupts.
            self._ops.tracer = tr
        t0 = time.perf_counter()
        try:
            return self._ops.run(key, op, budget_s=budget_s)
        finally:
            dt_ms = (time.perf_counter() - t0) * 1e3
            cell = self.op_broadcast_ms.get(str(key[0]))
            if cell is None:
                cell = self.op_broadcast_ms[str(key[0])] = [0, 0.0]
            cell[0] += 1
            cell[1] += dt_ms
            if tr is not None:
                tr.span(str(key[0]), "slice", t0,
                        args={"op": "/".join(str(k) for k in key)})

    def _sync(self) -> None:
        if self._stopped or self._ops.dead is not None:
            # Teardown tail: a request thread unwinding after a hard
            # close (or after the op stream died) still releases its
            # slot, which syncs tables — the followers are gone, the
            # device state is dead, so the host bookkeeping proceeds
            # without a broadcast.
            return
        # Deferred (rung 23): the broadcast rides the next flush — at
        # a page boundary that is the window dispatch a moment later,
        # so sync + dispatch go out as ONE OP_MULTI frame instead of
        # two header/payload collective pairs. np.array COPIES: the
        # serving layer mutates the host tables between queue and
        # flush, and the wire must carry this call's snapshot.
        tables = np.array(self._host_tables, np.int32)
        lengths = np.array(self._host_lengths, np.int32)
        self._queue_op(
            (OP_SYNC, 0, 0, 0), (tables, lengths),
            lambda: self._apply_sync(tables, lengths),
        )

    def _apply_sync(self, tables: np.ndarray, lengths: np.ndarray):
        import dataclasses

        self.state = dataclasses.replace(
            self.state,
            tables=self._global(tables.astype(np.int32)),
            lengths=self._global(lengths.astype(np.int32)),
        )

    def _check_live(self) -> None:
        if self._ops.dead is not None:
            raise SliceFollowerLost(
                f"slice op stream is dead (op {self._ops.dead} timed "
                f"out — follower lost); the slice must be rescheduled",
                op=self._ops.dead,
            )
        if self._stopped:
            raise PagedCacheError(
                "slice serve is stopped — the followers were released"
            )

    def _device_prefill(self, params, tokens, slot: int, offset: int):
        self._check_live()
        self._flush_ops()
        tokens = np.asarray(tokens, np.int32)

        def op():
            self._send_header(OP_PREFILL, slot, offset, tokens.shape[0])
            sent = np.asarray(self._bcast(tokens))
            return self._exec_prefill(params, sent, slot, offset)

        return self._traced_run(("prefill", tokens.shape[0]), op)

    def _exec_prefill(self, params, tokens: np.ndarray, slot: int,
                      offset: int):
        logits, self.state = self._k_prefill(
            params, self.state, self._global(tokens.astype(np.int32)),
            slot, self.cfg, offset,
        )
        return self._read(logits)

    def _active_np(self, active) -> np.ndarray:
        """bool [slots] mask on the HOST — the base class derives the
        default (None = every admitted slot) from device lengths, which
        a leader-only computation must not touch on a global array."""
        if active is None:
            return np.asarray(self._host_lengths, np.int64) > 0
        return np.asarray(active, bool)

    def _device_step(self, params, tokens, active):
        self._check_live()
        self._flush_ops()
        tokens = np.asarray(tokens, np.int32)
        mask = self._active_np(active)

        def op():
            self._send_header(OP_STEP)
            sent, m = self._bcast((tokens, mask))
            return self._exec_step(params, np.asarray(sent),
                                   np.asarray(m))

        return self._traced_run(("step",), op)

    def _exec_step(self, params, tokens: np.ndarray, mask: np.ndarray):
        logits, self.state = self._k_step(
            params, self.state, self._global(tokens.astype(np.int32)),
            self.cfg, self._global(mask.astype(bool)),
        )
        return self._read(logits)

    # ---- pipelined (overlap) window pair --------------------------------

    def _device_window_dispatch(self, params, tokens, n_steps: int,
                                active, steps_left, stop_tokens):
        """Leader: broadcast + enqueue a capped window WITHOUT reading
        the result. An entry of ``tokens`` below 0 (``None``: every
        entry) selects the device-resident carry (header flag ``b``:
        some entry does) — the previous window's final token row,
        which every process joins locally with its own replicated
        copy, so neither the leader nor any follower blocks on the
        previous window between the pair. The row rides the broadcast
        either way, so the payload shape is op-independent.
        The dispatch is a flush seam (rung 23): a buffered table sync
        rides the same framed broadcast."""
        self._check_live()
        tokens_np = self._host_tokens(tokens)
        carry = int((tokens_np < 0).any())
        mask = self._active_np(active)
        caps = np.asarray(steps_left, np.int32)
        stops = np.asarray(stop_tokens, np.int32)

        self._queue_op(
            (OP_WINDOWP, n_steps, carry, 0),
            (tokens_np, mask, caps, stops),
            lambda: self._exec_window_pipelined(
                params, tokens_np, mask, caps, stops,
                n_steps=n_steps, carry=bool(carry),
            ),
        )
        return self._flush_ops(("windowp", n_steps))

    def _exec_window_pipelined(self, params, tokens: np.ndarray,
                               mask: np.ndarray, caps: np.ndarray,
                               stops: np.ndarray, *,
                               n_steps: int, carry: bool):
        toks_in = self._global(tokens.astype(np.int32))
        if carry:
            toks_in = self._with_carry(toks_in)
        toks, self.state = self._k_window_capped(
            params, self.state, toks_in, self.cfg, n_steps,
            self._global_const("w_act", mask.astype(bool)),
            self._global_const("w_caps", caps.astype(np.int32)),
            self._global_const("w_stops", stops.astype(np.int32)),
        )
        self._carry = (toks, n_steps)
        return toks

    def _device_window_sampled_dispatch(self, params, tokens,
                                        n_steps: int, active, key_data,
                                        base_steps, temps, top_ps,
                                        sampled_mask, steps_left,
                                        stop_tokens):
        self._check_live()
        tokens_np = self._host_tokens(tokens)
        carry = int((tokens_np < 0).any())
        key_data = np.asarray(key_data, np.uint32)
        mask = self._active_np(active)
        payload = (
            tokens_np, mask, key_data,
            np.asarray(base_steps, np.int32),
            np.asarray(temps, np.float32),
            np.asarray(top_ps, np.float32),
            np.asarray(sampled_mask, bool),
            np.asarray(steps_left, np.int32),
            np.asarray(stop_tokens, np.int32),
        )

        # a = n_steps, b = key-data width, c = carry flag.
        self._queue_op(
            (OP_WSAMPLEP, n_steps, key_data.shape[1], carry), payload,
            lambda: self._exec_window_sampled_pipelined(
                params, *payload, n_steps=n_steps, carry=bool(carry),
            ),
        )
        return self._flush_ops(("wsamplep", n_steps))

    def _exec_window_sampled_pipelined(self, params, tokens, mask,
                                       key_data, base_steps, temps,
                                       top_ps, smask, caps, stops, *,
                                       n_steps: int, carry: bool):
        toks_in = self._global(tokens.astype(np.int32))
        if carry:
            toks_in = self._with_carry(toks_in)
        # key_data/base_steps advance every window; the rest repeat
        # in steady state and ride the memo.
        toks, self.state = self._k_wsample_capped(
            params, self.state, toks_in, self.cfg, n_steps,
            self._global_const("ws_act", mask.astype(bool)),
            self._global(key_data.astype(np.uint32)),
            self._global(base_steps.astype(np.int32)),
            self._global_const("ws_temps", temps.astype(np.float32)),
            self._global_const("ws_topps", top_ps.astype(np.float32)),
            self._global_const("ws_smask", smask.astype(bool)),
            self._global_const("ws_caps", caps.astype(np.int32)),
            self._global_const("ws_stops", stops.astype(np.int32)),
        )
        self._carry = (toks, n_steps)
        return toks

    def harvest_window(self, handle):
        """Leader: force a dispatched window's tokens. Deadline-bounded
        like every op, but NOT a broadcast — the tokens are replicated,
        every process already holds (or will hold, once its queued
        program runs) its own copy, and followers never read them. The
        read waits on device execution of everything queued up to and
        including this window — i.e. the in-flight pair — so it runs
        under the op budget rather than a bare timeout: the window
        programs were compiled at dispatch, and the steady budget is
        sized for device execution, not compilation."""
        self._check_live()
        self._flush_ops()
        return self._traced_run(("wharvest",), lambda: self._read(handle))

    # ---- preemptive swap (scheduler, SERVING.md rung 17) -----------------

    def _device_swapout(self, ids):
        """Leader: broadcast the page ids, then every process runs the
        same jitted gather — an all-gather over the model-sharded pool
        dims whose replicated result the leader reads host-side. The
        follower replays the op in the totally-ordered stream and
        discards its (identical) copy."""
        self._check_live()
        self._flush_ops()
        ids_np = np.asarray(ids, np.int32)

        def op():
            self._send_header(OP_SWAPOUT, ids_np.shape[0])
            sent = np.asarray(self._bcast(ids_np))
            return self._exec_swapout(sent)

        return self._traced_run(("swapout", ids_np.shape[0]), op)

    def _exec_swapout(self, ids: np.ndarray):
        out = self._k_swapout(
            self.state, self._global(ids.astype(np.int32)),
            kv_heads=self.cfg.kv_heads,
        )
        return tuple(self._read(x) for x in out)

    def _device_swapin(self, ids, arrays) -> None:
        """Leader: broadcast ids + the as-stored page bytes, then every
        process scatters them back into its own shard of the pools.
        The snapshot rides the op stream by value, like every other
        device input — followers hold no swap state between ops."""
        self._check_live()
        ids_np = np.asarray(ids, np.int32)
        arrs = tuple(np.asarray(a) for a in arrays)

        # Deferred (rung 23): the snapshot bytes ride the next flush's
        # frame — a swap-in immediately followed by the window dispatch
        # that needed those pages pays one broadcast, not two.
        self._queue_op(
            (OP_SWAPIN, ids_np.shape[0], 0, 0), (ids_np,) + arrs,
            lambda: self._exec_swapin(ids_np, arrs),
        )

    def _exec_swapin(self, ids: np.ndarray, arrays: tuple) -> None:
        self.state = self._k_swapin(
            self.state, self._global(ids.astype(np.int32)),
            tuple(self._global(a) for a in arrays),
        )

    def _device_cow(self, src: int, dst: int) -> None:
        """Leader: broadcast the (src, dst) pair, then every process
        runs the same jitted page copy on its own pool shard. Deferred
        like a swap-in (rung 23): the COW at an admission rides the
        next flush's frame with the table sync and prefill dispatch
        that follow it, so divergence costs no extra collective."""
        self._check_live()
        pair = np.asarray([src, dst], np.int32)
        self._queue_op(
            (OP_COWP, int(src), int(dst), 0), (pair,),
            lambda: self._exec_cow(pair),
        )

    def _exec_cow(self, pair: np.ndarray) -> None:
        self.state = self._k_cow(
            self.state, self._global(pair.astype(np.int32))
        )

    def _swap_templates(self, n: int) -> tuple:
        """Follower zero templates for an OP_SWAPIN payload of ``n``
        pages: shapes/dtypes must match the leader's broadcast exactly
        (as stored — [L, n, page, K, Dh] pools plus fp32 scale slabs
        for an int8 pool)."""
        pk = self.state.pool_k
        shape = (pk.shape[0], n, pk.shape[2],
                 self.cfg.kv_heads, self.cfg.d_head)
        out = [np.zeros((n,), np.int32),
               np.zeros(shape, pk.dtype), np.zeros(shape, pk.dtype)]
        if self.kv_quantized:
            out += [np.zeros(shape[:-1], np.float32),
                    np.zeros(shape[:-1], np.float32)]
        return tuple(out)

    def stop(self) -> None:
        """Leader: release the followers (end of serve). Idempotent —
        the serving layer calls this from ``close()`` UNDER the server
        lock (after the decode loop has exited), which serializes it
        after any in-flight request thread's cache call and makes the
        flag check atomic; a second STOP would be a collective the
        departed followers never join. After stop, table syncs become
        local no-ops (teardown still releases slots) and device ops
        refuse loudly.

        Deadline-bounded like every other op: if the followers are
        already dead the STOP broadcast would wedge ``close()`` — the
        stream is skipped when it has latched dead, and a fresh wedge
        here is swallowed after its budget (close() must return; the
        followers it failed to release are lost either way)."""
        if self._stopped:
            return
        self._stopped = True
        # Buffered coalescable ops die here unbroadcast: post-stop
        # device state is irrelevant (the followers are released and
        # teardown syncs are already local no-ops).
        self._discard_pending_ops()
        if self._ops.dead is not None:
            return  # stream already wedged; nothing left to release
        try:
            # STOP is a bare header — no compilation — so it gets the
            # steady budget even as a first use.
            self._traced_run(("stop",), lambda: self._send_header(OP_STOP),
                          budget_s=self._ops.steady_s)
        except DeviceOpTimeout:
            pass

    def reform(self, *, budget_s: float | None = None) -> None:
        """Leader: replace a dead op stream and re-form the slice
        (recovery supervisor, runtime/recovery.py).

        The dead :class:`DeadlineRunner`'s worker is parked on the
        wedged collective forever — it is shut down and abandoned, and
        a FRESH runner over the SAME :class:`OpBudgets` (compiled
        programs survived, so already-seen keys keep steady budgets)
        takes its place. Then one deadline-bounded **barrier SYNC**
        flows through it: a follower that re-entered
        :func:`follow_paged` replays it as its first op, re-syncing
        tables/lengths, and its success proves every follower is back
        in the collective. On timeout the fresh runner latches dead and
        the typed :class:`SliceFollowerLost` propagates — the old
        (also dead) stream state is effectively unchanged and the
        caller's next attempt, or escalation, takes over.

        ``budget_s`` bounds the barrier (None = the stream's steady
        budget — the SYNC program was compiled long before the
        failure). Raises PagedCacheError after ``stop()``: released
        followers are gone by contract, not by failure.
        """
        if self._stopped:
            raise PagedCacheError(
                "slice serve is stopped — the followers were released, "
                "not lost; there is nothing to re-form"
            )
        old, self._ops = self._ops, DeadlineRunner(
            self._op_budgets, failure=SliceFollowerLost,
            name="kvedge-slice-ops",
        )
        old.shutdown()
        # Ops buffered before the failure never reached the followers
        # and never ran on the leader either — and the barrier SYNC
        # below re-syncs tables from the authoritative host copies, so
        # replaying them into the fresh stream would be a double-apply.
        self._discard_pending_ops()
        # Any in-flight pipelined window died with the old stream; the
        # revived serving loop restarts from host tokens (its first
        # dispatch is never a carry), so the stale device carry must
        # not survive into the new stream.
        self.drop_carry()
        tables = np.asarray(self._host_tables, np.int32)
        lengths = np.asarray(self._host_lengths, np.int32)

        def op():
            self._send_header(OP_SYNC)
            return self._bcast((tables, lengths))

        try:
            got = self._ops.run(
                ("reform-barrier",), op,
                budget_s=budget_s if budget_s is not None
                else self._ops.steady_s,
            )
        except SliceFollowerLost:
            # The fresh stream latched dead on the barrier: the
            # followers are still gone. State is exactly as before the
            # call (a dead stream installed) — re-entrant for the next
            # attempt.
            raise
        t, l = got
        self._apply_sync(np.asarray(t), np.asarray(l))

    # ---- follower side ---------------------------------------------------

    def _follow_op(self, params) -> bool:
        """Receive and replay one op. Returns False on STOP."""
        hdr = np.asarray(self._bcast(np.zeros(_HEADER_LEN, np.int64)))
        op, a, b, c = (int(v) for v in hdr)
        if op == OP_STOP:
            return False
        # Per-follower replay span (cat "slice-follower"): stamped from
        # AFTER the header lands (the header wait is leader idle time,
        # not this follower's work) through payload receive + replay, so
        # each host's own contribution to a slow collective is visible
        # in its own timeline.
        tr = getattr(self, "tracer", None)
        t0 = tr.now() if tr is not None else 0.0
        if op in _COALESCABLE:
            # One zero-template broadcast shaped by _multi_templates —
            # the same shape table that carves OP_MULTI frames — then
            # the shared replay path. Bare and frame-carried ops are
            # identical past the receive.
            payload = [
                np.asarray(x) for x in self._bcast(tuple(
                    np.zeros(shape, dtype)
                    for shape, dtype in self._multi_templates(op, a, b, c)
                ))
            ]
            self._replay_packed(params, op, a, b, c, payload)
        elif op == OP_MULTI:
            # a = op count, b = frame bytes: one uint8 broadcast, then
            # carve [header | arrays]* by the embedded headers and
            # replay each through the same exec path, in frame order.
            frame = np.asarray(self._bcast(np.zeros((b,), np.uint8)))
            off = 0
            for _ in range(a):
                sub = np.frombuffer(
                    frame.data, np.int64, count=_HEADER_LEN, offset=off)
                off += _HEADER_LEN * 8
                sop, sa, sb, sc = (int(v) for v in sub)
                payload = []
                for shape, dtype in self._multi_templates(sop, sa, sb, sc):
                    count = int(np.prod(shape, dtype=np.int64))
                    arr = np.frombuffer(
                        frame.data, dtype, count=count, offset=off,
                    ).reshape(shape)
                    off += arr.nbytes
                    payload.append(arr)
                self._replay_packed(params, sop, sa, sb, sc, payload)
        elif op == OP_PREFILL:
            tokens = self._bcast(np.zeros((c,), np.int32))
            self._exec_prefill(params, np.asarray(tokens), a, b)
        elif op == OP_STEP:
            tokens, mask = self._bcast((
                np.zeros((self.slots,), np.int32),
                np.zeros((self.slots,), bool),
            ))
            self._exec_step(params, np.asarray(tokens), np.asarray(mask))
        elif op == OP_SWAPOUT:
            # a = page count. The gather's replicated result is
            # discarded — only the leader's host copy becomes the
            # snapshot; the follower just joins the collective.
            ids = self._bcast(np.zeros((a,), np.int32))
            self._exec_swapout(np.asarray(ids))
        else:  # pragma: no cover - protocol corruption is slice-fatal
            raise PagedCacheError(f"unknown slice-serve op {op}")
        if tr is not None:
            tr.span(_OP_NAMES.get(op, str(op)), "slice-follower", t0,
                    args={"op": op})
        return True


def follow_paged(cache: SlicePagedKVCache, params) -> None:
    """Follower loop: replay the leader's op stream until STOP.

    An exception here means this follower fell out of the collective
    (the leader's deadline watchdog will type it SliceFollowerLost and
    degrade the pool). The caller (runtime/workload.py) RE-ENTERS this
    loop a bounded number of times: the rejoined follower's first
    received op is the leader's reformation barrier SYNC (a shape it
    always knows how to replay), which restores its tables/lengths and
    puts it back in lockstep. Only when the rejoin budget is exhausted
    does the caller let the pod die — the StatefulSet restart remains
    the recovery path of last resort.
    """
    while cache._follow_op(params):
        pass
