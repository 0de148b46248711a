"""Continuous-batching generation server over the paged KV cache.

The request-level serving loop the paged cache (models/kvcache.py) exists
for: many concurrent requests with different prompt lengths and budgets
share one page pool and ONE batched decode step. A request joins
mid-stream (admit + per-sequence prefill into a free slot), rides the
batched ``step`` with whatever else is in flight, and leaves when its
budget is done (pages released back to the pool) — no request ever waits
for another to finish, which is the whole point of continuous batching
over static batches.

TPU-first split, same as the cache it wraps: the decode loop is one
batched jitted step over all ``slots`` regardless of occupancy (static
shapes, no retracing as requests come and go); admission, slot
assignment, and page-budget reservation are host-side Python under one
lock. Greedy decode here agrees token-for-token with the contiguous
:func:`~kvedge_tpu.models.decode.generate` — the paged attention math
matches decode.py exactly, and tests/test_serving.py pins the
equivalence under concurrency.

The reference has no serving of any kind (SURVEY.md §0); this is the
capability the repo's own README listed as future work.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
import hashlib
import heapq
import json
import queue
import threading
import time

import numpy as np

from kvedge_tpu.runtime.failures import (
    PageAccountingError,
    PoolPoisoned,
    ServingFailure,
    classify_failure,
)
from kvedge_tpu.runtime.journal import JournalEntry, RequestJournal
from kvedge_tpu.models.kvcache import FIRST_ON_DEVICE
from kvedge_tpu.models.scheduler import AdmissionScheduler, _Hist
from kvedge_tpu.runtime.tracing import (
    LOOP_PHASES,
    REQUEST_STATES,
    Hold,
    LockLedger,
    PhaseClock,
    PhaseSum,
    TimedLock,
)

# Stream sentinel objects (token queue carries ints, then one of these).
_STREAM_DONE = object()


def _raw_key_data(key) -> np.ndarray:
    """Raw uint32 key data from a PRNG key, typed or legacy — the form
    that crosses host/process boundaries (the sampled-window dispatch
    and the slice op-stream); kvcache wraps it back on device with the
    DEFAULT impl, so a typed key built with any other PRNG impl is
    rejected here, per-request at submit — not deep in the decode loop
    where the failure would poison every co-tenant."""
    import jax
    import jax.numpy as jnp

    arr = jnp.asarray(key)
    if jnp.issubdtype(arr.dtype, jax.dtypes.prng_key):
        default = str(jax.random.key_impl(jax.random.key(0)))
        got = str(jax.random.key_impl(arr))
        if got != default:
            raise ValueError(
                f"sampling seed key uses PRNG impl {got}; the serving "
                f"key schedule is defined on the default impl "
                f"({default}) — pass a jax.random.PRNGKey/key() seed"
            )
        return np.asarray(jax.random.key_data(arr))
    return np.asarray(arr, np.uint32)


def weights_summary(params: dict) -> tuple[float, str]:
    """(GB, dtype) of the tree the programs read, from the leaves'
    shapes alone (no device call): all its bytes, and the dtype that
    holds most of them — ``bfloat16`` for a tree cast at load
    (``transformer.serving_params``: the norm gains and the router stay
    float32), ``float32`` for masters handed over as they are."""
    import jax

    by_dtype: dict[str, int] = {}
    for leaf in jax.tree_util.tree_leaves(params):
        name = str(leaf.dtype)
        by_dtype[name] = by_dtype.get(name, 0) + leaf.nbytes
    return sum(by_dtype.values()) / 1e9, max(by_dtype, key=by_dtype.get)


class ServerBusy(RuntimeError):
    """No slot/page capacity became available within the timeout."""


class ServerOverloaded(ServerBusy):
    """Shed at admission by the scheduler's overload watermarks —
    raised BEFORE parking, so the caller pays one RTT instead of its
    full timeout. ``retry_after_s`` (when measurable) is the measured
    per-class queue wait; the HTTP layer forwards it as a hint."""

    def __init__(self, msg: str, retry_after_s: float | None = None):
        super().__init__(msg)
        if retry_after_s is not None:
            self.retry_after_s = retry_after_s


class ServerClosed(RuntimeError):
    """The server was shut down."""


class RequestCancelled(RuntimeError):
    """The request was cancelled (consumer disconnect / explicit)."""


# eq=False: a request is its identity (hashable — the journal keys on
# the live object), never field-equality over mutable token lists.
@dataclasses.dataclass(eq=False)
class _Request:
    prompt: list[int]
    n_new: int
    # (seed_key, temperature, top_p) or None for greedy. The key schedule
    # is decode.py's: token t samples with fold_in(seed_key, t) — a pure
    # function of the request, so batch composition changes nothing.
    sampling: tuple | None = None
    # The pending token: produced, not yet emitted, the next window's
    # input for this row. ``FIRST_ON_DEVICE`` while it is the first
    # token and the host has not read it: picked on the device after
    # the last prefill chunk (``first_dev``, the scalar) and fed to
    # the row's first window from there; the host reads it when it
    # harvests that window (``_first_token_locked``), and only then
    # is there a time to first token.
    next_token: int = -1
    first_dev: object = None
    # Early-termination token (rung 23): generation finishes the moment
    # this token is PRODUCED — it is emitted as the final token, then
    # the request completes with its remaining budget unused. -1 (no
    # stop token) can never match: every produced token id is >= 0, so
    # stop-free traffic takes bit-identical paths with zero compares on
    # device (the capped window kernels carry the per-row stop id and
    # report the first hit in the packed finish rows).
    stop_token: int = -1
    # Device/host-detected stop whose finish had to be DEFERRED: the
    # truncated stream (stop token last) is already emitted, but an
    # in-flight window still touches this slot, so the slot and pages
    # must survive until that window retires. The forced boundary's
    # finish sweep completes it.
    stopped: bool = False
    # Pages reserved at admission. With a prefix-cache hit
    # this is the PRIVATE part only (pages_needed − full shared pages);
    # the shared pages are covered by leases (serving._lease).
    pages_reserved: int = 0
    # Prefix sharing (rung 24): the FULL shared pages this request's
    # table starts on (leased, registry-refcounted, read-only) and the
    # trie node at that depth — the journal shadow's key. A partially
    # shared page is COWed at admission and is private, never listed
    # here. Both reset when a preempt/requeue round-trip materializes
    # the request as self-contained bytes.
    shared_pages: tuple = ()
    prefix_node: "int | None" = None
    # Raw uint32 data of the sampling seed key, fetched ONCE at
    # admission (the sampled-window dispatch needs it host-side every
    # window; re-fetching from the device key per window would add a
    # transfer per request per window).
    key_data: "np.ndarray | None" = None
    generated: list[int] = dataclasses.field(default_factory=list)
    done: threading.Event = dataclasses.field(
        default_factory=threading.Event
    )
    error: Exception | None = None
    # Set for streaming requests: every generated token is put here as it
    # lands, then _STREAM_DONE (or the failing exception).
    stream: "queue.SimpleQueue | None" = None
    # Cancellation request (consumer gone / explicit): honored at the
    # next loop iteration — the step/window in flight completes first.
    cancelled: bool = False
    # Scheduler (models/scheduler.py): the request's priority class,
    # its admission ticket number (kept across preemption so a resumed
    # request re-queues ahead of later arrivals), and the admission
    # sequence victim selection orders by (preempt the LATEST admitted
    # request of the lowest class — least progress lost).
    pclass: str = "interactive"
    ticket_no: int = -1
    admit_seq: int = -1
    # Overlap pipeline bookkeeping: tokens this request will receive
    # from windows that are DISPATCHED but not yet harvested.
    # len(generated) + inflight is the request's committed position —
    # the number the next window's budget cap is computed from, so a
    # speculative dispatch can never outrun the budget even though the
    # host hasn't seen its tokens yet.
    inflight: int = 0
    # Tracing (runtime/tracing.py): the request ID minted/accepted at
    # HTTP ingress (echoed as X-Request-Id) and the sampling decision,
    # made ONCE at submit so all of this request's spans share fate.
    # The stage stamps (tracer clock) feed the serve_ttft_ms and
    # queue-vs-decode histograms; they are recorded even with tracing
    # off (perf_counter is cheap, histograms are always-on metrics).
    rid: str = ""
    trace: bool = False
    t_submit: float = 0.0
    t_admit: float = 0.0
    # First-token stamp (the TTFT observation instant): with the final
    # finish stamp it yields the request's mean inter-token gap — the
    # per-request ITL the rung-25 SLO engine computes its p99 over.
    t_first: float = 0.0
    # The request's ledger (ISSUE 38): what it is doing now
    # (runtime/tracing.py REQUEST_STATES) and since when, and the
    # milliseconds each state has had. A boundary is one stamp: the
    # state before it ends and the next one starts on it, so at the
    # end (``t_done``) the states add up to ``t_done - t_submit``.
    state: str = "queued"
    t_state: float = 0.0
    state_ms: dict = dataclasses.field(default_factory=dict)
    t_done: float = 0.0
    # The put of the first token on the stream (or its append): where
    # ``first_emit_ms`` ends and the handler's ``first_write`` starts.
    t_emit: float = 0.0
    # Exactly-once delivery watermark (rung 22): tokens at indices
    # below this were already streamed to the consumer before a
    # journal restore rewound ``generated`` to the checkpoint —
    # replayed decode regenerates them bit-identically (greedy argmax
    # / the positional fold_in key schedule) and ``_emit`` records
    # them WITHOUT re-streaming. 0 (the normal path) streams every
    # token.
    stream_resume_at: int = 0

    def pick(self, logits_row, step: int) -> int:
        """Next token from a [V] logits row, greedy or sampled. Used at
        prefill (one row); the decode windows pick every row's token
        on the device (``kvcache.dispatch_window`` and
        ``dispatch_window_sampled``)."""
        import jax.numpy as jnp

        if self.sampling is None:
            return int(jnp.argmax(logits_row))
        from kvedge_tpu.models.decode import row_sample_keys, sample_token

        seed_key, temperature, top_p = self.sampling
        keys = row_sample_keys(seed_key[None], step)
        return int(sample_token(
            logits_row[None], keys, temperature, top_p
        )[0])


class StreamHandle:
    """Iterator over a streaming request's tokens + cancellation.

    Iteration semantics match the old generator exactly (tests and the
    HTTP layer consume it with ``next``/``for``); ``cancel()`` is the
    new client-disconnect hook — it frees the request's slot and pages
    at the next step/window boundary instead of decoding out the
    reserved budget.
    """

    def __init__(self, server: "PagedGenerationServer", req: _Request):
        self._server = server
        self._req = req
        self._produced = 0

    def __iter__(self) -> "StreamHandle":
        return self

    def __next__(self) -> int:
        if self._produced >= self._req.n_new:
            raise StopIteration
        item = self._req.stream.get()
        if item is _STREAM_DONE:
            raise StopIteration
        if isinstance(item, Exception):
            raise item
        self._produced += 1
        return item

    def cancel(self) -> None:
        self._server.cancel(self._req)

    def first_written(self) -> None:
        """The consumer's line with the request's first token is
        flushed (the HTTP handler says so, once): the time since that
        token's put on this stream is the request's ``first_write``.
        One append from the caller's thread, no lock."""
        t_emit = self._req.t_emit
        if t_emit:
            self._server._first_writes.append(
                (time.perf_counter() - t_emit) * 1e3)


class PagedGenerationServer:
    """Continuous-batching decode over a :class:`PagedKVCache` — greedy
    by default, per-request nucleus sampling via ``submit(sampling=...)``
    (same key schedule and filter as the contiguous backend).

    ``submit`` blocks the calling thread until its tokens are ready (the
    HTTP handler model); the single background decode thread advances
    every in-flight request one token per batched step. Admission
    reserves each request's WORST-CASE page budget
    (``ceil((prompt + n_new) / page_size)``) up front, so ``grow`` can
    never exhaust the pool mid-decode — a request either gets capacity
    at admission or waits/queues, it never dies halfway.
    """

    def __init__(self, params: dict, cfg, *, slots: int = 4,
                 pages: int = 64, page_size: int = 16,
                 prefill_chunk: int = 0, prefix_cache: bool = True,
                 window: int | str = 64,
                 window_min: int = 1, window_max: int = 256,
                 kv_dtype: str = "", cache=None,
                 retry_after_s: float | None = None,
                 sched_policy: str = "strict",
                 sched_weights: dict | None = None,
                 sched_max_queue_depth: int = 0,
                 sched_max_queue_wait_s: float = 0.0,
                 sched_swap_budget_mb: int = 0,
                 min_bucket: int = 0,
                 page_low_watermark: float = 0.0,
                 page_high_watermark: float = 0.0,
                 tracer=None, debug_locks: bool = False,
                 checkpoint_every: int = 0,
                 journal_budget_mb: int = 0,
                 prefix_host_mb: int = 0,
                 debug_pages: bool = False,
                 slo=None, slo_shed: bool = False,
                 occupancy_ring: int = 0):
        from kvedge_tpu.models.kvcache import (
            PagedKVCache, settle_paged_attention,
        )

        settled = settle_paged_attention(
            cfg if cache is None else cache.cfg, params)
        if cache is None:
            if settled is not cfg:
                print("[kvedge-serve] paged_attention = 'auto' takes the "
                      "gather: the params span several devices and the "
                      "Pallas decode kernel cannot be partitioned",
                      flush=True)
            cfg = settled
        elif settled is not cache.cfg:
            # An injected pool carries its own cfg and is already
            # built: this server cannot re-route it, only refuse.
            raise ValueError(
                "the injected cache would trace the Pallas decode "
                "kernel over params that span several devices; build "
                "it with paged_attention='gather'")
        if cfg.layer_pattern and prefix_cache:
            raise ValueError(
                "prefix_cache (serving_prefix_cache) cannot serve a "
                "patterned block (layer_pattern): " + (
                    "a shared page that a 'window' layer has given back "
                    "cannot be attended again" if cfg.window_layers else
                    "a recurrent state holds a row's whole prefix in one "
                    "array and cannot be shared by page")
                + "; pass prefix_cache=False")
        self._params = params
        self._weights_gb, self._weights_dtype = weights_summary(params)
        self._cfg = cfg
        # Request-scoped tracing (runtime/tracing.py, SERVING.md rung
        # 18): a shared flight recorder, or None (off — every emission
        # site guards on it). Held as a plain attribute with no device
        # or thread state, so it survives revive() and slice
        # reformation unchanged.
        self.tracer = tracer
        # Exact counts at the loop's own boundaries (plain ints, under
        # the lock): decode steps the device ran (sum of window
        # lengths), row-steps that produced a token, row-steps the
        # device computed (bucket x window), pages holding tokens of
        # live rows x window length, and tokens recorded into requests.
        self._decode_steps = 0
        self._decode_row_steps = 0
        self._decode_bucket_steps = 0
        self._pages_live_steps = 0
        self._window_pages_live_steps = 0  # the same, the window pool's
        self._tokens_emitted = 0
        # Device-window cap (steps per dispatched greedy decode scan).
        # The host round trip per dispatch is the paged path's tax — a
        # window amortizes it ~window x.
        # Round 4 hardwired the cap to page_size (16), which chained
        # throughput to that round trip (VERDICT r4 weak #2); the cap
        # is now an operator knob ([payload] serving_window, default
        # 64). The compiled program set stays the powers of two
        # {1, 2..window} (see _dispatch_window_locked); the tradeoff
        # is admission latency — a submitter joins at the next window
        # boundary, so worst-case wait grows with the window
        # (SERVING.md).
        # "auto" hands the choice to the online controller (SERVING.md
        # rung 26): _window starts at the bounds cap and is re-picked
        # at every harvested window from EWMAs of the measured host
        # turnaround R and per-step device time t — the smallest power
        # of two with W*t >= R, the saturation point of the rung-16
        # law. The controller is plain data owned by this server and
        # mutated only under the work lock; revive() and slice
        # reformation never recreate it, so its learned state rides
        # through recovery (tests/test_autotune.py).
        self._autotune = None
        if window == "auto":
            from kvedge_tpu.runtime.autotune import WindowController
            self._autotune = WindowController(lo=window_min,
                                              hi=window_max)
            window = self._autotune.window()
        elif isinstance(window, str):
            raise ValueError("window must be an int >= 1 or 'auto'")
        if window < 1:
            raise ValueError("window must be >= 1")
        self._window = window
        # Double-buffered window dispatch: the decode loop enqueues
        # window N+1 before harvesting window N, so the host's round
        # trip and bookkeeping for N hide under the device's execution
        # of N+1 — steps/s moves from 1/(R + W*t) toward
        # 1/max(R, W*t) (SERVING.md rung 16). The loop falls back to a
        # non-overlapped boundary whenever exactness needs one:
        # admissions, cancellations.
        # The one in-flight (dispatched, unharvested) window record:
        # {"window": steps, "parts": [(slot, req, adv)], "handle":
        # unforced device tokens, "t0": dispatch stamp}. Depth is at
        # most 1 — double buffering, not an unbounded queue — so the
        # admission-latency price is bounded at one extra window.
        self._inflight: dict | None = None
        # The window the loop is reading back with the lock released
        # (``_harvest_locked``): dispatched, unreconciled, and no
        # longer (or not only) ``_inflight``.
        self._harvesting: dict | None = None
        self._overlap_windows = 0
        # Per-window latency histograms (ms; exported via /metrics):
        # dispatch->harvest wall time (the device+RTT leg), host
        # processing time (the work the overlap hides), and the
        # pipeline depth observed at each dispatch.
        self._hist_rtt = _Hist((1.0, 2.0, 5.0, 10.0, 20.0, 50.0,
                                100.0, 200.0, 500.0, 1000.0, 2000.0))
        self._hist_host = _Hist((0.1, 0.2, 0.5, 1.0, 2.0, 5.0, 10.0,
                                 20.0, 50.0, 100.0))
        self._hist_depth = _Hist((0.0, 1.0))
        # What keeps a window queued behind the running one and what
        # does not: rows that entered an overlapped window from the
        # host's row (a newcomer joins on the carry), and the times the
        # pipeline fell back to a boundary, by cause
        # (pipeline_collapses_total{cause=...}; _boundary_wanted_locked
        # names them).
        self._pipeline_joins = 0
        # First tokens picked and kept on the device: against the
        # count of ``phase_ms["admit/first_pick"]``, the share of
        # picks nobody waited for with the lock in hand.
        self._first_tokens_on_device = 0
        self._pipeline_collapses = dict.fromkeys(
            ("cancel", "newcomer", "bucket", "stop", "checkpoint",
             "scheduler"), 0)
        # Per-request stage histograms (ms; always on — cheap
        # perf_counter stamps, independent of the tracer): time to
        # first token (submit -> prefill logits picked), the
        # queue-vs-decode split (submit -> admit, admit -> done).
        # The log-spaced tail past 30 s keeps overload-regime p99s
        # measurable (openloop wait p99s used to clamp at the 30 000
        # cap); the pre-existing edges are unchanged so cumulative
        # bucket deltas stay comparable across bench snapshots.
        _stage_edges = (1.0, 2.0, 5.0, 10.0, 20.0, 50.0, 100.0,
                        200.0, 500.0, 1000.0, 2000.0, 5000.0,
                        10000.0, 30000.0, 60000.0, 120000.0,
                        240000.0, 480000.0, 960000.0)
        self._hist_ttft = _Hist(_stage_edges)
        self._hist_queue = _Hist(_stage_edges)
        self._hist_decode = _Hist(_stage_edges)
        # Device-time attribution (SERVING.md rung 25): the forced
        # device sync inside each window/harvest call, timed on its
        # own. Subtracted from the dispatch->harvest RTT it proves
        # where a regression lives — device kernel vs host bookkeeping
        # vs transport. Same always-on contract as the stage hists:
        # two perf_counter stamps per WINDOW, not per token.
        self._hist_device = _Hist((1.0, 2.0, 5.0, 10.0, 20.0, 50.0,
                                   100.0, 200.0, 500.0, 1000.0,
                                   2000.0))
        # Per-request mean inter-token gap, observed once at finish
        # ((t_done - t_first) / (tokens - 1)) — the SLO engine's
        # inter-token SLI input. Cheaper and tail-honest vs stamping
        # every token: a stall inflates the request's mean.
        self._hist_itl = _Hist((0.5, 1.0, 2.0, 5.0, 10.0, 20.0,
                                50.0, 100.0, 200.0, 500.0))
        # The submit path from inside (phases admit/lock_wait and
        # admit/prefill_chunk, once per prefill chunk), and the
        # server's own delay after the first token is picked: pick to
        # that token's put on the stream (or its append, buffered).
        _wait_edges = (1.0, 2.0, 5.0, 10.0, 20.0, 50.0, 100.0, 200.0,
                       500.0, 1000.0, 2000.0, 5000.0, 10000.0)
        self._hist_prefill_wait = _Hist(_wait_edges)
        self._hist_prefill_chunk = _Hist(_wait_edges)
        self._hist_first_emit = _Hist(_wait_edges)
        # Phases (runtime/tracing.py): named stretches of the decode
        # loop's and the submit path's time, each with the accumulator
        # it adds to. Always on as these accumulators (stats() exports
        # them), profiler annotations while a capture is live, ring
        # spans when the tracer is on.
        self._phase = PhaseClock({
            "loop/lock_wait": PhaseSum(),
            "loop/wait_work": PhaseSum(),
            "loop/boundary": PhaseSum(),
            "loop/dispatch": PhaseSum(),
            "loop/harvest_wait": self._hist_device,
            "loop/emit": self._hist_host,
            "admit/lock_wait": self._hist_prefill_wait,
            "admit/prefill_chunk": self._hist_prefill_chunk,
            "admit/first_pick": PhaseSum(),
            # A block with recurrent layers zeroes the slot's state as
            # it admits a request (kvcache.PagedKVCache.admit).
            **({"admit/state_reset": PhaseSum()}
               if cfg.layer_pattern else {}),
            # A block with layers bound to a window gives the pages
            # behind the window back, on the host, lock held: at every
            # harvested decode window (inside ``loop/emit``, not a
            # phase of the loop's chain) and after every prefill chunk
            # (inside ``admit/prefill_chunk``).
            **({"loop/window_release": PhaseSum(),
                "admit/window_release": PhaseSum()}
               if cfg.window_layers else {}),
        }, tracer, chained=LOOP_PHASES)
        # The time the loop thread has run, by its own clock (what its
        # phases must add up to): finished threads in _loop_ran, the
        # live one since _loop_since, both written by that thread
        # alone, outside the lock. _lock_wait is the loop's next wait
        # for the lock, made ahead while it still holds it: how fast
        # each side asks again after a release decides who wins the
        # lock, so nothing is built between a release and the next
        # acquire that was not built there before phases.
        self._loop_ran = PhaseSum()
        self._loop_since = 0.0
        self._lock_wait = self._phase("loop/lock_wait")
        # Completion counters (goodput / shed-rate SLIs): requests
        # that finished NORMALLY and the generated tokens they
        # realized. Cancels/failures don't count — goodput is good.
        self._done_total = 0
        self._tokens_done_total = 0
        # Device-resident finish bookkeeping (rung 23): slots whose
        # NEXT boundary sweep should examine them for completion —
        # registered by every site that sets a pending token that
        # completes a budget or matches a stop token, so the sweep does
        # O(registered) work instead of scanning every active slot at
        # bucket 256. The sweep re-validates each entry; dispatch loops
        # re-register idle zero-budget rows as a self-healing backstop
        # (a missed registration costs one extra window, never a hang).
        self._finish_ready: set[int] = set()
        # Stop-terminated rows whose finish is deferred until the
        # window still touching their slot retires (_Request.stopped):
        # a positive count forces the pipeline to a boundary, where the
        # finish sweep completes them and zeroes this.
        self._stops_pending = 0
        self._stop_finishes = 0
        # Chunked prefill granule (0 = whole-prompt): long prompts land
        # in fixed-size chunks with the lock RELEASED between chunks, so
        # in-flight requests keep decoding during an admission and XLA
        # compiles per chunk length instead of per prompt length.
        self._prefill_chunk = prefill_chunk
        # An injected cache overrides the pool knobs: the multi-host
        # serve path hands in a SlicePagedKVCache whose device calls
        # span the slice (runtime/sliceserve.py); the server neither
        # knows nor cares — every cache call below already serializes
        # on the one lock, which is exactly the total-order guarantee
        # the slice protocol needs.
        if cache is not None:
            slots, pages = cache.slots, cache.num_pages
            page_size = cache.page_size
        self._cache = cache or PagedKVCache(
            cfg, slots=slots, pages=pages, page_size=page_size,
            kv_dtype=kv_dtype, min_bucket=min_bucket,
            # The most a row moves on between two givings-back: a
            # prefill chunk, or the longest decode window.
            window_advance=max(
                prefill_chunk or cfg.max_seq,
                window_max if self._autotune is not None else window),
        )
        # Can a window be waited for, and a first token kept on the
        # device, with no read through the pool (the loop then waits
        # with the work lock released)? The pool says (a slice cache:
        # no); one injected that does not say reads as always.
        self._unlocked_reads = bool(
            getattr(self._cache, "unlocked_reads", False))
        if cfg.layer_pattern:
            self._cache.reset_phase = functools.partial(
                self._phase, "admit/state_reset")
        if cfg.window_layers:
            self._cache.window_phase = functools.partial(
                self._phase, "admit/window_release")
        # Bucketed compile cache (SERVING.md rung 21): the device batch
        # dim is the cache's current BUCKET, not ``slots`` — every
        # dispatch-array site below sizes on ``self._cache.bucket``.
        # An injected cache governs its own bucketing (the slice cache
        # pins bucket == slots: the broadcast op stream fixes payload
        # shapes). A pending step-up requested by an admission that
        # found no row inside the current bucket; the decode loop
        # applies it at the next pipeline boundary.
        self._bucket_step_wanted = False
        # Free-page watermarks (fractions of the pool, 0 = off): below
        # ``low`` free-page headroom, non-top-priority admissions shed
        # with page-capacity terms instead of parking; swapped requests
        # resume only at ``high`` or better — the hysteresis that stops
        # preempt/resume thrash when the pool hovers at the edge.
        for name, v in (("page_low_watermark", page_low_watermark),
                        ("page_high_watermark", page_high_watermark)):
            if not 0.0 <= v < 1.0:
                raise ValueError(f"{name} must be in [0, 1), got {v}")
        if page_low_watermark and page_high_watermark \
                and page_low_watermark > page_high_watermark:
            raise ValueError(
                "page_low_watermark must be <= page_high_watermark"
            )
        self._page_low_wm = float(page_low_watermark)
        self._page_high_wm = float(page_high_watermark)
        # Prefix sharing: completed prompts register their page-aligned
        # prefixes here (key: token tuple -> pinned pages + LRU stamp);
        # a later prompt with the same prefix starts its table on those
        # READ-ONLY pages and prefills only the suffix. K/V depend only
        # on the prompt tokens and positions, so reuse is exact — for
        # sampled requests too. Capacity stays sound with zero
        # accounting changes: admission still reserves the WORST-CASE
        # page budget (sharing saves compute and physical pages, not
        # reservation), and registry pins are evicted LRU on demand —
        # excluding the entry being shared from — which is always
        # sufficient because every other allocation is within its own
        # reservation.
        self._prefix_enabled = prefix_cache
        # Radix trie over page-sized token blocks (NOT a dict of
        # full-prefix tuples: that costs O(len^2/page) hashing under
        # the lock per admission/registration). Node 0 is the root;
        # each node owns its out-edges {block_tuple: child_id} plus an
        # optional HBM entry {"pages": pinned page list, "last_used":
        # LRU stamp} and an optional host-tier record (rung 24b).
        # Lookup and registration walk the prompt once — O(len(prompt))
        # total hashing — and eviction prunes edge-less, entry-less,
        # host-less nodes upward so the trie never outlives its
        # residents. Node ids are monotonic and NEVER reused: the
        # journal's shadow store keys on them across evictions.
        self._prefix_nodes: dict[int, dict] = {
            0: {"parent": None, "edges": {}, "entry": None,
                "host": None},
        }
        self._prefix_entry_nodes: dict[int, dict] = {}  # id -> entry
        self._prefix_next_id = 1
        self._prefix_hits = 0
        self._prefix_lookups = 0
        self._prefix_tokens_saved = 0
        self._prefix_cow_copies = 0
        self._prefix_registrations = 0  # persistence dirty counter
        # Tiered residency (rung 24b): cold entries demote to host RAM
        # as the verbatim swapout bytes instead of being dropped, up to
        # ``prefix_host_mb`` (0 = off — evictions drop, exactly the
        # pre-rung behavior). A hit on a host-resident entry promotes
        # it back into fresh pinned pages at admission.
        if prefix_host_mb < 0:
            raise ValueError("prefix_host_mb must be >= 0")
        self._prefix_host_budget = int(prefix_host_mb) << 20
        self._prefix_host_nodes: dict[int, dict] = {}  # id -> record
        self._prefix_host_bytes = 0
        self._prefix_demotions = 0
        self._prefix_promotions = 0
        self._prefix_evictions = {
            "admission": 0, "pressure": 0, "revive": 0,
            "host_lru": 0, "host_over": 0,
        }
        # Live-sharer leases (rung 24 pricing): _reserved counts each
        # request's PRIVATE worst case plus ONE unit per distinct
        # shared prefix page any live request's table starts on —
        # shared pages are billed once, which is what lets page-gated
        # admission price an arrival at pages_needed − shared. The
        # unit belongs to the LEASE, not a request: it frees when the
        # last sharer releases, so an inheritor never loses coverage
        # because the creator finished first.
        self._lease: dict[int, int] = {}
        # Journal shadow store (rung 24c): trie node id -> the shared
        # prefix pages' verbatim swapout bytes, refcounted by the
        # journal entries that REFERENCE them instead of duplicating
        # them. Priced once against the journal budget (adjust_extra).
        self._prefix_shadow: dict[int, dict] = {}
        self._persist_stop: threading.Event | None = None
        self._persist_thread: threading.Thread | None = None
        # Registry pins live OUTSIDE any request's reservation, so the
        # cache needs a way to reclaim them when a mid-decode grow finds
        # the free list empty — otherwise one tenant's growth would
        # poison the whole server (see _relieve_pool_pressure_locked).
        self._cache.pressure_relief = self._relieve_pool_pressure_locked
        if tracer is not None:
            # Share the recorder with the cache: a slice-aware cache
            # (runtime/sliceserve.py) stamps per-op broadcast spans so
            # a slow follower is attributable; single-host caches
            # simply ignore the attribute.
            self._cache.tracer = tracer
        self._pages_total = pages
        self._reserved = 0  # worst-case pages of every in-flight request
        # The window layers' pool (kvcache.PagedState.win_pool_k; 0
        # pages where the block has none) holds every slot's cap, so a
        # slot is all an admission needs of it.
        self._window_pages_total = getattr(self._cache,
                                           "num_window_pages", 0)
        # Lock discipline ([payload] serving_debug_locks, SERVING.md
        # rung 19): the ownership-asserting DebugLock makes every
        # *_locked call and every Condition wait/notify verify the
        # calling thread actually holds the lock — the runtime twin of
        # the locklint static analyzer. Plain Lock in production.
        if debug_locks:
            from kvedge_tpu.runtime.debuglock import DebugLock
            inner = DebugLock()
        else:
            inner = threading.Lock()
        # The one lock keeps its own account (runtime/tracing.py): the
        # time it is held at all, and by whom. Every site below takes
        # it through ``self._hold(name)``. Two holders' waits are
        # phases that were there before the ledger: one record each.
        self._lock = TimedLock(inner)
        self._work = threading.Condition(self._lock)
        self._ledger = LockLedger(self._lock, tracer, waits={
            "loop": self._phase.sinks["loop/lock_wait"],
            "admit/prefill_chunk": self._hist_prefill_wait,
        })
        self._hold = self._ledger.hold
        # What finished requests did with their time, state by state
        # (the states of each add up to its life), and the handlers'
        # first writes: each adds its own from its own thread, and a
        # snapshot folds them in.
        self._request_ms = {state: PhaseSum() for state in REQUEST_STATES}
        self._first_writes: collections.deque = collections.deque()
        self._first_write_ms = PhaseSum()
        # The newest core snapshot a lock holder published: what
        # stats() returns when it cannot have the lock at once.
        self._published: dict | None = None
        # Admission scheduler (models/scheduler.py, SERVING.md rung 17):
        # per-class ticketed queue + preemption/shed policy. It SHARES
        # the server lock — queue order, slot state, and page
        # accounting mutate atomically together (invariant 5). With the
        # defaults (strict policy, single implicit class, no
        # watermarks, no swap budget) it degenerates to a fair FIFO:
        # every pre-scheduler exactness test runs unchanged on top of
        # it.
        self._sched = AdmissionScheduler(
            self._lock, policy=sched_policy, weights=sched_weights,
            max_queue_depth=sched_max_queue_depth,
            max_queue_wait_s=sched_max_queue_wait_s,
            swap_budget_mb=sched_swap_budget_mb,
            tracer=tracer,
        )
        # Host bytes one swapped-out page costs (k + v + int8 scale
        # slabs) — victim-sized budget checks BEFORE paying the device
        # gather. Filled lazily: the pool arrays exist after the cache
        # does.
        self._swap_page_bytes: int | None = None
        self._active: dict[int, _Request] = {}
        # Min-heap: allocation always takes the LOWEST free slot, so the
        # occupied set stays dense at the bottom of the batch dim — the
        # property that lets the bucket step back down when load drops.
        self._free_slots = list(range(slots))
        self._closed = False
        self._draining = False
        # Degraded mode (runtime/failures.py): a decode-loop failure
        # poisons the pool — in-flight waiters get the typed failure,
        # new submits are refused with a retry-after hint, and the
        # reason is exposed lock-free so /healthz can flip to 503
        # without touching the server lock.
        self._poison: ServingFailure | None = None
        self._degraded_reason: str | None = None
        # Optional observer (set by the workload layer): called once,
        # outside the lock, when the pool poisons — e.g. to persist a
        # post-mortem failure record in the state dir.
        self.on_degraded = None
        # Retry-after hint for poisoned-pool refusals: a static default
        # ([payload] serving_retry_after_s; None = taxonomy default),
        # overridden live by ``retry_after_hint`` — a () -> float|None
        # callable the recovery supervisor installs so refusals carry
        # the MEASURED recovery time while a heal is in flight.
        self._retry_after_s = retry_after_s
        self.retry_after_hint = None
        # Boundary checkpointing (runtime/journal.py, SERVING.md rung
        # 22): every ``checkpoint_every`` pipeline boundaries the loop
        # journals each live request's resumable state — KV pages as
        # the verbatim swapout bytes, token log, pending token,
        # original ticket — so _poison_locked can DIVERT journaled
        # requests (waiters stay parked) and revive()/reform re-admits
        # them bit-identically instead of failing them. 0 = off:
        # today's fail-everything poison semantics, zero cost.
        if checkpoint_every < 0:
            raise ValueError("checkpoint_every must be >= 0")
        if journal_budget_mb < 0:
            raise ValueError("journal_budget_mb must be >= 0")
        self._checkpoint_every = int(checkpoint_every)
        self._journal = RequestJournal(
            max_bytes=journal_budget_mb * (1 << 20)
        )
        # Boundaries-or-harvests since the last checkpoint: a saturated
        # overlap pipeline rarely visits a boundary on its own, so the
        # clock also advances per harvested window and an overdue clock
        # collapses the pipeline (_boundary_wanted_locked) — cadence N
        # means "at most ~N windows of decode progress ever at risk".
        self._ckpt_clock = 0
        self._checkpoints_total = 0
        self._checkpoint_skipped = 0
        # Delta-skipped checkpoints (rung 26): live requests whose
        # standing journal entry already matches (gen_len, next_token)
        # — re-serializing would be byte-identical, so the boundary
        # skips their device gather entirely.
        self._checkpoints_unchanged = 0
        self._journal_restores = 0
        # Page-conservation audit ([payload] serving_debug_pages): the
        # chaos soak's invariant 1, checked at every quiescent boundary
        # and raised as a typed PageAccountingError on violation.
        self._debug_pages = bool(debug_pages)
        # The capacity bucket rung at poison time: revive restores it
        # (instead of resetting to the bottom rung) so a loaded server
        # doesn't pay a retrace storm the moment traffic returns.
        self._prebucket = 0
        # Recorded by start_prefix_persistence so a poisoned-but-
        # readable pool can emergency-dump its warm prefixes on the
        # way down.
        self._persist_path: str | None = None
        self._persist_fp: str | None = None
        # Admissions whose chunked prefill is in flight (slot granted,
        # not yet in _active): the decode loop must not exit — and a
        # drain must not report done — while any exist, or their
        # waiters would hang on a request no loop will ever serve.
        self._prefilling = 0
        # SLO engine (runtime/slo.py, SERVING.md rung 25): rolling
        # multi-window SLIs from deltas of the cumulative histograms
        # above, fed one snapshot per quiescent boundary. None = off
        # (the default) — the boundary feed guards on it, so off costs
        # one attribute read per boundary and tokens are bit-identical.
        self._slo = None
        if slo is not None:
            from kvedge_tpu.runtime.slo import SloEngine
            self._slo = SloEngine(slo)
            if slo_shed:
                # Knob-gated burn-rate input to the rung-17 shed
                # decision: while the multi-window alert fires,
                # non-top classes shed at the door. Off by default —
                # the scheduler's burn_input stays None and every
                # shed path is byte-for-byte the rung-17 one.
                self._sched.burn_input = self._slo.alert
        elif slo_shed:
            raise ValueError("slo_shed needs SLO objectives (slo=...)")
        # Occupancy timeline ring (rung 25): HBM/page/bucket/prefix
        # residency gauges sampled at quiescent boundaries. 0 = off.
        # With tracing on, the ring doubles as the Chrome counter
        # track source so Perfetto draws occupancy under the spans.
        self._occ_ring = None
        if occupancy_ring:
            from kvedge_tpu.runtime.slo import OccupancyRing
            self._occ_ring = OccupancyRing(occupancy_ring)
            if tracer is not None:
                tracer.counter_source = self._occ_ring.chrome_counters
        if debug_locks:
            # Wrap every bound *_locked method (server AND the
            # scheduler sharing its lock) to assert ownership at call
            # time — executed L1, before the decode thread exists so
            # the loop only ever sees the checked bindings.
            from kvedge_tpu.runtime.debuglock import (
                instrument_locked_methods,
            )
            instrument_locked_methods(self, self._lock)
            instrument_locked_methods(self._sched, self._lock)
        # Installed AFTER lock instrumentation so the journal's drop
        # observer is the (possibly ownership-checked) bound method.
        # Every journal call site holds the work lock, so the observer
        # runs under it too.
        self._journal.on_drop = self._journal_drop_locked
        self._thread = threading.Thread(
            target=self._loop, name="kvedge-paged-serve", daemon=True
        )
        self._thread.start()

    # ---- public API ------------------------------------------------------

    def submit(self, prompt: list[int], n_new: int,
               timeout: float = 120.0, sampling: tuple | None = None,
               priority: str = "interactive",
               deadline_ms: int | None = None,
               request_id: str = "",
               stop_token: int | None = None) -> list[int]:
        """Blocking generate: returns the prompt plus UP TO ``n_new``
        generated tokens.

        Greedy unless ``sampling = (seed_key, temperature, top_p)`` —
        then token ``t`` samples with ``fold_in(seed_key, t)`` through
        the same nucleus filter as the contiguous backend, so the two
        produce identical tokens for identical requests.

        ``stop_token`` ends generation early: the first produced
        occurrence is emitted as the final token and the rest of the
        budget goes unused (admission still reserves the worst case —
        early stops return pages sooner, they never change capacity
        semantics). Detection runs ON DEVICE inside the capped window
        scans and comes back in the window's packed finish rows, so a
        stop costs no extra host work per token.

        ``priority`` names the request's scheduling class
        (``interactive``/``batch``); ``deadline_ms`` optionally bounds
        the ADMISSION wait tighter than ``timeout`` and lets the
        scheduler shed the request up front when the measured queue
        wait makes the deadline unmeetable. Raises :class:`ServerBusy`
        when capacity doesn't free up in time (a subclass,
        :class:`ServerOverloaded`, when shed early by the overload
        watermarks), ValueError for requests that can never fit.
        """
        req = self._start(prompt, n_new, timeout, sampling,
                          stream=False, priority=priority,
                          deadline_ms=deadline_ms,
                          request_id=request_id,
                          stop_token=stop_token)
        req.done.wait()
        if req.error is not None:
            raise req.error
        return req.prompt + req.generated

    def submit_stream(self, prompt: list[int], n_new: int,
                      timeout: float = 120.0,
                      sampling: tuple | None = None,
                      priority: str = "interactive",
                      deadline_ms: int | None = None,
                      request_id: str = "",
                      stop_token: int | None = None) -> "StreamHandle":
        """Streaming generate: an iterator yielding each generated token
        as it lands, with a ``cancel()`` method.

        Same admission/sampling/priority semantics as :meth:`submit`. A
        consumer that merely stops iterating leaves the request decoding
        out its reserved budget (co-tenants are never perturbed); a
        consumer that KNOWS the client is gone calls ``cancel()`` and
        the request releases its slot and pages at the next step/window
        boundary — or immediately if it is still parked in the
        admission queue or swapped out. A mid-stream failure raises
        from the iterator after the tokens already produced.
        """
        req = self._start(prompt, n_new, timeout, sampling,
                          stream=True, priority=priority,
                          deadline_ms=deadline_ms,
                          request_id=request_id,
                          stop_token=stop_token)
        return StreamHandle(self, req)

    def cancel(self, req: _Request) -> None:
        """Ask the decode loop to drop a request at the next boundary.

        Idempotent, and a no-op for a request that already finished. The
        waiter (blocked ``submit`` / stream consumer) gets
        :class:`RequestCancelled`.
        """
        with self._hold("cancel", rid=req.rid, ring=req.trace):
            req.cancelled = True
            # Cancel-while-swapped-out (or parked in the journal of a
            # poisoned pool awaiting revive): the request holds no slot
            # and no reservation — only a host snapshot. Free it here
            # (no decode-loop boundary will ever see this request
            # again) and fail the waiter.
            dropped = self._sched.drop_swapped_locked(req) is not None
            if not dropped and req not in self._active.values():
                dropped = self._journal.pop(req) is not None
            if dropped:
                self._fail_request(req, RequestCancelled(
                    "request cancelled while swapped out"))
            # Cancel-while-parked: the waiter owns its ticket — wake
            # every parked thread so the cancelled one can dequeue
            # itself without consuming a slot or reservation.
            self._sched.wake_all_locked()
            self._work.notify_all()

    def _refusal(self) -> Exception:
        """The typed refusal a new/interrupted request gets (lock
        held): a poisoned pool beats plain ServerClosed — the client
        learns it may retry (against the rescheduled pod) and how long
        to wait, instead of a terminal-looking shutdown error."""
        if self._poison is not None:
            hint = None
            if self.retry_after_hint is not None:
                try:
                    hint = self.retry_after_hint()
                except Exception:
                    hint = None
            if hint is None:
                hint = self._retry_after_s
            e = PoolPoisoned(
                f"serving pool is poisoned ({self._degraded_reason}); "
                f"queue depth [{self._sched.depth_text_locked()}]; "
                f"retry against the recovered or rescheduled pod",
                **({} if hint is None else {"retry_after_s": hint}),
            )
            e.__cause__ = self._poison
            return e
        return ServerClosed(
            "server is draining" if self._draining
            else "server is shut down"
        )

    def _retry_hint(self) -> float | None:
        """The live retry-after hint (lock held): the recovery
        supervisor's measured estimate when installed, else the static
        config default."""
        if self.retry_after_hint is not None:
            try:
                hint = self.retry_after_hint()
            except Exception:
                hint = None
            if hint is not None:
                return hint
        return self._retry_after_s

    def _start(self, prompt: list[int], n_new: int, timeout: float,
               sampling: tuple | None, stream: bool,
               priority: str = "interactive",
               deadline_ms: int | None = None,
               request_id: str = "",
               stop_token: int | None = None) -> _Request:
        if not prompt or n_new < 1:
            raise ValueError("need a non-empty prompt and n_new >= 1")
        if stop_token is not None and stop_token < 0:
            raise ValueError("stop_token must be >= 0 (or None)")
        self._sched.rank(priority)  # unknown classes fail fast
        if deadline_ms is not None and deadline_ms < 1:
            raise ValueError("deadline_ms must be >= 1")
        total = len(prompt) + n_new
        if total > self._cfg.max_seq:
            raise ValueError(
                f"prompt ({len(prompt)}) + n_new ({n_new}) exceeds the "
                f"model's max_seq ({self._cfg.max_seq})"
            )
        pages_needed = self._pages_needed(total)
        if pages_needed > self._cache.max_pages_per_seq:
            raise ValueError(
                f"request needs {pages_needed} pages > max_pages_per_seq "
                f"= {self._cache.max_pages_per_seq}"
            )
        if pages_needed > self._pages_total:
            raise ValueError(
                f"request needs {pages_needed} pages > pool size "
                f"{self._pages_total}"
            )

        tr = self.tracer
        req = _Request(
            prompt=list(prompt), n_new=n_new, sampling=sampling,
            stop_token=-1 if stop_token is None else int(stop_token),
            pages_reserved=pages_needed,
            key_data=_raw_key_data(sampling[0]) if sampling else None,
            stream=queue.SimpleQueue() if stream else None,
            pclass=priority,
            rid=request_id,
            # The per-request sampling decision, made ONCE here: all of
            # this request's spans share fate, and a caller-replayed
            # X-Request-Id traces (or not) identically everywhere.
            trace=tr is not None and tr.sampled(request_id),
            t_submit=time.perf_counter(),
        )
        req.t_state = req.t_submit
        deadline = time.monotonic() + timeout
        if deadline_ms is not None:
            deadline = min(deadline,
                           time.monotonic() + deadline_ms / 1000.0)
        try:
            return self._admit(req, pages_needed, deadline, priority,
                               deadline_ms)
        except BaseException:
            # Shed, refused, timed out, cancelled or failed before it
            # was active: its ledger ends here.
            self._end_request(req, time.perf_counter())
            raise

    def _admit(self, req: _Request, pages_needed: int, deadline: float,
               priority: str, deadline_ms: int | None) -> _Request:
        """The rest of :meth:`_start`, from its first hold of the work
        lock on: admission, the prefill in chunks, the pick."""
        import jax.numpy as jnp

        with self._hold("admit/start", rid=req.rid,
                        ring=req.trace) as hold:
            if self._closed or self._draining:
                raise self._refusal()
            # Overload shedding: reject BEFORE parking when the queue
            # watermarks say the wait is hopeless, with the measured
            # per-class wait as the retry hint (falling back to the
            # recovery machinery's hint).
            shed = self._sched.shed_check_locked(priority, deadline_ms,
                                                 rid=req.rid)
            if shed is None:
                # Page-watermark shed (capacity semantics, SERVING.md
                # rung 21): when granting this request's worst-case
                # reservation would push free-page headroom below the
                # low watermark, non-top-priority arrivals shed with
                # page terms instead of parking behind a pool that
                # cannot absorb them. The top class always parks — it
                # is what the preemption path frees pages FOR. The
                # price is the arrival's MARGINAL cost (rung 24): its
                # private budget plus the lease units its shared
                # prefix pages would newly pin — a mostly-cached
                # prompt no longer sheds at full pages_needed.
                self._prefix_lookups += 1
                _, shared0, st0, _ = self._prefix_lookup(req.prompt)
                shed = self._page_shed_locked(
                    priority,
                    self._admission_price_locked(
                        pages_needed, shared0, st0),
                )
            if shed is not None:
                hint = shed["retry_after_s"]
                if hint is None:
                    hint = self._retry_hint()
                raise ServerOverloaded(
                    f"request shed: {shed['reason']}; "
                    f"{self._capacity_text_locked()}; queue depth "
                    f"[{self._sched.depth_text_locked()}]"
                    + (f"; retry after ~{hint:.1f}s" if hint is not None
                       else ""),
                    retry_after_s=hint,
                )
            # Ticketed admission (SERVING.md rung 17): park on a
            # per-class FIFO ticket and wait on the TICKET's condition.
            # Only the policy head is ever woken, and only the head
            # takes capacity — admission order is the queue's order,
            # not the lock's (the notify_all fairness fix). The decode
            # loop preempts a lower-class slot at a window boundary
            # when this ticket is head and cannot fit.
            ticket = self._sched.enqueue_locked(req, priority,
                                                pages_needed)
            req.ticket_no = ticket.no
            if (not self._free_slots
                    or self._reserved + pages_needed
                    > self._pages_total):
                # Actually parking: kick the decode loop so the next
                # boundary can consider preempting for this ticket.
                # (The uncontended admit must NOT wake the loop — it
                # adds nothing and perturbs the seed path's timing.)
                self._work.notify_all()
            try:
                while True:
                    if self._closed or self._draining:
                        raise self._refusal()
                    if req.cancelled:
                        raise RequestCancelled(
                            "request cancelled while queued for "
                            "admission"
                        )
                    # Re-priced each wake: the trie changes while this
                    # ticket parks, so the marginal cost (private
                    # budget + unleased shared pages) and the HBM-hot
                    # flag both refresh here. A hot non-head ticket may
                    # be admitted past a head STARVED for capacity
                    # (prefix affinity, rung 24d) — bounded by the
                    # scheduler's bypass cap so the head cannot starve
                    # behind an endless hot stream.
                    _, shared_w, st_w, _ = self._prefix_lookup(
                        req.prompt)
                    price = self._admission_price_locked(
                        pages_needed, shared_w, st_w)
                    ticket.hot = st_w > 0
                    head = self._sched.head_locked()
                    at_head = head is ticket
                    if not at_head and ticket.hot:
                        at_head = (
                            head is not None
                            and (not self._free_slots
                                 or self._reserved + head.pages_needed
                                 > self._pages_total)
                            and self._sched.bypass_ok_locked(ticket)
                        )
                    if (at_head and self._free_slots
                            and self._reserved + price
                            <= self._pages_total
                            and self._ensure_bucket_locked()):
                        break
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        hint = self._retry_hint()
                        raise ServerBusy(
                            "no page capacity within the timeout "
                            f"({len(self._active)} requests in "
                            f"flight; {self._capacity_text_locked()}; "
                            f"queue depth "
                            f"[{self._sched.depth_text_locked()}]"
                            + (f"; retry after ~{hint:.1f}s"
                               if hint is not None else "") + ")"
                        )
                    # Parked in the scheduler's queue: not a hold.
                    hold.pause()
                    ticket.cond.wait(timeout=remaining)
                    hold.resume()
                self._sched.admit_locked(ticket)
                ticket = None  # admitted: the finally must not remove
            finally:
                if ticket is not None:
                    self._sched.remove_locked(ticket)
            req.admit_seq = self._sched.next_admit_seq_locked()
            req.t_admit = time.perf_counter()
            self._to_state(req, "admit", req.t_admit)
            self._hist_queue.observe(
                (req.t_admit - req.t_submit) * 1e3
            )
            slot = heapq.heappop(self._free_slots)
            # Prefix sharing: start the table on the cached prefix's
            # read-only pages and evict LRU registry pins (never the
            # donor entry) until the free list covers this request's
            # full PRIVATE budget — so later grows can never starve on
            # registry-held pages. A host-tier match deeper than the
            # HBM one promotes first (best-effort: promotion can never
            # fail the admission — it falls back to the HBM match).
            donor, shared, shared_tokens, host_node = \
                self._prefix_lookup(req.prompt)
            if host_node is not None:
                got = self._promote_host_locked(host_node, {donor})
                if got is not None:
                    donor, shared, shared_tokens = got
            page = self._cache.page_size
            partial = shared_tokens % page != 0
            shared_full = tuple(shared[:-1] if partial else shared)
            private = pages_needed - len(shared_full)
            self._reserved += private
            self._lease_take_locked(shared_full)
            req.pages_reserved = private
            req.shared_pages = shared_full
            if shared_full:
                # The trie node at the full-shared depth: the journal's
                # shadow key. For a partial (COW) match the donor is
                # one level deeper — its parent is the shared path.
                req.prefix_node = (
                    self._prefix_nodes[donor]["parent"][0]
                    if partial else donor
                )
            try:
                self._evict_prefixes_for(private, {donor})
                self._cache.admit(slot, len(req.prompt), shared)
                if partial:
                    # COW divergence (rung 24a): the donor's partial
                    # last page is shared too — copy it device-side
                    # BEFORE the suffix prefill writes into it, so the
                    # registry's original stays immutable. The copy is
                    # within the private budget (it was priced as
                    # owned, never leased).
                    if self._cache.cow_page(slot, len(shared) - 1) \
                            is not None:
                        self._prefix_cow_copies += 1
            except Exception:
                self._release_locked(slot, private, req.shared_pages)
                req.shared_pages = ()
                req.prefix_node = None
                raise
            self._prefilling += 1
            if shared_tokens:
                self._prefix_hits += 1
                self._prefix_tokens_saved += shared_tokens
            # This thread's next wait for the lock is made and started
            # while it still holds it, here and after every chunk:
            # between a release and the next acquire stands nothing
            # that was not there before phases (a thread that asks
            # again at once beats the decode loop, which the release
            # only wakes; three microseconds more and it loses, and
            # its next chunk waits a window).
            off = shared_tokens  # cached prefix K/V are already in place
            next_lock = self._admit_wait(req, off)
        # Prefill in chunks, the lock held only PER CHUNK: the decode
        # loop interleaves batched steps for in-flight requests between
        # chunks (they never touch this slot — the loop's active mask
        # excludes anything not yet in self._active), so one admission's
        # long prompt no longer stalls every co-tenant; and XLA compiles
        # one program per CHUNK length instead of per prompt length —
        # a bounded compile surface under arbitrary operator traffic.
        # Each cache call still happens under the lock: cache state
        # mutations must serialize against the step loop.
        chunk = self._prefill_chunk or len(req.prompt)
        activated = False
        try:
            logits = None
            while off < len(req.prompt):
                piece = req.prompt[off:off + chunk]
                with next_lock as hold:
                    self._to_state(req, "prefill", hold.t0)
                    if self._closed:
                        raise self._refusal()
                    if req.cancelled:
                        raise RequestCancelled(
                            "request cancelled during prefill"
                        )
                    with self._phase("admit/prefill_chunk", rid=req.rid,
                                     ring=req.trace,
                                     args={"off": off, "n": len(piece)}):
                        logits = self._cache.prefill_chunk(
                            self._params, slot,
                            jnp.asarray(piece, jnp.int32), off,
                        )
                    off += len(piece)
                    next_lock = self._admit_wait(req, off)
            with next_lock as hold:
                picked = hold.waited
                try:
                    # Re-check under the activation lock: a hard close
                    # can land between the last chunk and here, after
                    # which no loop is alive to serve (or poison) this
                    # request.
                    if self._closed:
                        raise self._refusal()
                    if self._first_token_stays_locked():
                        # The pick is a program queued behind the
                        # chunks above, and nothing is read: nobody
                        # waits for the device with the lock in hand.
                        # The row's first window takes the token from
                        # the device, and the host reads it when it
                        # harvests that window.
                        req.first_dev = self._cache.pick_first(
                            logits, slot,
                            req.sampling and (req.key_data,
                                              *req.sampling[1:]))
                        req.next_token = FIRST_ON_DEVICE
                        self._first_tokens_on_device += 1
                    else:
                        # The pick reads the logits back: this thread
                        # waits, lock held, for the chunks above and
                        # for whatever window the device was given
                        # before them.
                        req.next_token = req.pick(logits, 0)
                finally:
                    # The pick's hold ends with the token dispatched
                    # (or read), on one stamp with the phase (its wait
                    # for the lock and this hold are the phase); the
                    # activation below is the admission's.
                    picked.stop(hold.switch("admit/start"))
                t_pick = picked.t1
                self._to_state(req, "join_wait", t_pick)
                if req.first_dev is None:
                    self._first_token_known(req, t_pick)
                if req.trace:
                    # Admission to the pick: the parent, by time, of
                    # this request's chunk phases in the ring.
                    self.tracer.span(
                        "prefill", "serve", req.t_admit, t_pick,
                        rid=req.rid,
                        args={"prompt": len(req.prompt),
                              "shared": shared_tokens,
                              "class": req.pclass},
                    )
                self._active[slot] = req
                self._note_finish_candidate_locked(slot, req)
                self._prefilling -= 1
                activated = True
                # The fully-prefilled prompt's page-aligned prefixes
                # are now reusable K/V: pin and register them.
                self._register_prefixes(
                    req.prompt, self._cache.slot_pages(slot)
                )
                self._work.notify_all()  # wake the decode loop
        except Exception as e:
            with self._hold("admit/start", rid=req.rid, ring=req.trace):
                if not activated:
                    self._prefilling -= 1
                    self._release_locked(slot, req.pages_reserved,
                                         req.shared_pages)
                    req.shared_pages = ()
                    req.prefix_node = None
                if (isinstance(e, ServingFailure)
                        and not e.retryable):
                    # A terminal failure on the SUBMIT path (the op
                    # watchdog can fire during this request's prefill,
                    # not just in the decode loop) kills the pool for
                    # everyone: poison co-tenants now with the typed
                    # error rather than letting them ride a dead cache
                    # into the same failure one window later.
                    self._poison_locked(e)
            raise
        return req

    def _first_token_stays_locked(self) -> bool:
        """Does a request's first token stay on the device (lock
        held)? Where the pool can keep it there (``unlocked_reads``),
        unless the server checkpoints (``checkpoint_every > 0``: the
        boundary a newcomer joins at journals its pending token), the
        only reasons left: there a newcomer joins at a boundary, which
        needs its token on the host, and the handler reads it back as
        it always did."""
        return self._unlocked_reads and self._checkpoint_every == 0

    def _first_token_known(self, req: _Request, now: float) -> None:
        """The host has the request's first token (``now``: a stamp
        taken there). Time to first token is submit to this: for a
        token read back by the handler, the read; for one kept on the
        device, the harvest of the first window that carried the row,
        which is when a client can be given it. The stamp is kept on
        the request: the first emission's ride is counted from it
        (``first_emit_ms``), and the finish pairs it with the final
        token for the per-request inter-token gap (rung 25)."""
        req.t_first = now
        self._hist_ttft.observe((now - req.t_submit) * 1e3)

    def _first_token_locked(self, req: _Request) -> None:
        """Read a first token kept on the device (lock held). The
        pick was queued before every window that carried the row, so
        at the harvest of one it is long computed; only the two sites
        that need the token of a row no window carried yet wait here,
        at a boundary with nothing else in flight, for the row's own
        prefill chunks: the finish of a request asked for one token,
        and a preemption (the row of first tokens is by slot, which a
        resume changes)."""
        if req.first_dev is not None:
            req.next_token = int(req.first_dev)
            req.first_dev = None
            self._first_token_known(req, time.perf_counter())

    def _admit_wait(self, req: _Request, off: int) -> Hold:
        """The submit path's next hold of the lock, made and its wait
        started (lock still held): for its next prefill chunk, or past
        the prompt's end for the pick of its first token, whose phase
        goes on through the hold to the token read."""
        more = off < len(req.prompt)
        wait = self._phase(
            "admit/lock_wait" if more else "admit/first_pick",
            rid=req.rid, ring=req.trace).start()
        self._to_state(req, "prefill_wait" if more else "pick", wait.t0)
        return self._hold(
            "admit/prefill_chunk" if more else "admit/first_pick",
            waited=wait, ends_wait=more, rid=req.rid, ring=req.trace)

    # ---- the request's ledger (ISSUE 38) -------------------------------

    def _to_state(self, req: _Request, state: str, now: float) -> None:
        """The request passes a boundary (``now``: a stamp taken
        there): the state it was in gets the time since the last one,
        and for a sampled request the states with no span of their own
        (the queue's, the chunk phases and the pick have theirs) go
        to the ring."""
        was = req.state
        req.state_ms[was] = (req.state_ms.get(was, 0.0)
                             + (now - req.t_state) * 1e3)
        if req.trace and was in ("admit", "join_wait", "swapped"):
            self.tracer.span(was, "serve", req.t_state, now, rid=req.rid)
        req.state, req.t_state = state, now

    def _fail_request(self, req: _Request, err: Exception) -> None:
        """The request ends without the rest of its tokens: its waiter
        (and its stream) gets ``err``."""
        req.error = err
        if req.stream is not None:
            req.stream.put(err)
        self._end_request(req, time.perf_counter())
        req.done.set()

    def _end_request(self, req: _Request, now: float) -> None:
        """The request's life ends at ``now``, however it ends: its
        last state is closed, and for a sampled request the root span
        its states lie in is recorded. Once."""
        if req.t_done:
            return
        self._to_state(req, "done", now)
        req.t_done = now
        if req.trace:
            self.tracer.span(
                "request", "serve", req.t_submit, now, rid=req.rid,
                args={"class": req.pclass,
                      "tokens": len(req.generated)})

    # ---- capacity semantics (SERVING.md rung 21) ------------------------

    def _capacity_text_locked(self) -> str:
        """Page-capacity terms for refusal payloads: pages, not slots,
        gate admission now, so a refused caller learns the pool state
        it is actually queued behind."""
        free = self._pages_total - self._reserved
        return (f"{free}/{self._pages_total} pages unreserved, "
                f"bucket {self._cache.bucket}/{self._cache.slots} rows")

    def _page_shed_locked(self, priority: str,
                          pages_needed: int) -> dict | None:
        """Low-watermark page shed: None (park) or a shed record in the
        scheduler's shape. Top-priority arrivals never page-shed —
        preemption exists to free pages for exactly them."""
        if not self._page_low_wm or self._sched.rank(priority) == 0:
            return None
        free_after = self._pages_total - self._reserved - pages_needed
        if free_after >= self._page_low_wm * self._pages_total:
            return None
        self._sched.shed += 1
        return {
            "reason": (
                f"free-page headroom below the low watermark "
                f"({free_after} of {self._pages_total} pages would "
                f"stay unreserved, watermark {self._page_low_wm:.0%})"
            ),
            "retry_after_s": None,
        }

    def _resume_pages_ok_locked(self, pages_needed: int) -> bool:
        """High-watermark resume gate: a preempted request swaps back
        in only when doing so leaves free-page headroom at or above
        the HIGH watermark — the hysteresis that stops a pool hovering
        at the low watermark from thrashing preempt/resume cycles."""
        if not self._page_high_wm:
            return True
        free_after = self._pages_total - self._reserved - pages_needed
        return free_after >= self._page_high_wm * self._pages_total

    def _ensure_bucket_locked(self) -> bool:
        """Admission's bucket clause: True iff a free slot INSIDE the
        current device bucket exists. When every free slot lies above
        the bucket, resize directly if the cache is quiescent (an idle
        pipeline); otherwise flag the step-up for the
        decode loop's next boundary and keep the caller parked — it is
        woken when the resize lands."""
        if self._free_slots and self._free_slots[0] < self._cache.bucket:
            return True
        if not self._free_slots:
            return False
        # With nothing dispatched-unharvested the resize is safe here:
        # the loop's next dispatch at a boundary is always first=True
        # (host tokens), so the carry set_bucket drops was dead anyway.
        if self._inflight is None and self._harvesting is None:
            self._cache.set_bucket(
                self._cache.bucket_for(self._free_slots[0] + 1)
            )
            return True
        self._bucket_step_wanted = True
        self._work.notify_all()
        return False

    def _maybe_step_bucket_locked(self) -> None:
        """Resize the device batch dim at a pipeline boundary: step UP
        when an admission parked on a row above the bucket
        (``_bucket_step_wanted``), step DOWN when the occupied set has
        drained out of the bucket's top half and nothing is queued.
        Quiescent points only; no-op with bucketing disabled."""
        if not self._cache.min_bucket or self._inflight is not None:
            return
        bucket = self._cache.bucket
        want = self._cache.rows_in_use()
        if self._bucket_step_wanted and self._free_slots:
            want = max(want, self._free_slots[0] + 1)
        self._bucket_step_wanted = False
        target = self._cache.bucket_for(want)
        if target > bucket or (target < bucket
                               and self._sched.head_locked() is None):
            self._cache.set_bucket(target)
            self._sched.wake_head_locked()
            self._work.notify_all()

    # ---- boundary checkpoints + page audit (SERVING.md rung 22) ----------

    def _maybe_checkpoint_locked(self) -> None:
        """Quiescent-boundary durability hook (lock held, nothing in
        flight): audit page conservation when asked, then — every
        ``checkpoint_every`` clock ticks — journal each live request's
        resumable state. The KV snapshot is the SAME verbatim-bytes
        gather preemption swaps out (``swapout_pages``, int8 scale
        slabs included), taken on the live slot without releasing it;
        ``saved_len`` covers exactly the committed positions, with the
        pending token stored host-side — the preempt/resume contract,
        which is why restore is bit-identical for free."""
        if self._debug_pages:
            self._audit_pages_locked()
        if not self._checkpoint_every:
            return
        self._ckpt_clock += 1
        if self._ckpt_clock < self._checkpoint_every:
            return
        self._ckpt_clock = 0
        if not self._active:
            return
        t0 = time.perf_counter()
        # Host-path elimination (rung 26): the old loop issued one
        # device gather + one forced transfer PER live request, every
        # checkpoint tick, even when nothing had changed. Two fixes:
        #
        # * Delta-skip — a request whose (gen_len, next_token) match
        #   its standing entry would re-serialize byte-identical state
        #   (decode only appends; KV below saved_len never mutates),
        #   so it keeps the old entry at zero device work. A quiescent
        #   boundary now costs O(changes), not O(live).
        # * Coalesced gather — every page the boundary DOES need
        #   (own suffixes + any new prefix shadows, deduped per node)
        #   rides ONE ``swapout_pages`` call: one device program, one
        #   forced transfer, sliced per entry on host. The slices are
        #   compacted copies so the journal's byte accounting stays
        #   honest (a view would pin the whole batch buffer).
        jobs = []       # (req, saved_len, n_pages, own_span, sh_spans)
        all_ids = []
        new_shadow_spans: dict = {}   # node -> (start, sh_n)
        for slot, req in self._active.items():
            if req.cancelled:
                continue
            saved_len = len(req.prompt) + len(req.generated)
            prev = self._journal.get(req)
            if (prev is not None
                    and prev.gen_len == len(req.generated)
                    and prev.next_token == req.next_token):
                self._checkpoints_unchanged += 1
                continue
            n_pages = -(-saved_len // self._cache.page_size)
            ids = self._cache.slot_pages(slot)[:n_pages]
            sh_n = len(req.shared_pages)
            shared = req.prefix_node is not None and sh_n
            own_ids = ids[sh_n:] if shared else ids
            own_span = (len(all_ids), len(own_ids))
            all_ids.extend(own_ids)
            node = None
            if shared:
                node = req.prefix_node
                if (node not in self._prefix_shadow
                        and node not in new_shadow_spans):
                    new_shadow_spans[node] = (len(all_ids), sh_n)
                    all_ids.extend(ids[:sh_n])
            jobs.append((req, saved_len, sh_n if shared else 0, node,
                         own_span, slot))
        if not jobs:
            return
        batch = (self._cache.swapout_pages(all_ids)
                 if all_ids else None)

        def _slice(span):
            # Gathered slabs are [L, n_pages, ...] (_gather_pages_impl)
            # — the page dimension is axis 1, layers axis 0.
            start, n = span
            return tuple(np.ascontiguousarray(a[:, start:start + n])
                         for a in batch)

        new_shadows = {node: _slice(span)
                       for node, span in new_shadow_spans.items()}
        for req, saved_len, sh_n, node, own_span, slot in jobs:
            # With the pages, the row's recurrent state as it stands
            # at this boundary (empty for a block that keeps none).
            own = _slice(own_span) + self._cache.swapout_row(slot)
            if node is not None:
                ok = self._checkpoint_shared_locked(
                    req, saved_len, sh_n, own,
                    new_shadows.get(node))
            else:
                entry = JournalEntry(
                    req=req, pclass=req.pclass,
                    ticket_no=req.ticket_no,
                    admit_seq=req.admit_seq,
                    pages_reserved=req.pages_reserved,
                    saved_len=saved_len, gen_len=len(req.generated),
                    next_token=req.next_token,
                    emitted=len(req.generated),
                    arrays=own,
                    nbytes=sum(a.nbytes for a in own),
                )
                ok = self._journal.put(req, entry)
            if ok:
                self._checkpoints_total += 1
            else:
                # Budget-refused: the request keeps its previous
                # (older but internally consistent) entry, or stays
                # unjournaled — counted so operators see the bound
                # biting.
                self._checkpoint_skipped += 1
        if self.tracer is not None:
            self.tracer.span(
                "checkpoint", "serve", t0,
                args={"live": len(self._active),
                      "entries": len(self._journal),
                      "bytes": self._journal.nbytes},
            )

    def _checkpoint_shared_locked(self, req: _Request,
                                  saved_len: int, sh_n: int,
                                  own: tuple,
                                  sh_arrays: tuple | None) -> bool:
        """Checkpoint a request whose table starts on cached-prefix
        pages (lock held): the entry carries only the request's OWN
        page bytes plus a REFERENCE (trie node id + page/token depth)
        into a per-node shadow snapshot of the shared bytes, taken
        once and refcounted across every entry that cites it — N
        requests on one system prompt bill the journal budget 1 shadow
        + N suffixes, not N full copies (rung 24c). Refs bump BEFORE
        ``put`` so the on_drop of a replaced older entry (which fires
        inside ``put``) nets correctly when both cite the same node.
        ``own``/``sh_arrays`` arrive pre-gathered from the boundary's
        single coalesced ``swapout_pages`` batch; ``sh_arrays`` is
        only consulted when the node's shadow does not exist yet."""
        node = req.prefix_node
        shadow = self._prefix_shadow.get(node)
        extra = 0
        if shadow is None:
            if sh_arrays is None:
                # Should be unreachable — the batching loop gathers
                # shadow bytes for every node it cannot find — but a
                # refused-then-retried node races only against itself,
                # so refuse rather than journal a dangling reference.
                return False
            extra = sum(a.nbytes for a in sh_arrays)
            shadow = {"arrays": sh_arrays, "nbytes": extra,
                      "refs": 0, "npages": sh_n}
            self._prefix_shadow[node] = shadow
        shadow["refs"] += 1
        entry = JournalEntry(
            req=req, pclass=req.pclass, ticket_no=req.ticket_no,
            admit_seq=req.admit_seq,
            pages_reserved=req.pages_reserved,
            saved_len=saved_len, gen_len=len(req.generated),
            next_token=req.next_token, emitted=len(req.generated),
            arrays=own, nbytes=sum(a.nbytes for a in own),
            prefix_node=node, prefix_pages_n=sh_n,
            prefix_tokens=sh_n * self._cache.page_size,
        )
        if self._journal.put(req, entry, extra=extra):
            if extra:
                self._journal.adjust_extra(extra)
            return True
        shadow["refs"] -= 1
        if shadow["refs"] <= 0:
            # Freshly created for this refused entry — unwind it
            # without billing (extra was never adjusted in).
            del self._prefix_shadow[node]
        return False

    def _journal_drop_locked(self, entry) -> None:
        """Journal entry-drop observer (lock held, wired to
        ``RequestJournal.on_drop``): settle a dropped entry's prefix
        reference — the last citation of a shadow snapshot releases
        its bytes from the budget. Fires on put-replacement and pop;
        restore settles drained entries itself after re-admission."""
        node = entry.prefix_node
        if node is None:
            return
        shadow = self._prefix_shadow.get(node)
        if shadow is None:
            return
        shadow["refs"] -= 1
        if shadow["refs"] <= 0:
            del self._prefix_shadow[node]
            self._journal.adjust_extra(-shadow["nbytes"])

    def _audit_pages_locked(self) -> None:
        """Assert page conservation at a quiescent boundary (lock
        held): free + live == pages_total with clean books. Raises the
        typed :class:`PageAccountingError` — the decode loop's normal
        failure path poisons the pool with it, so a leak is loud and
        attributable to the boundary that found it."""
        acct_fn = getattr(self._cache, "page_accounting", None)
        if acct_fn is None:  # injected cache without the census
            return
        acct = acct_fn()
        window_ok = (
            "window_free" not in acct
            or (acct["window_free"] + acct["window_live"]
                == acct["window_pages_total"]
                and not acct["window_free_dup"]
                and not acct["window_held_dup"]
                and not acct["window_free_live"]
                and not acct["window_over_cap"]))
        if (acct["free"] + acct["live"] == acct["pages_total"]
                and not acct["free_dup"] and not acct["neg_refs"]
                and not acct["free_live"] and window_ok):
            return
        if not window_ok:
            raise PageAccountingError(
                "page conservation violated in the window layers' pool "
                "at a quiescent boundary: " + ", ".join(
                    f"{k}={v}" for k, v in acct.items()
                    if k.startswith("window_")))
        raise PageAccountingError(
            f"page conservation violated at a quiescent boundary: "
            f"free={acct['free']} live={acct['live']} "
            f"total={acct['pages_total']} dup_free={acct['free_dup']} "
            f"neg_refs={acct['neg_refs']} "
            f"free_but_live={acct['free_live']}"
        )

    def _divert_to_journal_locked(self, req: _Request) -> bool:
        """Poison-path diversion (lock held): True when ``req`` has a
        checkpoint to resume from — its waiter stays parked across the
        outage and revive() re-admits it. Records the exactly-once
        watermark: every token in ``generated`` RIGHT NOW (including
        post-checkpoint decode) was already delivered, so the replay
        must not re-stream below this count."""
        if req.cancelled:
            return False
        entry = self._journal.get(req)
        if entry is None:
            return False
        entry.emitted = max(entry.emitted, len(req.generated))
        # Out of the pool until revive() puts it back.
        self._to_state(req, "swapped", time.perf_counter())
        return True

    def _journal_swapped_locked(self, entry) -> bool:
        """Move a swapped-out request's snapshot into the journal at
        poison time (lock held): the scheduler entry already holds the
        verbatim host bytes, saved length, and original ticket — a
        ready-made checkpoint. False (caller fails the request and
        frees the snapshot) when checkpointing is off, the request was
        cancelled, or the journal budget refuses the bytes."""
        if not self._checkpoint_every or entry.req.cancelled:
            entry.arrays = ()
            return False
        req = entry.req
        je = JournalEntry(
            req=req, pclass=entry.pclass, ticket_no=entry.no,
            admit_seq=req.admit_seq,
            pages_reserved=entry.pages_needed,
            saved_len=entry.saved_len, gen_len=len(req.generated),
            next_token=req.next_token, emitted=len(req.generated),
            arrays=entry.arrays, nbytes=entry.nbytes,
        )
        if not self._journal.put(req, je):
            self._checkpoint_skipped += 1
            entry.arrays = ()
            return False
        self._checkpoints_total += 1
        return True

    def _fail_journal_locked(self, err: Exception) -> None:
        """Fail every journaled waiter (lock held) — the close() path
        of a pool that will never be revived. Without this, diverted
        requests would park forever behind a teardown."""
        for entry in self._journal.take_all():
            self._journal_drop_locked(entry)
            req = entry.req
            if req.done.is_set():
                continue
            self._fail_request(req, err)

    def capacity_probe(self) -> dict:
        """Lock-free capacity snapshot for /healthz: like
        :attr:`degraded`, bare attribute reads only — a health probe
        must answer even when a thread is misbehaving around the
        server lock — so values may be one boundary stale.
        ``pages_free`` is UNRESERVED pages (the admission resource a
        load balancer drains on), not the device free list."""
        return {
            "pages_free": max(self._pages_total - self._reserved, 0),
            "pages_total": self._pages_total,
            "bucket": self._cache.bucket,
        }

    def _poison_locked(self, failure: ServingFailure) -> None:
        """Poison the pool (lock held): every in-flight waiter gets the
        typed failure, the degraded flag flips for stats/healthz, and
        admission waiters wake to fail fast with _refusal()'s
        retry-after hint. The exiting decode loop runs _degrade() for
        the outside-the-lock cleanup (emergency dump, observer)."""
        if self._poison is None:
            self._poison = failure
            self._degraded_reason = f"{type(failure).__name__}: {failure}"
            # Satellite of rung 22: remember the capacity rung so
            # revive restores it instead of resetting to the bottom.
            self._prebucket = self._cache.bucket
        # Rung 22 diversion: a request with a journal checkpoint is
        # NOT failed — its waiter stays parked (done unset, stream
        # quiet) and revive() re-admits it from the checkpoint,
        # replaying the post-checkpoint gap bit-identically. Requests
        # the journal never caught (cadence, budget skip, checkpointing
        # off) fail exactly as before.
        survivors = 0
        failed = 0
        for req in self._active.values():
            if self._divert_to_journal_locked(req):
                survivors += 1
                continue
            failed += 1
            self._fail_request(req, failure)
        self._active.clear()
        # Degraded mode reaches the swap set too (rung 14 x rung 17):
        # a swapped-out request's device pages are gone and no healthy
        # loop will ever resume it. Its host snapshot is ALREADY a
        # verbatim checkpoint under the original ticket — with
        # checkpointing on it moves into the journal; otherwise fail
        # it like an active one and free the snapshot.
        for entry in self._sched.take_swapped_locked():
            if self._journal_swapped_locked(entry):
                survivors += 1
                continue
            failed += 1
            self._fail_request(entry.req, failure)
        if self.tracer is not None:
            # The poison instant anchors the flight-recorder tail the
            # post-mortem (last-failure.json) embeds.
            self.tracer.event(
                "poison", "failure",
                args={"type": type(failure).__name__,
                      "failed": failed,
                      "journaled": survivors},
            )
        self._closed = True
        self._sched.wake_all_locked()
        self._work.notify_all()

    # ---- prefix sharing (lock held for every method here) ----------------

    def _prefix_lookup(self, prompt: list[int]):
        """(donor_node, pages, shared_tokens, host_node) of the best
        cached prefix — capped at len(prompt)-1 so at least one token
        prefills and produces the first-emission logits.

        The walk matches whole page-sized blocks down the radix trie;
        from the deepest walked node it then tries a PARTIAL last
        block against the children's HBM entries (COW divergence,
        rung 24a): an entry whose next block shares >= 1 leading token
        with the remaining prompt lends its partial page too — the
        admission path copies that page device-side before the suffix
        prefill writes into it. ``donor_node`` is the entry whose
        pages are borrowed (the admission's eviction keep-set).
        ``host_node`` is the deepest host-resident entry STRICTLY
        deeper than the HBM match (rung 24b) — admission promotes it
        when it can. One walk: O(len(prompt)) hashing."""
        if not self._prefix_enabled:
            return None, (), 0, None
        page = self._cache.page_size
        node, depth = 0, 0
        best = (None, (), 0)
        host = None
        for k in range(1, (len(prompt) - 1) // page + 1):
            block = tuple(prompt[(k - 1) * page:k * page])
            child = self._prefix_nodes[node]["edges"].get(block)
            if child is None:
                break
            node, depth = child, k
            rec = self._prefix_nodes[node]
            if rec["entry"] is not None:
                rec["entry"]["last_used"] = time.monotonic()
                best = (node, tuple(rec["entry"]["pages"]), k * page)
            if rec["host"] is not None:
                host = (node, k)
        cap = len(prompt) - 1 - depth * page
        if cap > 0:
            tail = prompt[depth * page:(depth + 1) * page]
            best_ov = 0
            for block, child in (
                    self._prefix_nodes[node]["edges"].items()):
                entry = self._prefix_nodes[child]["entry"]
                if entry is None:
                    continue
                ov = 0
                for a, b in zip(tail, block):
                    if a != b:
                        break
                    ov += 1
                ov = min(ov, cap)
                if ov > best_ov:
                    best_ov = ov
                    entry["last_used"] = time.monotonic()
                    best = (child,
                            tuple(entry["pages"][:depth + 1]),
                            depth * page + ov)
        host_node = None
        if host is not None and host[1] * page > best[2]:
            host_node = host[0]
        return best[0], best[1], best[2], host_node

    def _admission_price_locked(self, pages_needed: int, shared,
                                shared_tokens: int) -> int:
        """The MARGINAL page cost of admitting an arrival whose prefix
        lookup matched ``shared`` (lock held): its private budget (a
        partially-shared page's COW copy counts as private) plus one
        lease unit per full shared page no live request leases yet.
        This is what the low-watermark shed and the park-loop capacity
        clause gate on — shared pages already resident and leased are
        free to admit against (rung 24)."""
        page = self._cache.page_size
        full = (shared[:-1] if shared and shared_tokens % page
                else shared)
        new_leases = sum(1 for p in full if p not in self._lease)
        return pages_needed - len(full) + new_leases

    def _trie_child(self, node: int, block: tuple) -> int:
        """The trie child for ``block`` under ``node``, created if
        absent (lock held) — the ONE node-allocation walk step, shared
        by live registration and the persistence loader."""
        child = self._prefix_nodes[node]["edges"].get(block)
        if child is None:
            child = self._prefix_next_id
            self._prefix_next_id += 1
            self._prefix_nodes[node]["edges"][block] = child
            self._prefix_nodes[child] = {
                "parent": (node, block), "edges": {}, "entry": None,
                "host": None,
            }
        return child

    def _register_prefixes(self, prompt: list[int],
                           pages: list[int]) -> None:
        """Pin every page-aligned prefix of committed token state.
        Only full pages covered entirely by the given tokens register
        — later writes land past them (the first grow opens a fresh
        page even at an aligned boundary, and a shared partial page
        COWs before its first write), so registered pages are
        immutable. One walk down the trie: O(len(prompt))."""
        if not self._prefix_enabled:
            return
        page = self._cache.page_size
        node = 0
        for k in range(1, len(prompt) // page + 1):
            block = tuple(prompt[(k - 1) * page:k * page])
            node = self._trie_child(node, block)
            if self._prefix_nodes[node]["entry"] is None:
                held = list(pages[:k])
                self._cache.retain_pages(held)
                entry = {"pages": held, "last_used": time.monotonic()}
                self._prefix_nodes[node]["entry"] = entry
                self._prefix_entry_nodes[node] = entry
                self._prefix_registrations += 1
                if self._prefix_nodes[node]["host"] is not None:
                    # A live registration supersedes a host-tier copy
                    # of the same prefix (K/V are deterministic — the
                    # bytes are identical); keeping both would double-
                    # bill the host budget.
                    self._drop_host_record_locked(node)

    def _insert_prefix_entry(self, tokens: list[int],
                             pages) -> int:
        """Attach ONE registry entry holding ``pages`` at the trie
        node for ``tokens`` (a whole number of blocks), creating path
        nodes as needed (lock held). Ownership transfers: the caller's
        page refs (``allocate_pinned_page``) BECOME the registry pin —
        no extra retain — exactly the host-promotion idiom. Used by
        the journal restore to resurrect a shadow snapshot's shared
        pages as a live cache entry. Returns the node id."""
        page = self._cache.page_size
        node = 0
        for k in range(1, len(tokens) // page + 1):
            node = self._trie_child(
                node, tuple(tokens[(k - 1) * page:k * page]))
        if self._prefix_nodes[node]["entry"] is not None:
            # Already live (another path resurrected it first): the
            # existing pin wins, the caller's refs return to the pool.
            self._cache.release_pages(pages)
            return node
        entry = {"pages": list(pages), "last_used": time.monotonic()}
        self._prefix_nodes[node]["entry"] = entry
        self._prefix_entry_nodes[node] = entry
        self._prefix_registrations += 1
        if self._prefix_nodes[node]["host"] is not None:
            self._drop_host_record_locked(node)
        return node

    def _prune_prefix_upward(self, node: int) -> None:
        """Prune edge-less, entry-less, host-less nodes upward (lock
        held) — the trie never outlives its residents."""
        cur = node
        while cur != 0:
            rec = self._prefix_nodes[cur]
            if (rec["entry"] is not None or rec["host"] is not None
                    or rec["edges"]):
                break
            pid, block = rec["parent"]
            del self._prefix_nodes[cur]
            del self._prefix_nodes[pid]["edges"][block]
            cur = pid

    def _evict_prefix_node(self, node: int, cause: str) -> None:
        """Unpin one HBM entry — demoting its bytes to the host tier
        when the budget allows (rung 24b) — and prune upward. The
        low-watermark/pressure story this implements: cold SHARED
        pages leave HBM (to host, not to nowhere) before any unique
        live victim is preempted, because registry pins are always
        relieved ahead of the preemption path seeing starvation.
        ``cause`` feeds the eviction-by-cause counters; "revive" never
        demotes — the device is suspect after a poison, so only the
        emergency dump's host bytes are trusted."""
        entry = self._prefix_entry_nodes.pop(node)
        self._prefix_evictions[cause] += 1
        rec = self._prefix_nodes[node]
        rec["entry"] = None
        if (self._prefix_host_budget and cause != "revive"
                and rec["host"] is None):
            self._demote_prefix_locked(node, entry)
        self._cache.release_pages(entry["pages"])
        self._prune_prefix_upward(node)

    def _demote_prefix_locked(self, node: int, entry: dict) -> None:
        """Swap an evicted entry's pages to the host tier (lock held):
        the same verbatim as-stored bytes preemption uses (int8 scale
        slabs ride along). Oversize records drop; host-LRU eviction
        makes room otherwise. Best-effort — a failing device gather
        (poisoned pool mid-relief) drops the entry instead of failing
        the caller."""
        try:
            arrays = self._cache.swapout_pages(entry["pages"])
        except Exception:
            return
        nbytes = sum(a.nbytes for a in arrays)
        if nbytes > self._prefix_host_budget:
            self._prefix_evictions["host_over"] += 1
            return
        while (self._prefix_host_bytes + nbytes
               > self._prefix_host_budget):
            lru = min(
                self._prefix_host_nodes,
                key=lambda n: self._prefix_host_nodes[n]["last_used"],
            )
            self._prefix_evictions["host_lru"] += 1
            self._drop_host_record_locked(lru)
        rec = {"arrays": arrays, "nbytes": nbytes,
               "npages": len(entry["pages"]),
               "last_used": entry["last_used"]}
        self._prefix_nodes[node]["host"] = rec
        self._prefix_host_nodes[node] = rec
        self._prefix_host_bytes += nbytes
        self._prefix_demotions += 1

    def _drop_host_record_locked(self, node: int) -> None:
        """Forget a host-tier record and un-bill its bytes (lock
        held), pruning the trie path if nothing else holds it."""
        rec = self._prefix_host_nodes.pop(node)
        self._prefix_host_bytes -= rec["nbytes"]
        self._prefix_nodes[node]["host"] = None
        self._prune_prefix_upward(node)

    def _promote_host_locked(self, node: int, keep) -> tuple | None:
        """Swap a host-resident prefix entry back into HBM at an
        admission hit (rung 24b). Returns the promoted
        (node, pages, shared_tokens), or None — promotion is
        best-effort and must NEVER fail the admission, which falls
        back to the shallower HBM match. Fresh pages come from the
        pinned allocator after an LRU sweep of colder HBM entries
        (never ``keep``); if the free list still cannot cover the
        record, the promotion simply doesn't happen."""
        rec = self._prefix_host_nodes.get(node)
        if rec is None:
            return None
        n = rec["npages"]
        self._evict_prefixes_for(n, keep)
        if self._cache.free_pages() < n:
            return None
        pages = [self._cache.allocate_pinned_page() for _ in range(n)]
        try:
            self._cache.swapin_pages(pages, rec["arrays"])
        except Exception:
            self._cache.release_pages(pages)
            raise
        entry = {"pages": pages, "last_used": time.monotonic()}
        self._prefix_nodes[node]["entry"] = entry
        self._prefix_entry_nodes[node] = entry
        self._prefix_nodes[node]["host"] = None
        self._prefix_host_nodes.pop(node)
        self._prefix_host_bytes -= rec["nbytes"]
        self._prefix_promotions += 1
        self._prefix_registrations += 1
        return node, tuple(pages), n * self._cache.page_size

    def _evict_prefixes_for(self, needed_free: int, keep=()) -> None:
        """Evict LRU registry entries (never one in ``keep``) until
        the free list can cover ``needed_free`` pages. Always
        sufficient for an admission within its reservation: every
        non-registry allocation sits inside some request's reserved
        budget (or a lease), and reservations never exceed the pool."""
        while (self._cache.free_pages() < needed_free
               and any(n not in keep
                       for n in self._prefix_entry_nodes)):
            victim = min(
                (n for n in self._prefix_entry_nodes if n not in keep),
                key=lambda n: self._prefix_entry_nodes[n]["last_used"],
            )
            self._evict_prefix_node(victim, "admission")

    def _relieve_pool_pressure_locked(self, needed: int = 1) -> bool:
        """Cache callback when an allocation finds the free list short
        (kvcache.grow/admit/cow): registry pins sit outside every
        request's reservation, so a mid-decode grow — which IS within
        its request's reservation — must be able to reclaim them;
        after all pins are dropped, free >= every in-reservation need.
        Eviction demotes to the host tier when configured, so relief
        moves cold shared pages out of HBM instead of destroying them.
        Runs under the server lock (every cache call holds it).
        Returns True iff ``needed`` pages are now free."""
        while (self._prefix_entry_nodes
               and self._cache.free_pages() < needed):
            victim = min(
                self._prefix_entry_nodes,
                key=lambda n: self._prefix_entry_nodes[n]["last_used"],
            )
            self._evict_prefix_node(victim, "pressure")
        return self._cache.free_pages() >= needed

    # ---- prefix persistence ---------------------------------------------
    #
    # The registry's pinned pages are device state, so a pod reschedule
    # loses them — unless they ride the state volume like every other
    # thing worth keeping (the reference's whole resilience story is
    # PVC-backed state, README.md:88). dump writes tokens + K/V of every
    # registered entry; load re-pins them into a fresh server. K/V are
    # valid ONLY for the params that produced them: the caller passes a
    # fingerprint (checkpoint step + model geometry) and a mismatched
    # file is ignored, never half-trusted.

    def _node_tokens(self, node: int) -> list[int]:
        """A trie node's full token path (lock held)."""
        blocks = []
        cur = node
        while cur != 0:
            parent_id, block = self._prefix_nodes[cur]["parent"]
            blocks.append(block)
            cur = parent_id
        return [t for block in reversed(blocks) for t in block]

    def dump_prefix_cache(self, path: str, fingerprint: str) -> int:
        """Persist the prefix registry to ``path`` (.npz). Returns the
        number of entries written (0 = nothing registered, no file
        touched). Callable any time before close — the lock serializes
        against the decode loop."""
        import json

        with self._hold("control"):
            entries = [
                {"tokens": self._node_tokens(node),
                 "pages": list(entry["pages"])}
                for node, entry in self._prefix_entry_nodes.items()
            ]
            if not entries:
                return 0
            page_ids = sorted({p for e in entries for p in e["pages"]})
            # Only the gather DISPATCH runs under the lock; the fresh
            # device arrays are donation-immune, so the big
            # device->host transfer below happens with decode running
            # — a periodic dump must not freeze token emission for the
            # duration of a multi-hundred-MB copy.
            snapshot = self._cache.snapshot_pages(page_ids)
        # Transfer as stored (int8 pools ship compact + scales), then
        # dequantize host-side. npz has no bfloat16; float32 holds bf16
        # (and fp16) exactly, and the load path casts back (or
        # re-quantizes) to the pool dtype.
        pool_k, pool_v = self._cache.snapshot_to_host(snapshot)
        doc = {
            "fingerprint": fingerprint,
            "page_size": self._cache.page_size,
            "entries": entries,
            "page_ids": page_ids,
        }
        tmp = f"{path}.tmp"
        with open(tmp, "wb") as f:
            np.savez(f, doc=np.frombuffer(
                json.dumps(doc).encode(), np.uint8
            ), pool_k=pool_k, pool_v=pool_v)
        import os

        os.replace(tmp, path)  # atomic: never a torn cache file
        return len(entries)

    def load_prefix_cache(self, path: str, fingerprint: str) -> int:
        """Re-pin a dumped registry into this (fresh) server. Returns
        entries loaded; 0 with a reason logged when the file is absent,
        stale (fingerprint/page-size mismatch), or the pool too full.
        Entries load ancestors-first so nested prefixes share pages
        exactly as they did live; an entry whose fresh pages exceed the
        free list is SKIPPED (later entries that fit — e.g. descendants
        sharing already-loaded pages — still load), and nothing is ever
        evicted — a cache must not displace capacity."""
        import json
        import os

        if not os.path.exists(path):
            return 0
        try:
            with np.load(path) as data:
                # Fingerprint first: a stale file (training advanced the
                # checkpoint) must not pay the K/V decompression — npz
                # members load lazily on access.
                doc = json.loads(bytes(data["doc"]).decode())
                if (doc.get("fingerprint") != fingerprint
                        or doc.get("page_size")
                        != self._cache.page_size):
                    print(f"[kvedge-serve] ignoring stale prefix cache "
                          f"{path} (fingerprint/page-size changed)",
                          flush=True)
                    return 0
                pool_k, pool_v = data["pool_k"], data["pool_v"]
        except Exception as e:
            print(f"[kvedge-serve] ignoring unreadable prefix cache "
                  f"{path}: {e!r}", flush=True)
            return 0
        old_pos = {p: i for i, p in enumerate(doc["page_ids"])}
        loaded = 0
        with self._hold("control"):
            if (not self._prefix_enabled or self._closed
                    or self._prefix_entry_nodes):
                # Boot-time only: loading into a registry that already
                # has live entries would need dedup-against-live (and
                # two loads would double-pin); nothing needs it.
                return 0
            remap: dict[int, int] = {}
            writes: list[tuple[int, int]] = []  # (new_id, dump position)
            for e in sorted(doc["entries"],
                            key=lambda e: len(e["tokens"])):
                fresh = set(p for p in e["pages"] if p not in remap)
                if len(fresh) > self._cache.free_pages():
                    # Skip, don't stop: sibling subtrees are not ordered
                    # by fresh-page need (a later descendant sharing an
                    # already-loaded ancestor may need fewer pages than
                    # an unrelated same-length entry that didn't fit).
                    continue
                for p in fresh:
                    new = self._cache.allocate_pinned_page()
                    remap[p] = new
                    writes.append((new, old_pos[p]))
                # Refcount shape must equal live registration's: one ref
                # per entry per page it holds. A freshly allocated page's
                # ref 1 IS this entry's hold; pages shared from earlier
                # entries take one more.
                self._cache.retain_pages(
                    [remap[p] for p in e["pages"] if p not in fresh]
                )
                self._insert_prefix_entry(
                    e["tokens"], [remap[p] for p in e["pages"]]
                )
                loaded += 1
            if writes:
                ids = [w for w, _ in writes]
                pos = [i for _, i in writes]
                self._cache.write_pages(
                    ids, pool_k[:, pos], pool_v[:, pos]
                )
        return loaded

    def start_prefix_persistence(self, path: str, fingerprint: str,
                                 interval: float = 30.0) -> None:
        """Dump the prefix registry to ``path`` every ``interval``
        seconds while it has changed — so a SIGKILL'd pod (the
        reference's own failure story: PVC-backed state surviving
        rescheduling, README.md:88) keeps its warm prefixes, not just a
        gracefully drained one. The dump is atomic (os.replace) and
        takes the server lock itself; this thread never holds it across
        the write. Idempotent to call once; close() stops the timer."""
        if self._persist_stop is not None:
            raise RuntimeError("prefix persistence already started")
        self._persist_stop = threading.Event()
        # Remembered for the degraded path: a poisoned-but-readable
        # pool emergency-dumps to the same file on its way down.
        self._persist_path, self._persist_fp = path, fingerprint

        def loop() -> None:
            dumped_at = 0
            while not self._persist_stop.wait(interval):
                with self._hold("control"):
                    registered = self._prefix_registrations
                if registered == dumped_at:
                    continue
                try:
                    self.dump_prefix_cache(path, fingerprint)
                    dumped_at = registered
                except Exception as e:  # never kill serving for a dump
                    print(f"[kvedge-serve] periodic prefix-cache dump "
                          f"failed: {e!r}", flush=True)

        self._persist_thread = threading.Thread(
            target=loop, name="kvedge-prefix-persist", daemon=True
        )
        self._persist_thread.start()

    def close(self, drain: bool = False) -> None:
        """Shut down. Hard close (default) poisons in-flight requests
        with :class:`ServerClosed`; ``drain=True`` stops admission
        immediately (new submits fail with ServerClosed) but lets every
        accepted request decode out its budget before the loop exits —
        the graceful-restart path. Bounded: an in-flight budget is at
        most max_seq tokens."""
        if self._persist_stop is not None:
            # Stop the periodic dump timer first: a dump landing while
            # the pool tears down would read dying device state.
            self._persist_stop.set()
            self._persist_thread.join(timeout=60)
        with self._hold("control"):
            if drain:
                self._draining = True
            else:
                self._closed = True
            # Parked admission tickets wait on their OWN conditions —
            # wake them all into the refusal path.
            self._sched.wake_all_locked()
            self._work.notify_all()
        self._thread.join(timeout=600 if drain else 30)
        if not drain and self._thread.is_alive():
            # A healthy-but-slow step (first-time window compile on
            # a large model can exceed 30 s) must not be classified as a
            # wedged follower below — retry the join once before
            # deciding the thread is dead.
            self._thread.join(timeout=60)
        if drain:
            with self._hold("control"):
                self._closed = True
                self._sched.wake_all_locked()
                self._work.notify_all()
        # A slice-aware cache (runtime/sliceserve.py) releases its
        # followers here — under the lock, so the stop op serializes
        # AFTER any in-flight request thread's cache call (a hard close
        # can race a chunked prefill whose error path still releases its
        # slot) and the cache's idempotence flag is check-then-act
        # atomic. Single-host caches define no stop. Slice ops are
        # deadline-bounded now (runtime/failures.py), so a dead
        # follower poisons the loop with SliceFollowerLost instead of
        # wedging it — the liveness guard below is the backstop for a
        # step wedged OUTSIDE the watchdog (single-host device hang):
        # skip the release rather than hang close() too. stop() itself
        # is also deadline-bounded, so close() stays bounded even when
        # the followers die between the last op and the STOP broadcast.
        with self._hold("control"):
            # A closed pool is never revived: journaled survivors of a
            # poison must not park forever behind a teardown — fail
            # them with the poison (retryable, hint attached) or plain
            # ServerClosed.
            if len(self._journal):
                self._fail_journal_locked(
                    self._poison if self._poison is not None
                    else ServerClosed("server is shut down")
                )
        stop = getattr(self._cache, "stop", None)
        if stop is not None and not self._thread.is_alive():
            with self._hold("control"):
                stop()

    def lower_decode_window(self, n_steps: int | None = None):
        """The greedy decode-window program this server dispatches,
        lowered for its live params and pool (``n_steps`` defaults to
        the window cap) — see :meth:`PagedKVCache.lower_decode_window`."""
        return self._cache.lower_decode_window(
            self._params, n_steps or self._window
        )

    @property
    def degraded(self) -> str | None:
        """The degraded-mode reason, or None while healthy. Lock-free
        on purpose: /healthz reads this and must answer even if some
        thread is misbehaving around the server lock."""
        return self._degraded_reason

    def _degrade(self) -> None:
        """Best-effort degraded-mode work, run once by the exiting
        decode loop, OUTSIDE the lock: emergency-dump the prefix cache
        if the pool is still readable (a follower-lost slice cache
        refuses persistence and a dead op stream would wedge — both
        surface as an exception and the dump is skipped; a single-host
        pool poisoned by a host-side bug is usually intact), then
        notify the workload observer."""
        if self._persist_path is not None and self._prefix_entry_nodes:
            try:
                n = self.dump_prefix_cache(
                    self._persist_path, self._persist_fp
                )
                print(f"[kvedge-serve] degraded: emergency prefix dump "
                      f"wrote {n} entries", flush=True)
            except Exception as e:
                print(f"[kvedge-serve] degraded: emergency prefix dump "
                      f"skipped ({e!r})", flush=True)
        cb = self.on_degraded
        if cb is not None:
            try:
                cb(self._degraded_reason, self._poison)
            except Exception as e:  # observers never re-poison teardown
                print(f"[kvedge-serve] on_degraded observer failed: "
                      f"{e!r}", flush=True)

    def set_params(self, params: dict) -> None:
        """Serve from ``params`` from the next dispatch on (the recovery
        supervisor's checkpoint re-restore). Same shapes and dtypes as
        the tree it replaces run the programs already compiled."""
        summary = weights_summary(params)
        with self._hold("control"):
            self._params = params
            self._weights_gb, self._weights_dtype = summary

    def revive(self, *, prefill_wait_s: float = 30.0) -> int:
        """Warm-restart a poisoned pool in place (recovery supervisor).
        Returns the number of journaled in-flight requests re-admitted.

        Pre-condition: the failed op stream is live again — for a slice
        cache the supervisor runs ``cache.reform()`` FIRST, because the
        slot releases below flow ``_sync`` ops to the (re-joined)
        followers. Raises RuntimeError when the pool is not poisoned or
        its decode loop has not finished exiting.

        The scrub drops everything poisoning stranded: prefix-registry
        pins are evicted (the device K/V behind them is suspect after a
        failure — the emergency dump reloads them from the reusable
        snapshot), every still-admitted slot is released, and the
        slot/reservation books reset to empty. Unjournaled in-flight
        requests were already failed by ``_poison_locked``; journaled
        ones (rung 22) re-admit below into fresh slots — original
        ticket and class preserved, pages restored verbatim via
        ``swapin_pages``, decode resumed from the checkpointed offset
        — transactionally: a re-admission fault re-journals everything
        (nothing lost) and leaves the pool poisoned for the next
        attempt. Compiled programs survive untouched — that is the
        point of reviving over rescheduling.
        """
        # The dying decode thread must be gone before a replacement
        # starts (two loops over one pool would interleave cache calls).
        self._thread.join(timeout=5.0)
        if self._thread.is_alive():
            raise RuntimeError("decode loop still running; cannot revive")
        deadline = time.monotonic() + prefill_wait_s
        with self._hold("control") as hold:
            if self._poison is None:
                raise RuntimeError("pool is not poisoned; nothing to revive")
            # Chunked prefills caught mid-flight by the poison fail on
            # their next cache call and decrement under the lock; wait
            # them out so none can land tokens into the reset pool.
            while self._prefilling > 0:
                left = deadline - time.monotonic()
                if left <= 0:
                    raise RuntimeError(
                        f"{self._prefilling} prefill(s) still in flight "
                        f"after {prefill_wait_s:g}s; cannot revive"
                    )
                hold.pause()
                self._work.wait(timeout=left)
                hold.resume()
            for node in list(self._prefix_entry_nodes):
                # "revive" never demotes: device K/V are suspect after
                # a poison. The host tier and the journal's shadow
                # snapshots are host bytes taken BEFORE the failure —
                # they survive and stay trusted.
                self._evict_prefix_node(node, "revive")
            for slot in range(self._cache.slots):
                if self._cache.is_admitted(slot):
                    self._cache.release(slot)
            self._free_slots = list(range(self._cache.slots))
            self._reserved = 0
            self._lease.clear()
            self._bucket_step_wanted = False
            self._active.clear()
            # The failing loop drained its in-flight window before
            # poisoning; clear defensively and forget the device
            # carry — a revived pipeline restarts from host tokens
            # (a slice cache's reform() already dropped its own).
            self._inflight = None
            self._harvesting = None
            self._finish_ready.clear()
            self._stops_pending = 0
            self._cache.drop_carry()
            if self._cache.min_bucket:
                # Restore the PRE-POISON rung (floored at what the
                # journal re-admissions below need) instead of
                # resetting to the bottom: the compiled programs for
                # that rung survived, and a loaded server stepping up
                # from the bottom would pay a retrace storm the moment
                # traffic returns.
                rung = self._prebucket or self._cache.bucket_for(0)
                rung = max(rung,
                           self._cache.bucket_for(len(self._journal)))
                self._cache.set_bucket(rung)
            # Scheduler scrub: unjournaled swapped-out requests were
            # already failed by _poison_locked (snapshots freed);
            # straggler tickets were woken into the refusal path. The
            # queues restart empty; cumulative counters — including
            # the ticket sequence, so restored tickets stay ordered
            # ahead of post-revive arrivals — survive.
            self._sched.reset_locked()
            restored = self._restore_journal_locked()
            self._poison = None
            self._degraded_reason = None
            self._closed = False
            self._draining = False
            self._thread = threading.Thread(
                target=self._loop, name="kvedge-paged-serve", daemon=True
            )
            self._thread.start()
            if self.tracer is not None:
                # Same recorder, same timeline: the revival lands next
                # to the poison it heals, and the tracer itself needs
                # no reset (it holds no device or thread state).
                self.tracer.event("revive", "serve",
                                  args={"restored": restored})
            self._work.notify_all()
        return restored

    def _restore_journal_locked(self) -> int:
        """Re-admit every journaled request into the scrubbed pool
        (lock held, decode thread not yet started). Each entry rewinds
        its request to the checkpoint — ``generated`` truncates to the
        checkpointed length, the pending token and books restore, and
        the delivered watermark arms ``_emit``'s replay suppression —
        then takes a fresh slot with the verbatim page bytes swapped
        back in. The rewind is idempotent, so the failure path can
        re-journal already-restored entries and retry wholesale.

        Prefix-reference entries (rung 24c) re-materialize the shared
        bytes ONCE per cited node: the first restorer swaps the shadow
        snapshot into freshly pinned pages and resurrects the registry
        entry, every later citer of the same node re-leases those
        pages via ``admit(shared=...)`` — N conversations on one
        system prompt swap in 1 prefix + N suffixes. Shadow refs
        settle only after the WHOLE restore commits; the unwind
        re-puts entries with refs untouched, so a retry still finds
        its shadows."""
        entries = self._journal.take_all()
        all_drained = list(entries)
        node_pages: dict[int, tuple] = {}
        restored: list[tuple[int, JournalEntry]] = []
        t0 = time.perf_counter()
        try:
            while entries:
                entry = entries[0]
                req = entry.req
                if req.cancelled or req.done.is_set():
                    entries.pop(0)
                    continue
                if not self._free_slots:
                    # More checkpoints than slots (the poison caught
                    # swapped-out requests too): the overflow re-queues
                    # below, after the direct restores commit.
                    break
                req.stream_resume_at = max(req.stream_resume_at,
                                           entry.emitted)
                del req.generated[entry.gen_len:]
                req.next_token = entry.next_token
                req.inflight = 0
                # A stop detected after the checkpoint is replay state:
                # the rewound decode re-detects it bit-identically.
                req.stopped = False
                req.pages_reserved = entry.pages_reserved
                req.ticket_no = entry.ticket_no
                req.admit_seq = entry.admit_seq
                req.shared_pages = ()
                req.prefix_node = None
                slot = heapq.heappop(self._free_slots)
                self._reserved += entry.pages_reserved
                self._active[slot] = req
                self._to_state(req, "join_wait", time.perf_counter())
                # In ``restored`` BEFORE the device calls: a faulting
                # admit/swapin must find its slot and reservation in
                # the unwind below (the entry is then briefly in both
                # lists — the double re-journal is a same-key replace).
                restored.append((slot, entry))
                sh_n = entry.prefix_pages_n
                if entry.prefix_node is not None and sh_n:
                    node = entry.prefix_node
                    pins = node_pages.get(node)
                    if pins is None:
                        shadow = self._prefix_shadow[node]
                        fresh = [self._cache.allocate_pinned_page()
                                 for _ in range(sh_n)]
                        try:
                            self._cache.swapin_pages(
                                fresh, shadow["arrays"])
                        except Exception:
                            self._cache.release_pages(fresh)
                            raise
                        self._insert_prefix_entry(
                            req.prompt[:entry.prefix_tokens], fresh)
                        pins = node_pages[node] = tuple(fresh)
                    self._cache.admit(slot, entry.saved_len, pins)
                    self._cache.swapin_slot(slot, entry.arrays,
                                            skip_pages=sh_n)
                    self._lease_take_locked(pins)
                    req.shared_pages = pins
                    req.prefix_node = node
                else:
                    self._cache.admit(slot, entry.saved_len)
                    self._cache.swapin_slot(slot, entry.arrays)
                entries.pop(0)
        except Exception:
            # Transactional unwind: put everything back — restored
            # rows included (their rewind is idempotent) — so the next
            # revive attempt loses nothing. Shadow refs are NOT
            # settled (the re-put entries still cite them); registry
            # entries resurrected above stay until the next revive's
            # scrub evicts them.
            for slot, entry in restored:
                self._active.pop(slot, None)
                self._release_locked(slot, entry.pages_reserved,
                                     entry.req.shared_pages)
                entry.req.shared_pages = ()
                entry.req.prefix_node = None
            for _, entry in restored:
                self._journal.put(entry.req, entry)
            for entry in entries:
                self._journal.put(entry.req, entry)
            raise
        # Slot-overflow checkpoints go back to the SWAP SET under their
        # original tickets (host bookkeeping only — cannot fault): the
        # decode loop resumes them at boundaries exactly like preempted
        # victims, ahead of post-revive arrivals. Prefix-reference
        # entries materialize the FULL byte snapshot here (shadow
        # prefix + own suffix, page axis 1) — a swapped-out request
        # has no live pages to lease, so its resume is self-contained.
        requeued = 0
        for entry in entries:
            req = entry.req
            req.stream_resume_at = max(req.stream_resume_at,
                                       entry.emitted)
            del req.generated[entry.gen_len:]
            req.next_token = entry.next_token
            req.inflight = 0
            req.stopped = False
            arrays = entry.arrays
            pages_needed = entry.pages_reserved
            if entry.prefix_node is not None and entry.prefix_pages_n:
                shadow = self._prefix_shadow[entry.prefix_node]
                arrays = tuple(
                    np.concatenate([s, o], axis=1)
                    for s, o in zip(shadow["arrays"], entry.arrays)
                )
                pages_needed += entry.prefix_pages_n
            req.pages_reserved = pages_needed
            req.shared_pages = ()
            req.prefix_node = None
            self._sched.record_swapout_locked(
                req, entry.pclass, entry.ticket_no,
                pages_needed, entry.saved_len, arrays,
                restore=True,
            )
            requeued += 1
        # Full success: settle every drained entry's shadow reference
        # — restored requests will re-cite at their next checkpoint,
        # requeued ones became self-contained above.
        for entry in all_drained:
            if entry.prefix_node is not None:
                self._journal_drop_locked(entry)
        self._journal_restores += len(restored) + requeued
        if self.tracer is not None and (restored or requeued):
            self.tracer.span(
                "journal-restore", "serve", t0,
                args={"restored": len(restored), "requeued": requeued},
            )
        return len(restored) + requeued

    def stats(self) -> dict:
        # /metrics aggregation mostly off the work lock (rung 26
        # host-path budget): the lock covers only the raw counter and
        # histogram copies that mutate under it; the tracer/SLO/
        # occupancy/slice merges are documented ring-copy reads and
        # happen after release, so a scrape no longer taxes a decode
        # boundary with their assembly. The Prometheus text rendering
        # itself (runtime/status.py) was always outside.
        #
        # A reader does not wait for the lock it measures: if it does
        # not get the lock within 10 ms, it gets the newest snapshot a
        # holder published (the loop, at the end of every hold), which
        # is one hold's counters with that hold's ``clock_s`` like any
        # other. Only a server that has published nothing yet is
        # waited for.
        hold = self._hold("stats")
        if hold.acquire(-1 if self._published is None else 0.01):
            try:
                # The newest snapshot there is, so the one to leave
                # for the next reader too: no reader is ever given an
                # older one than another was given before.
                # locklint: allow[unlocked-call] held: the acquire above is the hold's own, with a time limit, so no with-block fits
                self._publish_locked(time.perf_counter())
            finally:
                hold.release()
        out = dict(self._published)
        self._stats_merge_unlocked(out)
        return out

    def _publish_locked(self, now: float) -> None:
        """Leave the core snapshot as of ``now`` (a stamp taken in
        this hold) for the readers that find the lock taken: some
        20 us of copying."""
        self._published = self._stats_core_locked(now)

    @contextlib.contextmanager
    def _published_on_exit(self):
        """Around the body of a hold of the loop's: on the way out,
        whichever way, the snapshot is published as of the end of the
        loop's last phase, so a reader who comes for what this hold
        did (a request it finished) finds it there."""
        try:
            yield
        finally:
            # locklint: allow[unlocked-call] held: this is entered in the loop's with-statement, after its hold, and so left before it
            self._publish_locked(self._phase.last)

    def _stats_locked(self) -> dict:
        # The flight bundle's variant: ONE acquisition covers the
        # whole document so metrics/SLO/books stay mutually
        # consistent (the chaos invariant). The merge helpers are
        # lock-free reads, safe to run with the lock held too.
        out = self._stats_core_locked(time.perf_counter())
        self._stats_merge_unlocked(out)
        return out

    def _stats_core_locked(self, now: float) -> dict:
        phase_ms = self._phase.snapshot(now)
        ledger = self._ledger.snapshot(now)
        # The handlers' first writes since the last snapshot (each a
        # deque.append from its own thread), folded in under the lock.
        writes = self._first_writes
        while writes:
            self._first_write_ms.observe(writes.popleft())
        out = {
            "degraded": 1 if self._degraded_reason else 0,
            "in_flight": len(self._active),
            "free_slots": len(self._free_slots),
            "free_pages": self._cache.free_pages(),
            "reserved_pages": self._reserved,
            # Capacity semantics (SERVING.md rung 21): the page
            # pool is the admission resource and the bucket is the
            # device batch dim — the gauges an operator needs to
            # see shed/preempt pressure coming.
            "pages_total": self._pages_total,
            "slots_total": self._cache.slots,
            "bucket": self._cache.bucket,
            "bucket_min": self._cache.min_bucket,
            "page_low_watermark": self._page_low_wm,
            "page_high_watermark": self._page_high_wm,
            "window": self._window,
            "kv_dtype": ("int8" if self._cache.kv_quantized
                         else str(self._cfg.dtype)),
            # The tree the programs read: set where it is installed.
            "weights_gb": self._weights_gb,
            "weights_dtype": self._weights_dtype,
            "prefix_entries": len(self._prefix_entry_nodes),
            "prefix_hits": self._prefix_hits,
            "prefix_lookups": self._prefix_lookups,
            "prefix_tokens_saved": self._prefix_tokens_saved,
            # Prefix-cache semantics (SERVING.md rung 24): COW
            # divergence copies, HBM bytes the shared prefixes
            # avoided re-prefilling, the host residency tier, and
            # evictions by cause (one labelled counter in
            # /metrics).
            "prefix_bytes_saved": self._prefix_tokens_saved * (
                self._page_bytes_locked()
                // self._cache.page_size),
            "prefix_cow_copies": self._prefix_cow_copies,
            "prefix_host_entries": len(self._prefix_host_nodes),
            "prefix_host_bytes": self._prefix_host_bytes,
            "prefix_demotions": self._prefix_demotions,
            "prefix_promotions": self._prefix_promotions,
            "prefix_evictions": dict(self._prefix_evictions),
            "journal_shadow_nodes": len(self._prefix_shadow),
            "journal_shadow_bytes": self._journal.extra_bytes,
            "overlap_windows_total": self._overlap_windows,
            "overlap_inflight_depth":
                1 if self._inflight is not None else 0,
            # Histogram snapshots (dict-valued; status.py renders
            # them as Prometheus histograms, scalar consumers
            # should skip them).
            "window_dispatch_harvest_ms": self._hist_rtt.snapshot(),
            "window_host_ms": self._hist_host.snapshot(),
            # Device-time attribution (SERVING.md rung 25): the
            # forced-sync leg of each window on its own, so RTT
            # minus device is host bookkeeping + pipeline slack.
            "window_device_ms": self._hist_device.snapshot(),
            "window_inflight_depth": self._hist_depth.snapshot(),
            "pipeline_joins_total": self._pipeline_joins,
            "first_tokens_on_device_total": self._first_tokens_on_device,
            "pipeline_collapses": dict(self._pipeline_collapses),
            # Per-request stage histograms (SERVING.md rung 18):
            # TTFT and the queue-vs-decode split.
            "ttft_ms": self._hist_ttft.snapshot(),
            "queue_ms": self._hist_queue.snapshot(),
            "decode_ms": self._hist_decode.snapshot(),
            # Per-request mean inter-token gap + completion
            # counters (rung 25 SLI inputs).
            "itl_ms": self._hist_itl.snapshot(),
            "requests_done_total": self._done_total,
            "tokens_done_total": self._tokens_done_total,
            # Durability semantics (SERVING.md rung 22): journal
            # occupancy, checkpoint throughput, and the restores
            # revive() performed — the gauges that prove in-flight
            # requests are actually covered.
            "checkpoint_every": self._checkpoint_every,
            "journal_entries": len(self._journal),
            "journal_bytes": self._journal.nbytes,
            "checkpoints_total": self._checkpoints_total,
            "checkpoint_skipped_total": self._checkpoint_skipped,
            "checkpoint_unchanged_total": self._checkpoints_unchanged,
            "journal_restores_total": self._journal_restores,
            # Device-resident endgame (SERVING.md rung 23):
            # stop-token finishes.
            "stop_finishes_total": self._stop_finishes,
            # The clock inside the loop and the submit path (phases,
            # runtime/tracing.py). clock_s is stamped inside this lock
            # hold: a reader divides a counter's difference by the
            # time between the two snapshots it has, not by the time
            # it asked for them (each waits for the lock).
            "clock_s": now,
            "phase_ms": phase_ms,
            "loop_ms_total": self._loop_ms(now),
            # The work lock's ledger (runtime/tracing.py): the time it
            # was held at all, and each holder's waits and holds. The
            # loop's share is its entry, under the name it had before
            # the ledger.
            **ledger,
            "loop_lock_held_ms_total": ledger["lock_held_ms"]["loop"][1],
            # What finished requests did with their time, state by
            # state: [requests, total ms]. A request's states add up
            # to its life, and every state is over the same requests.
            "request_ms": {
                **{state: [acc.n, acc.total]
                   for state, acc in self._request_ms.items()},
                "first_write": [self._first_write_ms.n,
                                self._first_write_ms.total],
            },
            "prefill_lock_wait_ms": self._hist_prefill_wait.snapshot(),
            "prefill_chunk_ms": self._hist_prefill_chunk.snapshot(),
            "first_emit_ms": self._hist_first_emit.snapshot(),
            # Exact counts at the loop's own boundaries: decode steps
            # (sum of window lengths), row-steps that produced a token
            # over row-steps the device computed (live rows over
            # bucket rows), pages in use x steps (over steps x
            # pages_total: pages in use against reserved_pages), and
            # tokens recorded into requests.
            "decode_steps_total": self._decode_steps,
            "decode_row_steps_total": self._decode_row_steps,
            "decode_bucket_steps_total": self._decode_bucket_steps,
            "pages_live_steps_total": self._pages_live_steps,
            "tokens_emitted_total": self._tokens_emitted,
        }
        if self._window_pages_total:
            # The window layers' pool, beside the full layers' above
            # (``free_pages``, ``pages_total``, ``reserved_pages`` and
            # ``pages_live_steps_total`` keep meaning the full pool).
            out["window_pages_total"] = self._window_pages_total
            out["window_free_pages"] = self._cache.free_window_pages()
            out["window_pages_released_total"] = (
                self._cache.window_pages_released)
            out["window_pages_live_steps_total"] = (
                self._window_pages_live_steps)
        recurrent = self._cache.state.recurrent
        if recurrent is not None:
            # State of the second kind (SERVING.md "Recurrent state"):
            # every slot holds its rows' whether a request is in it or
            # not, so slots bound admission as memory too. The picks
            # are the decode windows' own counts, summed at harvest.
            from kvedge_tpu.models import hybrid

            picks = self._cache.expert_picks
            if "ssm" in recurrent:
                out["state_rows"] = self._cache.slots
                out["state_gb"] = (recurrent["ssm"].nbytes
                                   + recurrent["conv"].nbytes) / 1e9
            out["expert_picks_total"] = int(picks[0])
            out["expert_picks_held_total"] = int(picks[1])
            out["expert_picks_by_expert"] = [int(n) for n in picks[2:-1]]
            # (layer, held expert, step) triples in which the expert
            # got a live row's pick, of the ``expert_reads_per_step``
            # (routed layers x held experts) matrices every step reads.
            out["expert_touched_total"] = int(picks[-1])
            out["expert_reads_per_step"] = hybrid.expert_reads_per_step(
                self._cfg)
            # The matrices the decode windows did read: the touched
            # ones where a window's program walks the list of them,
            # ``expert_reads_per_step`` a step where it does not.
            out["expert_reads_total"] = self._cache.expert_reads
        if self._autotune is not None:
            # Online window controller (SERVING.md rung 26): the
            # current pick and its EWMA inputs — R (host turnaround
            # per window) and t (per-step device time). R/t gauges
            # make the law auditable from a scrape: the pick should
            # be the smallest pow2 with window*t >= R.
            snap = self._autotune.snapshot()
            out["autotune_window"] = snap["window"]
            out["autotune_r_ms"] = round(snap["r_ms"], 3)
            out["autotune_t_ms"] = round(snap["t_ms"], 4)
            out["autotune_updates"] = snap["updates"]
        # Scheduler observability: per-class queue depth and wait
        # histograms, preemption/resume/shed counters, swap gauges.
        out.update(self._sched.stats_locked())
        if self._degraded_reason:
            out["degraded_reason"] = self._degraded_reason
        return out

    def _stats_merge_unlocked(self, out: dict) -> None:
        """Merge the lock-free observability planes into a stats
        snapshot: the tracer, the SLO engine and the occupancy ring
        all read ring copies, and the slice cache's broadcast bill is
        a plain dict the runner thread owns. Callable with or without
        the work lock (stats() releases it first; flight_bundle()
        keeps its single-acquisition consistency contract)."""
        if self.tracer is not None:
            out.update(self.tracer.stats())
        if self._slo is not None:
            # Rolling SLI gauges + burn rates (fast window), flat
            # for /metrics; GET /slo carries the full document.
            out.update(self._slo.metrics())
        if self._occ_ring is not None:
            # Latest occupancy sample, flattened into gauges; the
            # timeline itself exports via the Chrome counter track
            # and the flight bundle's tail.
            out["occupancy_samples_total"] = (
                self._occ_ring.samples_total
            )
            last = self._occ_ring.last()
            if last:
                for k, v in last.items():
                    out["occupancy_" + k] = v
        op_ms = getattr(self._cache, "op_broadcast_ms", None)
        if op_ms:
            # Slice-cache per-op broadcast bill (rung 25): dict of
            # op kind -> [frames, cumulative ms], rendered as two
            # labelled counters in /metrics.
            out["slice_op_ms"] = {k: list(v) for k, v in op_ms.items()}

    # ---- SLO engine + flight bundle (SERVING.md rung 25) -----------------

    def slo_doc(self) -> dict | None:
        """The ``GET /slo`` document, or None when the engine is off
        (the route 404s with the knob pointer). Lock-free: the engine
        reads ring copies."""
        if self._slo is None:
            return None
        return self._slo.doc()

    def _config_doc_locked(self) -> dict:
        """The serving-shape config the bundle fingerprints — enough
        to tell 'same knobs, new failure' from 'different deployment'
        across two bundles without shipping the whole payload TOML."""
        return {
            "slots": self._cache.slots,
            "pages_total": self._pages_total,
            "page_size": self._cache.page_size,
            "window": self._window,
            "prefill_chunk": self._prefill_chunk,
            "prefix_cache": int(self._prefix_enabled),
            "checkpoint_every": self._checkpoint_every,
            "page_low_watermark": self._page_low_wm,
            "page_high_watermark": self._page_high_wm,
            "kv_dtype": ("int8" if self._cache.kv_quantized
                         else str(self._cfg.dtype)),
            "slo": (dataclasses.asdict(self._slo.objectives)
                    if self._slo is not None else None),
        }

    def flight_bundle(self) -> dict:
        """The rung-25 post-mortem bundle: one versioned JSON document
        carrying everything a human (or the chaos harness) needs to
        explain a dead replica — metrics snapshot, SLO/burn state,
        occupancy timeline tail, journal summary, page-accounting
        books, config fingerprint, trace tail.

        Everything under the lock is ONE acquisition, so the metrics
        snapshot, the SLO state and the page books are mutually
        consistent (the chaos invariant compares them). Works on a
        poisoned pool: nothing here touches device state beyond the
        same host-side books stats() already reads."""
        with self._hold("control"):
            doc = {
                "bundle_version": 1,
                "reason": self._degraded_reason,
                "degraded": 1 if self._degraded_reason else 0,
                "metrics": self._stats_locked(),
                "slo": (self._slo.doc()
                        if self._slo is not None else None),
                "occupancy_tail": (self._occ_ring.tail()
                                   if self._occ_ring is not None
                                   else []),
                "journal": {
                    "entries": len(self._journal),
                    "bytes": self._journal.nbytes,
                    "extra_bytes": self._journal.extra_bytes,
                    "budget_bytes": self._journal.max_bytes,
                },
                "config": self._config_doc_locked(),
            }
            books = getattr(self._cache, "page_accounting", None)
            if books is not None:
                try:
                    doc["page_accounting"] = books()
                except Exception:
                    # A torn-down cache must not take the bundle with
                    # it — the post-mortem is most valuable exactly
                    # when things are broken.
                    doc["page_accounting"] = None
        doc["config_fingerprint"] = hashlib.sha256(
            json.dumps(doc["config"], sort_keys=True).encode("utf-8")
        ).hexdigest()[:12]
        # Trace tail outside the lock: the tracer ring is lock-free by
        # contract and last_events() can retry its snapshot.
        doc["trace_tail"] = (self.tracer.last_events()
                             if self.tracer is not None else [])
        return doc

    def _occupancy_fields_locked(self) -> dict:
        """One occupancy sample (lock held): pool pages/HBM from the
        cache plus the serving layer's own residency gauges. All O(1)
        attribute reads — safe at every quiescent boundary."""
        fields = {
            "slots_active": len(self._active),
            "reserved_pages": self._reserved,
            "prefix_entries": len(self._prefix_entry_nodes),
            "prefix_host_bytes": self._prefix_host_bytes,
            "journal_bytes": self._journal.nbytes,
            "queue_depth": self._sched.depth_locked(),
        }
        occ = getattr(self._cache, "occupancy", None)
        if occ is not None:
            fields.update(occ())
        return fields

    def _observe_boundary_locked(self) -> None:
        """Quiescent-boundary observability feed (rung 25, lock held):
        one SLO-ring snapshot (throttled inside the engine) and one
        occupancy sample. Touches no device state and emits nothing —
        bit-identity with the knobs off is structural (None checks)."""
        if self._slo is None and self._occ_ring is None:
            return
        now = time.perf_counter()
        if self._slo is not None:
            self._slo.observe(now, {
                "ttft_ms": self._hist_ttft.snapshot(),
                "itl_ms": self._hist_itl.snapshot(),
                "queue_ms": self._hist_queue.snapshot(),
                "tokens_total": self._tokens_done_total,
                "done_total": self._done_total,
                "shed_total": self._sched.shed,
            })
        if self._occ_ring is not None:
            self._occ_ring.sample(
                now, self._occupancy_fields_locked()
            )

    # ---- decode loop -----------------------------------------------------

    def _lease_take_locked(self, pages) -> None:
        """Acquire one live-sharer lease per page (lock held). The
        FIRST sharer of a page books its one reservation unit; later
        sharers ride the existing lease for free (rung 24)."""
        for p in pages:
            n = self._lease.get(p, 0)
            self._lease[p] = n + 1
            if n == 0:
                self._reserved += 1

    def _lease_drop_locked(self, pages) -> None:
        """Release leases (lock held): a page's reservation unit frees
        only when its LAST live sharer leaves."""
        for p in pages:
            n = self._lease[p] - 1
            if n:
                self._lease[p] = n
            else:
                del self._lease[p]
                self._reserved -= 1

    def _release_locked(self, slot: int, pages_needed: int,
                        shared: tuple = ()) -> None:
        """Return a slot + its reservation to the pool (lock held).
        ``pages_needed`` is the request's PRIVATE reservation;
        ``shared`` drops its prefix-page leases too."""
        if self._cache.is_admitted(slot):
            self._cache.release(slot)
        heapq.heappush(self._free_slots, slot)
        self._reserved -= pages_needed
        self._lease_drop_locked(shared)
        # Targeted admission wakeup: the policy head (and ONLY the
        # head) re-checks capacity; the work condition still fans out
        # to the decode loop (which may now resume a swapped request).
        self._sched.wake_head_locked()
        self._work.notify_all()

    def _finish_request_locked(self, slot: int, req: _Request) -> None:
        """Complete a finished request (lock held): decode-stage
        histogram, completion span, slot/reservation release, waiter
        wakeup — the ONE exit path every normal finish site (budget
        sweep, inline overlap finish) shares."""
        t1 = time.perf_counter()
        if req.t_admit:
            self._hist_decode.observe((t1 - req.t_admit) * 1e3)
        # Goodput + inter-token SLI inputs (rung 25): every normal
        # finish funnels through here, so the counters are exact.
        self._done_total += 1
        self._tokens_done_total += len(req.generated)
        if req.t_first and len(req.generated) > 1:
            self._hist_itl.observe(
                (t1 - req.t_first) * 1e3 / (len(req.generated) - 1)
            )
        if req.trace:
            # The state the request ends in, with what it made.
            self.tracer.span(
                "decode", "serve",
                req.t_state if req.state == "decode" else t1, t1,
                rid=req.rid,
                args={"tokens": len(req.generated),
                      "class": req.pclass},
            )
        # Its ledger closes on the same stamp, and its states join
        # those of the requests that finished before it.
        self._end_request(req, t1)
        for state, ms in req.state_ms.items():
            self._request_ms[state].observe(ms)
        del self._active[slot]
        self._journal.pop(req)  # a finished request never resumes
        if self._prefix_enabled:
            # Multi-turn reuse (rung 24a): the finished slot's
            # committed K/V — prompt AND generated — is exact reusable
            # prefix state (K/V at position i depend only on tokens
            # 0..i), so a follow-up turn whose prompt embeds this
            # conversation hits. Registered before the release drops
            # the page refs; clamped to the committed device length so
            # a deferred stop can never register scribbled positions.
            tokens = (req.prompt + req.generated)[
                :self._cache.slot_length(slot)]
            self._register_prefixes(
                tokens, self._cache.slot_pages(slot)
            )
        self._release_locked(slot, self._pages_for(req),
                             req.shared_pages)
        if req.stream is not None:
            req.stream.put(_STREAM_DONE)
        req.done.set()

    def _pages_needed(self, total: int) -> int:
        """Worst-case pages for a ``total``-token request."""
        return -(-total // self._cache.page_size)

    @staticmethod
    def _pages_for(req: _Request) -> int:
        return req.pages_reserved

    @staticmethod
    def _emit(req: _Request, token: int) -> None:
        """Record a generated token (and stream it when requested).
        After a journal restore, indices below ``stream_resume_at``
        are REPLAY — bit-identical regenerations of tokens the
        consumer already received — recorded but not re-streamed
        (exactly-once). The normal path's watermark is 0, so this is
        one dead comparison per token."""
        idx = len(req.generated)
        req.generated.append(token)
        if req.stream is not None and idx >= req.stream_resume_at:
            req.stream.put(token)

    @staticmethod
    def _emit_many(req: _Request, tokens: list) -> None:
        """Bulk :meth:`_emit`: one ``extend`` for the token log and
        the same exactly-once replay watermark for the stream. The
        harvest hot path hands whole per-row windows here (plain
        Python ints from ``ndarray.tolist()``) instead of looping
        ``_emit`` per token — the per-token Python frame was a
        measurable slice of the boundary budget at window 64."""
        if not tokens:
            return
        idx = len(req.generated)
        req.generated.extend(tokens)
        if req.stream is not None:
            skip = req.stream_resume_at - idx
            put = req.stream.put
            for t in (tokens[skip:] if skip > 0 else tokens):
                put(t)

    def _note_emitted_locked(self, req: _Request, before: int) -> None:
        """Book one row's emission (lock held; ``before`` is
        ``len(req.generated)`` before it): one addition per row,
        replays after a journal restore included, and for a request's
        first tokens the ``first_emit_ms`` observation — the pick of
        the first token (``t_first``) to its put on the stream, or
        its append for a buffered request."""
        self._tokens_emitted += len(req.generated) - before
        if (before == 0 and req.generated
                and req.stream_resume_at == 0):
            req.t_emit = time.perf_counter()
            self._hist_first_emit.observe(
                (req.t_emit - req.t_first) * 1e3)

    def _emit_pending_locked(self, req: _Request) -> None:
        """Emit the request's pending token alone, and book it."""
        self._first_token_locked(req)
        before = len(req.generated)
        self._emit(req, req.next_token)
        self._note_emitted_locked(req, before)

    def _count_steps_locked(self, steps: int, bucket: int,
                            rows) -> None:
        """Book one window (or single step) of
        ``steps`` decode steps over ``bucket`` device rows, before its
        ``rows`` ((slot, request), live and still admitted) emit and
        release: the steps, the row-steps the device computed, and
        the pages in use times the steps — pages holding tokens of
        the rows, by the lengths the cache keeps on the host (a window
        still in flight included), a shared prefix page once however
        many rows lease it. O(rows)."""
        self._decode_steps += steps
        self._decode_bucket_steps += bucket * steps
        page = self._cache.page_size
        live = len(self._lease)
        for slot, req in rows:
            live += (-(-self._cache.slot_length(slot) // page)
                     - len(req.shared_pages))
        self._pages_live_steps += live * steps
        if self._window_pages_total:
            self._window_pages_live_steps += steps * sum(
                self._cache.window_pages_held(slot) for slot, _ in rows)

    @staticmethod
    def _key_data_shape(samplers) -> tuple:
        """Trailing shape of one row's raw key data (threefry: (2,));
        taken from a live request so the impl is never hardcoded."""
        return next(iter(samplers.values())).key_data.shape

    def _sweep_cancelled_locked(self) -> None:
        """Cancelled requests leave at a boundary: slot and pages
        return to the pool, the waiter (if any) gets RequestCancelled.
        Runs before the finish-sweep so a cancel that raced budget
        completion still wins — the consumer is gone either way."""
        for slot in list(self._active):
            req = self._active[slot]
            if not req.cancelled:
                continue
            del self._active[slot]
            self._journal.pop(req)  # a cancelled request never resumes
            self._release_locked(slot, self._pages_for(req),
                                 req.shared_pages)
            self._fail_request(req, RequestCancelled(
                "request cancelled mid-decode"))

    def _note_finish_candidate_locked(self, slot: int,
                                      req: _Request) -> None:
        """Register a slot for the O(finishes) boundary sweep (lock
        held): called by every site that installs a pending token
        whose stepless emission would complete the request (budget
        filled, stop token, or an already-stopped row awaiting its
        deferred finish). The sweep re-validates, so a spurious
        registration is one wasted lookup, never a wrong finish."""
        if (req.stopped
                or len(req.generated) + 1 >= req.n_new
                or req.next_token == req.stop_token):
            self._finish_ready.add(slot)

    def _finish_stopped_locked(self, slot: int, req: _Request) -> None:
        """Complete a stop-terminated row (lock held, truncated stream
        already emitted with the stop token last). If an in-flight
        window still touches this slot its pages are still being
        scattered into on device — defer: mark the row stopped (later
        harvests skip its emission), force a boundary via
        ``_stops_pending``, and let the sweep finish it there."""
        self._stop_finishes += 1
        rec = self._inflight
        if rec is not None and any(
                s == slot for s, _, _ in rec["parts"]):
            req.stopped = True
            self._finish_ready.add(slot)
            self._stops_pending += 1
            return
        self._finish_request_locked(slot, req)

    def _sweep_finished_locked(self) -> None:
        """A request whose pending token completes its budget — or IS
        its stop token — needs no step at all (the token is already
        known): finish it before the batch, the same discipline as
        generate()'s n_new - 1 decode steps. O(active-finishes), not
        O(bucket): only slots registered in ``_finish_ready`` are
        examined (rung 23 — at bucket 256 the per-boundary scan was
        the last host cost scaling with slot count), and each entry is
        re-validated against the live request before acting."""
        for slot in sorted(self._finish_ready):
            req = self._active.get(slot)
            if req is None or req.cancelled:
                continue
            if req.stopped:
                # Deferred stop finish: the truncated stream (stop
                # token last) was emitted at harvest time.
                self._finish_request_locked(slot, req)
            elif len(req.generated) + 1 >= req.n_new:
                self._emit_pending_locked(req)
                self._finish_request_locked(slot, req)
            elif req.next_token == req.stop_token:
                self._emit_pending_locked(req)
                self._stop_finishes += 1
                self._finish_request_locked(slot, req)
        self._finish_ready.clear()
        # Every deferred stop finished (or was cancelled) above — this
        # sweep IS the boundary _stops_pending forced.
        self._stops_pending = 0

    # ---- scheduler boundary hooks (SERVING.md rung 17) -------------------

    def _sched_attention_locked(self, *,
                                ignore_inflight: bool = False) -> bool:
        """Does the decode loop need a non-overlapped boundary for the
        scheduler (lock held)? True when the policy head could RESUME
        right now, or is starved and a preemptable victim exists. A
        head ticket that already fits is its own thread's job — no
        boundary needed. ``ignore_inflight`` is the pipeline-collapse
        variant: at the harvest-or-dispatch decision every active row
        still carries in-flight window tokens, but the harvest that a
        collapse implies reconciles them — so a victim is judged by
        what it will be AT the boundary, not mid-window."""
        head = self._sched.head_locked()
        if head is None:
            return False
        if (self._free_slots
                and self._reserved + head.pages_needed
                <= self._pages_total):
            return head.resume
        return (self._sched.preemption_enabled
                and self._pick_victim_locked(
                    head, ignore_inflight=ignore_inflight) is not None)

    def _swap_cost_locked(self, req: _Request, *,
                          include_inflight: bool = False) -> int:
        """Host bytes req's swap snapshot would occupy (lock held) —
        the budget check BEFORE paying the device gather.
        ``include_inflight`` prices the snapshot AS OF the next
        reconciled boundary (live length + in-flight window tokens):
        the pipeline-collapse probe must predict the boundary-time
        cost, or it can collapse the pipeline for a victim whose
        grown snapshot the budget then declines — a wasted collapse."""
        n_tokens = len(req.prompt) + len(req.generated)
        if include_inflight:
            n_tokens += req.inflight
        n_pages = -(-n_tokens // self._cache.page_size)
        return (n_pages * self._page_bytes_locked()
                + self._cache.row_state_bytes())

    def _page_bytes_locked(self) -> int:
        """Host bytes one KV page occupies (lock held; lazy — the
        pool's slab shapes are fixed at boot). Shared by swap-cost
        pricing and the prefix bytes-saved gauge."""
        if self._swap_page_bytes is None:
            st = self._cache.state
            per = st.pool_k.nbytes + st.pool_v.nbytes
            if st.scale_k is not None:
                per += st.scale_k.nbytes + st.scale_v.nbytes
            self._swap_page_bytes = -(-per // self._cache.num_pages)
        return self._swap_page_bytes

    def _pick_victim_locked(self, head, *,
                            ignore_inflight: bool = False) -> int | None:
        """The slot to preempt for ``head``, or None: a STRICTLY
        lower-class active request — never an equal (no intra-class
        churn) — preferring the lowest class, then the LATEST admitted
        (least progress lost), whose snapshot fits the host budget.
        Rows with in-flight window tokens are skipped: preemption
        joins only at reconciled boundaries (``ignore_inflight`` —
        the pipeline-collapse probe — looks past tokens the imminent
        harvest will reconcile)."""
        head_rank = self._sched.rank(head.pclass)
        best_slot, best_key = None, None
        for slot, req in self._active.items():
            if req.cancelled or (req.inflight and not ignore_inflight):
                continue
            rank = self._sched.rank(req.pclass)
            if rank <= head_rank:
                continue
            if not self._sched.swap_fits_locked(
                    self._swap_cost_locked(
                        req, include_inflight=ignore_inflight)):
                continue
            key = (rank, req.admit_seq)
            if best_key is None or key > best_key:
                best_slot, best_key = slot, key
        return best_slot

    def _maybe_resume_locked(self) -> None:
        """Re-admit swapped-out requests while the policy head is a
        resume entry that fits (lock held, boundary only). Worst-case
        reservation is re-acquired FIRST — the same invariant that
        makes normal admission safe makes swap-in safe: once the
        reservation is booked, ``admit`` + later ``grow`` can never
        starve (registry pins are evictable on demand). The page bytes
        go back verbatim (``swapin_pages`` — no dtype round trip), and
        the positional key schedule plus the host-held
        ``next_token``/``generated`` make the resumed stream
        bit-identical to a never-preempted run."""
        while True:
            head = self._sched.head_locked()
            if (head is None or not head.resume
                    or not self._free_slots
                    or self._reserved + head.pages_needed
                    > self._pages_total
                    or not self._resume_pages_ok_locked(
                        head.pages_needed)):
                return
            if self._free_slots[0] >= self._cache.bucket:
                # The resume row lies above the device bucket: step up
                # now if nothing is in flight, else at the next
                # boundary (this method only runs at boundaries, so
                # the flag lands one iteration later at worst).
                if self._inflight is None:
                    self._cache.set_bucket(
                        self._cache.bucket_for(self._free_slots[0] + 1)
                    )
                else:
                    self._bucket_step_wanted = True
                    return
            arrays = head.arrays
            self._sched.pop_resume_locked(head)
            req = head.req
            slot = heapq.heappop(self._free_slots)
            self._reserved += head.pages_needed
            # Active BEFORE the device calls: if the swap-in faults,
            # the poison path owns this waiter like any other.
            self._active[slot] = req
            self._note_finish_candidate_locked(slot, req)
            self._cache.admit(slot, head.saved_len)
            self._cache.swapin_slot(slot, arrays)
            # Back in the pool, and waiting for a window again.
            self._to_state(req, "join_wait", time.perf_counter())

    def _maybe_preempt_locked(self) -> None:
        """Swap out lower-class victims while the policy head is
        starved for capacity (lock held, boundary only). The victim's
        live pages — exactly ceil(len/page_size), as stored — move to
        host RAM, its slot and reservation free, and a resume entry
        under its ORIGINAL ticket re-enters the queue; the freed
        capacity wakes the head ticket."""
        if not self._sched.preemption_enabled:
            return
        while True:
            head = self._sched.head_locked()
            if head is None:
                return
            if (self._free_slots
                    and self._reserved + head.pages_needed
                    <= self._pages_total):
                self._sched.wake_head_locked()
                return
            victim = self._pick_victim_locked(head)
            if victim is None:
                return
            req = self._active[victim]
            # A first token still on the device sits in the pool's row
            # at this slot, and the resume takes another.
            self._first_token_locked(req)
            saved_len = len(req.prompt) + len(req.generated)
            n_pages = -(-saved_len // self._cache.page_size)
            # slot_pages is position-ordered; pages grown past the
            # live length hold no committed K/V and are simply freed.
            ids = self._cache.slot_pages(victim)[:n_pages]
            # The row's recurrent state (a block that keeps one) goes
            # with its pages, verbatim: it is resumed on neither
            # without the other.
            arrays = (self._cache.swapout_pages(ids)
                      + self._cache.swapout_row(victim))
            self._to_state(req, "swapped", time.perf_counter())
            del self._active[victim]
            # A preempted victim becomes SELF-CONTAINED: the verbatim
            # gather above copied its shared-prefix pages too, so its
            # leases dissolve and the resume prices (and later
            # re-reserves) the full footprint. Conservative — a resume
            # could in principle re-match the trie — but a resume that
            # cannot depend on cache state is a resume that always
            # fits its books.
            full = req.pages_reserved + len(req.shared_pages)
            self._release_locked(victim, req.pages_reserved,
                                 req.shared_pages)
            req.pages_reserved = full
            req.shared_pages = ()
            req.prefix_node = None
            self._sched.record_swapout_locked(
                req, req.pclass, req.ticket_no, full,
                saved_len, arrays,
            )

    def _loop(self) -> None:
        self._loop_since = self._phase.mark()
        while True:
            # From here to the lock held (the step stops the phase):
            # the loop's wait for the lock, in which prefill chunks run.
            with self._lock_wait:
                # Fair handoff: the loop would otherwise reacquire the
                # lock immediately, and under CPython's GIL an admission
                # waiter whose timeout already expired can lose that
                # race at EVERY boundary while device steps hold the
                # lock (lock convoy — observed as a waiter never getting
                # to raise ServerBusy until the occupying request
                # finished). One zero-sleep with the lock released
                # yields the GIL so waiters can take it.
                # locklint: allow[sleep-under-lock] deliberate GIL yield with the lock RELEASED — breaks the decode loop's lock convoy so expired admission waiters win the reacquisition race (rung 17 fair handoff; removing it starves ServerBusy)
                time.sleep(0)
                verdict = self._loop_once()
            if verdict == "exit":
                self._loop_ran.observe(
                    (time.perf_counter() - self._loop_since) * 1e3)
                self._loop_since = 0.0
                if self._poison is not None:
                    self._degrade()  # outside the lock, loop exited
                return

    def _loop_ms(self, now: float) -> float:
        """Milliseconds the loop thread has run by ``now``: what its
        phases add up to."""
        since = self._loop_since
        live = (now - since) * 1e3 if since else 0.0
        return self._loop_ran.total + live

    def _loop_once(self) -> str:
        """One iteration of the double-buffered decode loop ("exit"
        ends it).

        Two alternating shapes. At a NON-OVERLAPPED BOUNDARY
        (``_inflight is None``) it reconciles with nothing in flight —
        cancel sweep, finish sweep, admissions implicitly via
        ``_active`` — then DISPATCHES a window without harvesting
        it. With a window IN FLIGHT it first
        enqueues the next window on the device-resident carry (no host
        round trip between the two — this is the overlap), then
        harvests and processes the previous window's tokens while the
        next one runs. An admission does not end this: a newcomer
        (active, nothing of it in flight, its first token picked and
        still on the device, or read back by its handler) enters the
        next overlapped window beside the rows that ride the carry,
        and the read of the previous window's tokens is made with the
        lock released (``_harvest_locked``), so that its prefill
        chunks are not held up by it either. Whenever exactness
        needs a boundary (``_boundary_wanted_locked``: a cancel, a
        bucket step, a stop, a due checkpoint, the scheduler's resume
        or preemption, a newcomer to a server that checkpoints) it
        harvests WITHOUT dispatching, so the next iteration
        reconciles at a boundary.

        A speculatively dispatched window can never corrupt state: each
        row's device-side ``steps_left`` cap freezes it at its true
        budget (frozen rows stop scattering K/V and stop advancing
        length — kvcache._paged_decode_window_capped_impl), and the
        host truncates each row's emitted stream at its own cap.

        One hold of the lock, and as many iterations as it takes to
        leave it (``_iterate_locked``): an iteration that gave the lock
        up for its wait for a window ("again") goes round in the same
        hold, so that between a window's tokens and the next dispatch
        the loop asks for the lock once, after that wait, and not a
        second time behind whatever chain of prefill chunks got in.
        The handlers had the lock for the length of the wait: that is
        the hand-off ``_loop`` makes between two holds.
        """
        with self._hold("loop", waited=self._lock_wait) as hold, \
                self._published_on_exit():
            # The wait for the lock ended on the acquire's stamp; the
            # next one is made now.
            self._lock_wait = self._phase("loop/lock_wait")
            while True:
                verdict = self._iterate_locked(hold)
                if verdict != "again":
                    return verdict

    def _iterate_locked(self, hold: Hold) -> str:
        """One iteration of ``_loop_once`` (lock held, by ``hold``):
        "exit" ends the loop, "ran" the hold, and "again" says the
        lock was let go and taken back inside the iteration."""
        phase = self._phase
        while (not self._active and self._inflight is None
               and not self._closed
               and not self._sched_attention_locked()
               and not (self._draining
                        and not self._prefilling)):
            hold.pause()
            with phase("loop/wait_work"):
                self._work.wait()
            hold.resume()
        if (self._draining and not self._active
                and self._inflight is None
                and not self._prefilling
                and not self._sched.resume_pending_locked()):
            return "exit"
        if self._closed:
            # Hard close: abandon the in-flight window unforced
            # (the device finishes it harmlessly; never block a
            # close on a potentially dead op stream) and fail the
            # waiters.
            rec, self._inflight = self._inflight, None
            if rec is not None:
                for _, req, adv in rec["parts"]:
                    req.inflight -= adv
            for req in self._active.values():
                self._fail_request(req, ServerClosed(
                    "server shut down mid-request"))
            self._active.clear()
            self._fail_swapped_closed_locked()
            return "exit"
        try:
            if self._inflight is None:
                with phase("loop/boundary"):
                    self._sweep_cancelled_locked()
                    self._sweep_finished_locked()
                    # Preemption/resume join ONLY here — the
                    # non-overlapped boundary, where every row's
                    # tokens are reconciled and cache state is
                    # quiescent. Checkpoints share the boundary
                    # for the same reason: the swapout bytes must
                    # cover a reconciled, nothing-in-flight
                    # snapshot.
                    self._maybe_resume_locked()
                    self._maybe_preempt_locked()
                    self._maybe_step_bucket_locked()
                    self._maybe_checkpoint_locked()
                    self._observe_boundary_locked()
                if not self._active:
                    return "ran"
                with phase("loop/dispatch"):
                    self._inflight = self._dispatch_window_locked(
                        first=True
                    )
                return "ran"
            prev, self._inflight = self._inflight, None
            try:
                with phase("loop/boundary"):
                    collapse = self._boundary_wanted_locked(prev)
                if collapse:
                    # Overlap boundary: the pipeline collapses so a
                    # cancel/swap/resize can join reconciled.
                    self._pipeline_collapses[collapse] += 1
                    if self.tracer is not None:
                        self.tracer.event(
                            "boundary", "serve",
                            args={"reason": "reconcile",
                                  "cause": collapse})
                else:
                    # Enqueue N+1 on the carry BEFORE touching
                    # N's result — the device starts N+1 the
                    # moment N retires, while the host is still
                    # in the harvest below.
                    with phase("loop/dispatch"):
                        self._inflight = (
                            self._dispatch_window_locked(
                                first=False
                            )
                        )
                if self._harvest_locked(prev, hold):
                    return "again"
            except Exception:
                # prev was not reconciled — restore its inflight
                # accounting and drain it with whatever else is
                # queued, then poison below.
                self._drain_rec_locked(prev)
                raise
        except Exception as e:
            # Poison path: drain the in-flight window FIRST so
            # recovery (revive/reform) never races a queued device
            # program, then fail every waiter loudly.
            self._drain_inflight_locked()
            self._poison_locked(classify_failure(e))
            return "exit"
        return "ran"

    def _boundary_wanted_locked(self, prev: dict) -> str:
        """Should the pipeline fall back to a non-overlapped boundary
        instead of dispatching the next window? The cause if so (a key
        of ``pipeline_collapses``), "" if not.

        ``cancel``: a cancel must be honored. ``newcomer``: a slot is
        active that the in-flight window never dispatched, and the
        server checkpoints (``checkpoint_every > 0``, the only reason
        left): the boundary a newcomer joins at ticks the checkpoint
        clock, and at a cadence of 1 journals it before its first
        step, which rung 22 keeps.
        Otherwise a window's carry is one token a row, and the
        newcomer's is picked (on the device, or host-known; nothing
        of it is in flight): the next overlapped dispatch feeds that
        row from the pool's row of first tokens or from the host, and
        the others from the carry (``_dispatch_window_locked``), so a
        newcomer to such a pipeline is no cause. ``scheduler``: a
        resumable or starved-but-preemptable head collapses the
        pipeline to a boundary, where the swap may join. ``bucket``:
        the device batch dim can only resize with nothing in flight.
        ``stop``: a finish waits for its boundary's sweep: a stop
        token's, deferred while a window still wrote to its row, or
        that of a row with nothing in flight whose pending token ends
        its request with no step at all (a newcomer asked for one
        token, or whose first token is its stop)."""
        if any(req.cancelled for req in self._active.values()):
            return "cancel"
        if self._checkpoint_every > 0:
            dispatched = {slot for slot, _, _ in prev["parts"]}
            if any(slot not in dispatched for slot in self._active):
                return "newcomer"
        if self._bucket_step_wanted:
            return "bucket"
        if self._stops_pending > 0 or any(
                req is not None and req.inflight == 0
                for req in map(self._active.get, self._finish_ready)):
            return "stop"
        # ``checkpoint``: an overdue checkpoint clock (rung 22). A
        # saturated pipeline can run windows back-to-back indefinitely;
        # durability needs a real boundary every ``checkpoint_every``
        # windows, so the due clock forces the collapse the checkpoint
        # rides.
        if (self._checkpoint_every > 0
                and self._ckpt_clock >= self._checkpoint_every):
            return "checkpoint"
        if self._sched_attention_locked(ignore_inflight=True):
            return "scheduler"
        return ""

    def _fail_swapped_closed_locked(self) -> None:
        """Hard close reaches the swap set like the active set: a
        swapped-out request will never be resumed by an exiting loop —
        fail its waiter and free the host snapshot."""
        for entry in self._sched.take_swapped_locked():
            entry.arrays = ()  # nothing will journal this snapshot
            self._fail_request(entry.req, ServerClosed(
                "server shut down mid-request (swapped out)"))
        self._sched.wake_all_locked()

    def _dispatch_window_locked(self, first: bool) -> dict | None:
        """Enqueue one capped window for every active slot with budget
        remaining (lock held); returns the in-flight record, or None
        when no slot can advance.

        ``first`` distinguishes the boundary dispatch (explicit
        host-known pending tokens) from the overlapped dispatch: there
        a row with steps in flight states no token (-1) and the cache
        feeds it the previous window's final token row, still resident
        on device, while a row with nothing in flight (a newcomer: it
        sat out the window in flight, and ``next_token`` is its pick)
        is fed as a first window's is: ``next_token`` as the host has
        it, which for a pick still on the device is
        ``FIRST_ON_DEVICE``, and the cache takes the row's token from
        where the pick put it; where every row is such a one (all that
        were in flight end with that window), the carry is not read.
        The per-row
        cap is ``n_new - len(generated) - inflight - 1``: committed position
        plus the pending token the finish-check emits stepless, so a
        speculative window can never decode past a budget the host
        has not reconciled yet. A row whose previous window froze it
        early always reaches cap 0 here and sits the window out.
        """
        parts = []
        for slot, req in self._active.items():
            cap = req.n_new - len(req.generated) - req.inflight - 1
            if cap > 0 and not req.stopped:
                parts.append((slot, req, cap))
            elif req.inflight == 0:
                # Self-healing backstop for the O(finishes) sweep:
                # this loop is already O(active), so re-registering an
                # idle zero-budget (or stop-terminated) row costs
                # nothing and bounds a missed registration at one
                # extra iteration.
                self._finish_ready.add(slot)
        if not parts:
            return None
        # The widest remaining budget sets the window (pow2-floored,
        # so the compiled-program set stays {1, 2, 4, ..., window}):
        # rows with less budget freeze mid-window on device instead of
        # dragging every co-tenant down to the tightest budget.
        w = min(self._window, max(cap for _, _, cap in parts))
        if w > 1:
            w = 1 << (w.bit_length() - 1)
        n = self._cache.bucket
        tokens = np.zeros((n,), np.int32)
        mask = np.zeros((n,), bool)
        steps_left = np.zeros((n,), np.int32)
        stop_tokens = np.full((n,), -1, np.int32)
        recs = []
        joins = 0
        for slot, req, cap in parts:
            adv = min(w, cap)
            if first:
                tokens[slot] = req.next_token
            elif req.inflight == 0:
                tokens[slot] = req.next_token
                joins += 1
            else:
                tokens[slot] = -1  # the carry's
            mask[slot] = True
            steps_left[slot] = adv
            stop_tokens[slot] = req.stop_token
            recs.append((slot, req, adv))
        samplers = {slot: req for slot, req, _ in parts
                    if req.sampling is not None}
        if samplers:
            key_data = np.zeros(
                (n,) + self._key_data_shape(samplers), np.uint32
            )
            base_steps = np.zeros((n,), np.int32)
            temps = np.ones((n,), np.float32)
            top_ps = np.ones((n,), np.float32)
            smask = np.zeros((n,), bool)
            for slot, req in samplers.items():
                key_data[slot] = req.key_data
                # Committed position: len(generated)+1 with the
                # unharvested advance folded in, so token t samples
                # with fold_in(seed, t) (decode.generate's schedule)
                # regardless of pipelining.
                base_steps[slot] = (len(req.generated)
                                    + req.inflight + 1)
                temps[slot] = float(req.sampling[1])
                top_ps[slot] = float(req.sampling[2])
                smask[slot] = True
            handle = self._cache.dispatch_window_sampled(
                self._params, tokens, w, mask, key_data, base_steps,
                temps, top_ps, smask, steps_left=steps_left,
                stop_tokens=stop_tokens,
            )
        else:
            handle = self._cache.dispatch_window(
                self._params, tokens, w, active=mask,
                steps_left=steps_left, stop_tokens=stop_tokens,
            )
        t0 = time.perf_counter()
        for _, req, adv in recs:
            req.inflight += adv
            if req.state != "decode":
                # The first window that carries the row: a newcomer's
                # wait since its pick ends on the dispatch's stamp.
                self._to_state(req, "decode", t0)
        self._pipeline_joins += joins
        self._hist_depth.observe(0.0 if first else 1.0)
        return {"window": w, "parts": recs, "handle": handle,
                "depth": 0 if first else 1, "bucket": n, "t0": t0}

    def _await_window_unlocked(self, rec: dict, hold: Hold,
                               waited) -> None:
        """Wait for the device to finish ``rec`` with the work lock
        released (held on entry and on return, by ``hold``, the loop's:
        let go and taken again inside it, as around a
        ``Condition.wait``, so the ledgers stay exact). Prefill
        chunks, admissions and cancels get the lock meanwhile; none
        dispatches or harvests a decode window, and ``_harvesting``
        tells them one is out that ``_inflight`` may not show.
        ``waited`` (``loop/harvest_wait``) ends with the read, and the
        wait to have the lock back is a ``loop/lock_wait`` of its
        own."""
        self._harvesting = rec
        # What this hold did so far, for a reader who finds the lock
        # taken later: the loop may not leave its hold for many
        # windows (``_loop_once``).
        self._publish_locked(self._phase.last)
        hold.release()
        try:
            # Whoever waits for the lock runs first, even if the
            # window is there already (``_loop``'s hand-off).
            time.sleep(0)
            self._cache.await_window(rec["handle"])
        finally:
            waited.stop()
            hold.reacquire(self._phase("loop/lock_wait").start())
            self._harvesting = None

    def _harvest_locked(self, rec: dict, hold: Hold) -> bool:
        """Force an in-flight window's tokens and reconcile (lock
        held): emission, budget finishes, carry of the new pending
        token. Each row's stream truncates at its own dispatch-time
        cap (``adv``) — rows past their cap were frozen on device and
        their produced entries merely repeat the last live token.
        True where the lock was let go and taken back on the way.

        The blocking read is made with the lock released where the
        pool allows it (``_await_window_unlocked``); what the lock
        guards is looked at again after it: a hard close or a
        poisoning that landed meanwhile has failed the waiters, and
        the window is dropped unreconciled for the loop's next
        iteration to exit on; a row cancelled or released meanwhile is
        seen row by row below, as it always was."""
        with self._phase("loop/harvest_wait") as waited:
            if self._unlocked_reads:
                self._await_window_unlocked(rec, hold, waited)
                if self._closed:
                    rec["counted"] = True
                    for _, req, adv in rec["parts"]:
                        req.inflight -= adv
                    return True
            # No wait is left in this where the loop waited above.
            produced = np.asarray(
                self._cache.harvest_window(rec["handle"]))
        # Attribution (rung 25): the forced transfer is where the host
        # actually waits on the device (host clock, never device
        # time) — the RTT minus this is pure host bookkeeping and
        # pipeline slack.
        t_harvest = waited.t1
        rtt_ms = (t_harvest - rec["t0"]) * 1e3
        self._hist_rtt.observe(rtt_ms)
        if self.tracer is not None:
            # Dispatch -> harvest span with the pipeline depth the
            # window was dispatched at (0 = boundary, 1 = overlapped):
            # it spans the loop's phases, so it is no phase itself.
            self.tracer.span(
                "window", "serve", rec["t0"], t_harvest,
                args={"w": rec["window"],
                      "rows": len(rec["parts"]),
                      "depth": rec.get("depth", 0)},
            )
        w = rec["window"]
        with self._phase("loop/emit") as emit:
            rec["counted"] = True
            self._ckpt_clock += 1  # window of progress at risk (rung 22)
            for _, req, adv in rec["parts"]:
                req.inflight -= adv
            self._decode_row_steps += sum(
                adv for _, _, adv in rec["parts"])
            rows = [(slot, req) for slot, req, _ in rec["parts"]
                    if self._active.get(slot) is req]
            self._count_steps_locked(w, rec["bucket"], rows)
            if self._window_pages_total:
                # What the rows' windows have moved past goes back to
                # the window layers' pool now, not when the rows end.
                with self._phase("loop/window_release"):
                    self._cache.release_window_pages(
                        [slot for slot, _ in rows])
            stop_row = produced[w + 1]
            for slot, req, adv in rec["parts"]:
                if self._active.get(slot) is not req or req.stopped:
                    # Released while in flight (hard-close/cancel races
                    # resolve at boundaries, so normally unreachable),
                    # or stop-terminated at an earlier harvest with its
                    # finish deferred — nothing to emit into.
                    continue
                before = len(req.generated)
                # Device-resident finish bookkeeping (rung 23): rows
                # n_steps and n_steps+1 of the harvested block are the
                # packed per-slot finish reason (0 window-capped /
                # 1 budget-frozen / 2 stop) and the 1-based step of the
                # first stop hit — ONE transfer carries tokens and
                # bookkeeping both, and the host never compares
                # per-token.
                stop_at = int(stop_row[slot])
                hit = stop_at > 0
                if req.first_dev is not None:
                    # The row's first window: its first token was the
                    # device's until now (computed before this window
                    # ran, so the read waits for nothing). Had it been
                    # the row's stop token the host would have ended
                    # the request on it with no step at all: it ends
                    # there now, and the steps are discarded.
                    self._first_token_locked(req)
                    if req.next_token == req.stop_token:
                        hit, stop_at = True, 0
                if hit and not req.cancelled:
                    # Emit the pending token plus everything up to AND
                    # INCLUDING the stop token, then finish; steps past
                    # the stop decoded garbage inside the granted cap
                    # and are discarded (the slot releases, so the
                    # device-side over-advance is moot).
                    room = req.n_new - len(req.generated)
                    seq = [req.next_token]
                    seq += produced[:stop_at, slot].tolist()
                    self._emit_many(req, seq[:room])
                    self._note_emitted_locked(req, before)
                    self._finish_stopped_locked(slot, req)
                    continue
                # Bulk emission: one C-level column->list conversion
                # per LIVE row (rows the window advanced — O(changes),
                # idle bucket slots never touched), one extend, no
                # per-token Python frames.
                toks = produced[:adv, slot].tolist()
                self._emit_many(req, [req.next_token] + toks[:-1])
                self._note_emitted_locked(req, before)
                req.next_token = toks[-1]
                if (len(req.generated) + 1 >= req.n_new
                        and not req.cancelled):
                    # Inline finish: with the pipeline saturated the
                    # loop may never visit a boundary, so a filled
                    # budget must complete here. The cancelled guard
                    # keeps cancel-beats-finish — the cancel sweep at
                    # the forced boundary takes it.
                    self._emit_pending_locked(req)
                    self._finish_request_locked(slot, req)
            self._overlap_windows += 1
        if self._autotune is not None:
            # Close the rung-16 loop (rung 26): feed the controller
            # this window's measured split and adopt its pick for the
            # NEXT dispatch. The carry redispatch takes the window as
            # a plain scan length, so mid-pipeline changes are safe —
            # the device carry is one token row, shape-independent of
            # the window.
            self._autotune.observe(
                rtt_ms=rtt_ms, device_ms=waited.ms, host_ms=emit.ms,
                window=w,
            )
            self._window = self._autotune.window()
        return self._unlocked_reads

    def _drain_rec_locked(self, rec: dict | None) -> None:
        """Unwind one in-flight record on the failure path: restore
        the inflight counters and block (deadline-bounded for a slice
        cache; its runner is dead-latched after a failure and returns
        immediately) until the device has retired the window, so
        recovery never tears down state a queued program still
        writes."""
        if rec is None:
            return
        if not rec.get("counted"):
            for _, req, adv in rec["parts"]:
                req.inflight -= adv
        try:
            self._cache.harvest_window(rec["handle"])
        except Exception:
            pass

    def _drain_inflight_locked(self) -> None:
        rec, self._inflight = self._inflight, None
        self._drain_rec_locked(rec)
