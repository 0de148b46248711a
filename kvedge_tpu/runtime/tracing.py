"""Request-scoped tracing: span timelines, a flight recorder, Perfetto export.

The status surface this repo grew (/status, /metrics, POST /profile) is
all *aggregates* — until now there were no request IDs anywhere in the
codebase, so when an interactive request blew its p99 there was no way
to attribute the time to queue wait vs prefill vs window dispatch vs a
preemptive swap vs a slow slice follower. This module is the missing
attribution layer, in the same spirit as the device-level story
``jax.profiler`` already tells in runtime/profiling.py — but for the
HOST side of serving: the scheduler, the decode loop, the slice op
stream, and the failure/recovery machinery.

Design constraints (SERVING.md rung 18):

* **Lock-cheap.** Spans are recorded from under the server's ONE work
  lock (SERVING.md invariant 5) and from the decode loop's hot path.
  A record is ONE ``deque.append`` of a plain tuple — appends on a
  bounded deque are atomic under the GIL, so the recorder takes no
  lock of its own and never wakes anything. The uncontended-admit
  timing contract (serving.py) is preserved: tracing adds O(1) host
  work and zero notifies.
* **Bounded.** The buffer is a fixed-size ring (the **flight
  recorder**): the newest ``capacity`` events win, the oldest fall
  off. ``dropped`` counts what fell off. On pool poison the last N
  events are embedded in the ``last-failure.json`` post-mortem
  (runtime/workload.py), so a crash ships its own timeline.
* **Monotonic clocks.** Every stamp is ``time.perf_counter()`` —
  wall-clock steps (NTP) cannot reorder a timeline. Export rebases on
  the tracer's epoch so Chrome/Perfetto sees small positive
  microsecond stamps.
* **Deterministic sampling.** The ``serving_trace`` knob is
  off / on / a sample rate in (0, 1]. The sampling decision is a pure
  hash of the request ID, made ONCE at ingress — all spans of one
  request share fate, and a caller-supplied ``X-Request-Id`` yields
  the same decision on every pod. Global (non-request) spans — window
  timing, slice ops, failure/recovery events — always record when the
  tracer is enabled: they are the fabric the sampled request spans
  hang from.
* **Zero effect on tokens.** The tracer never touches device state,
  never sleeps, never raises into the serving path; tracing on vs off
  is token-bit-identical (pinned by tests/test_tracing.py) and the
  tracer object survives ``revive()`` and slice reformation unchanged
  (it holds no device or thread state).

Phases (ISSUE 24) — one primitive, three sinks. A *phase* is a named
stretch of ONE thread's time, entered as a context manager
(:class:`PhaseClock`, one per serving pool). Which sink is on when:

* **always**: its duration and a count go to an accumulator the server
  owns — a histogram where a distribution is wanted, a
  :class:`PhaseSum` (count, total ms) otherwise — which ``stats()``
  exports (``phase_ms`` and the histograms) and the benchmark reads
  with ``serving_trace`` off;
* **while a profiler session is live** (``POST /profile``, the
  benchmark's ``--trace 1``): it is a
  ``jax.profiler.TraceAnnotation("kvedge/<phase>")`` on its host
  thread's line of the SAME ``.xplane.pb`` as the device's operations,
  on the profiler's clock — open the capture in Perfetto/XProf and the
  ``kvedge/...`` rows lie above the device rows. With no session it is
  one flag test;
* **with the Tracer on** (``serving_trace``): it is the span in the
  ring (``rid`` set for request phases, which share the request's
  sampling fate).

The decode-loop thread's phases cover its whole time
(:data:`LOOP_PHASES`): ``loop/lock_wait`` (from wanting the work lock
to holding it, the GIL yield before it included: this is when prefill
chunks run), ``loop/wait_work`` (inside ``Condition.wait``, nothing to
do), ``loop/boundary`` (sweeps, resume/preempt, bucket step,
checkpoint, observe), ``loop/dispatch`` (building and enqueueing a
window), ``loop/harvest_wait`` (the blocking read of a window's
tokens, the work lock released where the pool allows it, and the wait
to have it back then a ``loop/lock_wait`` of its own; the
``window_device_ms`` histogram) and ``loop/emit``
(bookkeeping after it; ``window_host_ms``). The submit path's, per
prefill chunk on the caller's thread (:data:`ADMIT_PHASES`):
``admit/lock_wait`` (asking for the work lock to holding it;
``prefill_lock_wait_ms``) and ``admit/prefill_chunk`` (lock held, the
chunk dispatched and whatever it blocks on; ``prefill_chunk_ms``);
once per request ``admit/first_pick`` (asking for the lock again to
the first token picked from the prefill's logits: dispatched as a
program and left on the device, or, on a server that checkpoints,
read back, the caller waiting, lock held, for its chunks and for the
window the device was given before them).

The work lock's ledger (ISSUE 38). The server's one lock is a
:class:`TimedLock`: it stamps every acquire and release and adds the
difference to one total that knows nothing of names. Every site that
takes the lock does so through a :class:`Hold` that says who it is
(:data:`LOCK_HOLDERS`): its wait goes to ``lock_wait_ms[name]`` and
its hold, a phase ``lock/<name>`` with the three sinks above (so a
capture shows ``kvedge/lock/<name>`` on the holder's line), to
``lock_held_ms[name]``. A hold parked in a ``Condition.wait`` is
paused: it holds nothing; nor does the loop's while it reads a window
back, the lock released and taken again inside the one hold
(:meth:`Hold.release`, :meth:`Hold.reacquire`). What the names leave
of the total is what some site took without saying who it was.

Export targets:

* ``GET /trace`` (runtime/status.py) returns
  :meth:`Tracer.export_chrome` — Chrome trace-event JSON, loadable in
  Perfetto / ``chrome://tracing`` next to the XProf captures.
* ``/metrics`` per-stage histograms (``serve_ttft_ms`` and the
  queue-vs-decode split) are fed by models/serving.py from the same
  span boundaries.
* ``last-failure.json`` embeds :meth:`Tracer.last_events`.
"""

from __future__ import annotations

import collections
import time
import uuid
import zlib

# Record layout (plain tuple — cheap to build under the work lock):
#   (ph, t0, dur, name, cat, rid, args)
# ph is "X" (complete span, dur in seconds) or "i" (instant, dur 0.0).
# rid is "" for global events; args is a small JSON-safe dict or None.

# Flight-recorder tail embedded in the last-failure.json post-mortem.
POSTMORTEM_EVENTS = 64

# Request-id hygiene: caller-supplied X-Request-Id values ride into
# logs, JSON and trace exports; cap length and restrict the alphabet so
# a hostile header cannot smuggle structure anywhere downstream.
_RID_MAX_LEN = 64
_RID_OK = frozenset(
    "abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ0123456789-_.:"
)


def new_request_id() -> str:
    """Mint a request ID at HTTP ingress (workload.py). Random, not
    sequential: IDs must not collide across pods behind one
    LoadBalancer, and must not leak request volume."""
    return "req-" + uuid.uuid4().hex[:16]


def clean_request_id(raw) -> str:
    """A caller-supplied request ID, sanitized; "" when unusable."""
    if not isinstance(raw, str) or not raw:
        return ""
    rid = raw[:_RID_MAX_LEN]
    if all(c in _RID_OK for c in rid):
        return rid
    return ""


class Tracer:
    """A lock-cheap, bounded span recorder (the flight recorder).

    One instance per serving pool, shared by reference with the
    scheduler, the cache (slice op stream) and the recovery machinery.
    All methods are safe to call from any thread without additional
    locking: the only mutation is an append on a bounded deque (atomic
    under the GIL) and a few monotonically-increasing counters whose
    races are benign (observability, not accounting).
    """

    def __init__(self, sample: float = 1.0, capacity: int = 4096):
        self.sample = float(sample)
        self.capacity = int(capacity)
        self._ring: collections.deque = collections.deque(maxlen=capacity)
        self._appended = 0
        self.epoch = time.perf_counter()
        # Counter-track source (SERVING.md rung 25): a callable
        # ``epoch -> [event dict]`` returning fully-formed Chrome
        # counter events (ph="C") to merge into export_chrome — the
        # serving layer hangs its occupancy timeline ring here
        # (runtime/slo.py OccupancyRing.chrome_counters) so Perfetto
        # draws HBM/page/bucket occupancy under the span timeline.
        # None = no counter tracks; export is unchanged.
        self.counter_source = None

    # ---- construction from the config knob -------------------------------

    @staticmethod
    def from_knob(value, capacity: int = 4096) -> "Tracer | None":
        """``serving_trace`` (off / on / rate in (0,1]) -> a tracer or
        None. None is the off state: every call site guards with
        ``if tracer is not None`` so off costs one attribute read."""
        if value in ("off", "", None, False):
            return None
        if value in ("on", True):
            return Tracer(sample=1.0, capacity=capacity)
        rate = float(value)
        if not (0.0 < rate <= 1.0):
            raise ValueError(
                f"serving_trace sample rate must be in (0, 1], got {rate!r}"
            )
        return Tracer(sample=rate, capacity=capacity)

    # ---- recording --------------------------------------------------------

    @staticmethod
    def now() -> float:
        return time.perf_counter()

    def sampled(self, rid: str) -> bool:
        """Deterministic per-request sampling decision: a pure hash of
        the ID, so all spans of one request share fate and a replayed
        ``X-Request-Id`` traces (or not) identically everywhere."""
        if self.sample >= 1.0:
            return True
        bucket = zlib.crc32(rid.encode("utf-8", "replace")) % 10_000
        return bucket < int(self.sample * 10_000)

    def span(self, name: str, cat: str, t0: float, t1: float | None = None,
             rid: str = "", args: dict | None = None) -> None:
        """Record a complete span [t0, t1] (tracer clock)."""
        if t1 is None:
            t1 = time.perf_counter()
        self._ring.append(("X", t0, max(0.0, t1 - t0), name, cat, rid, args))
        self._appended += 1

    def event(self, name: str, cat: str, rid: str = "",
              args: dict | None = None) -> None:
        """Record an instant event at now()."""
        self._ring.append(
            ("i", time.perf_counter(), 0.0, name, cat, rid, args)
        )
        self._appended += 1

    # ---- introspection ----------------------------------------------------

    def __len__(self) -> int:
        return len(self._ring)

    @property
    def dropped(self) -> int:
        """Events that fell off the ring (flight-recorder overwrite)."""
        return max(0, self._appended - len(self._ring))

    def stats(self) -> dict:
        return {
            "trace_events": len(self._ring),
            "trace_events_total": self._appended,
            "trace_dropped_total": self.dropped,
            "trace_sample": self.sample,
        }

    def _snapshot(self) -> list:
        """A consistent copy of the ring. deque iteration can raise
        RuntimeError if a writer appends concurrently; retry a few
        times, then settle for list() (which copies atomically enough
        for observability purposes)."""
        for _ in range(4):
            try:
                return list(self._ring)
            except RuntimeError:
                continue
        return list(self._ring)

    def last_events(self, n: int = POSTMORTEM_EVENTS) -> list[dict]:
        """The newest ``n`` events as JSON-safe dicts, oldest first —
        the post-mortem embed for ``last-failure.json``."""
        out = []
        for ph, t0, dur, name, cat, rid, args in self._snapshot()[-n:]:
            doc = {
                "name": name,
                "cat": cat,
                "t_ms": round((t0 - self.epoch) * 1000.0, 3),
            }
            if ph == "X":
                doc["dur_ms"] = round(dur * 1000.0, 3)
            if rid:
                doc["rid"] = rid
            if args:
                doc["args"] = args
            out.append(doc)
        return out

    # ---- Chrome/Perfetto export -------------------------------------------

    def export_chrome(self) -> dict:
        """The ring as Chrome trace-event JSON (``GET /trace``).

        One process (pid 1), one track (tid) per span category, named
        with ph="M" thread_name metadata so Perfetto labels the rows.
        Timestamps are microseconds from the tracer's epoch (perf
        counter — monotonic, so the timeline cannot fold)."""
        tids: dict[str, int] = {}
        events = []
        for ph, t0, dur, name, cat, rid, args in self._snapshot():
            tid = tids.get(cat)
            if tid is None:
                tid = tids[cat] = len(tids) + 1
            ev = {
                "name": name,
                "cat": cat,
                "ph": ph,
                "ts": round((t0 - self.epoch) * 1e6, 1),
                "pid": 1,
                "tid": tid,
            }
            if ph == "X":
                ev["dur"] = round(dur * 1e6, 1)
            else:
                ev["s"] = "t"  # instant scope: thread
            a = dict(args) if args else {}
            if rid:
                a["rid"] = rid
            if a:
                ev["args"] = a
            events.append(ev)
        if self.counter_source is not None:
            # Occupancy counter tracks (ph="C", rung 25). Best-effort:
            # a broken source must never take /trace down with it.
            try:
                events.extend(self.counter_source(self.epoch) or [])
            except Exception:
                pass
        meta = [
            {
                "name": "thread_name",
                "ph": "M",
                "pid": 1,
                "tid": tid,
                "args": {"name": cat},
            }
            for cat, tid in sorted(tids.items(), key=lambda kv: kv[1])
        ]
        return {
            "traceEvents": meta + events,
            "displayTimeUnit": "ms",
            "otherData": {
                "recorder": "kvedge-tpu flight recorder",
                "dropped": self.dropped,
                "sample": self.sample,
            },
        }


# ---- phases: one primitive, three sinks ----------------------------------

# The decode-loop thread's phases: together they cover its whole time.
LOOP_PHASES = ("loop/lock_wait", "loop/wait_work", "loop/boundary",
               "loop/dispatch", "loop/harvest_wait", "loop/emit")
# The submit path's, on the caller's thread: the first two once per
# prefill chunk, the third once per request.
ADMIT_PHASES = ("admit/lock_wait", "admit/prefill_chunk",
                "admit/first_pick")


class PhaseSum:
    """Count and total milliseconds of a phase whose distribution
    nobody asks for. Same ``observe`` / ``n`` / ``total`` shape as the
    serving histograms, so a phase takes either as its accumulator.
    Mutated by the one thread that owns the phase, or under the work
    lock; a reader sees the value before or after an addition."""

    __slots__ = ("n", "total")

    def __init__(self):
        self.n = 0
        self.total = 0.0

    def observe(self, ms: float) -> None:
        self.n += 1
        self.total += ms


class Phase:
    """One entry into a phase (see the module docstring for the three
    sinks). ``stop()`` ends it before the ``with`` block does — the
    lock-wait phases end the moment the lock is held, inside the block
    that holds it. ``t0``/``t1``/``ms`` stay readable after.

    A phase of a *chain* (``chain``: the clock whose ``last`` stamp it
    shares with the other phases of its thread) starts where the one
    before it ended, so the bytecode between two phases is counted in
    the second and the chain's phases add up to the thread's time with
    nothing left over. The profiler annotation begins and ends in real
    time either way: it is made on entry, because making one starts it.

    A phase may be made well before it is entered, and entered with
    :meth:`start` where no ``with`` block fits. The waits for the work
    lock use both: whoever asks first after a release gets the lock
    (the release only wakes the other side), so the thread that will
    wait makes its phase while it still holds the lock, and the submit
    path also starts it there, a moment before the release: between
    its release and its next acquire it then runs what it ran before
    phases, and ``stop()`` ends the wait once the lock is held."""

    __slots__ = ("name", "args", "t0", "t1", "ms", "_sink", "_annotate",
                 "_note", "_ring", "_rid", "_chain")

    def __init__(self, name: str, sink, annotate, ring, rid: str, args,
                 chain):
        self.name = name
        self.args = args
        self.t0 = self.t1 = None
        self.ms = 0.0
        self._sink = sink
        self._annotate = annotate
        self._note = None
        self._ring = ring
        self._rid = rid
        self._chain = chain

    def start(self) -> "Phase":
        self._note = self._annotate("kvedge/" + self.name)
        chain = self._chain
        if chain is None:
            self.t0 = time.perf_counter()
        else:
            self.t0 = chain.last
            chain.open = self.name
        return self

    __enter__ = start

    def stop(self, t1: float | None = None) -> None:
        """``t1``: a stamp another primitive took at this same
        boundary (the lock's own, of the acquire that ends a wait), so
        one boundary has one stamp."""
        if self.t1 is not None:
            return
        self.t1 = t1 = time.perf_counter() if t1 is None else t1
        if self._chain is not None:
            self._chain.last = t1
            self._chain.open = None
        self._note.__exit__(None, None, None)
        self.ms = (t1 - self.t0) * 1e3
        self._sink.observe(self.ms)
        if self._ring is not None:
            self._ring.span(self.name, "serve", self.t0, t1,
                            rid=self._rid, args=self.args)

    def __exit__(self, *exc) -> bool:
        self.stop()
        return False


class PhaseClock:
    """The phase factory of one serving pool: the accumulator of every
    phase it may enter (``sinks``: name -> a :class:`PhaseSum` or a
    histogram, anything with ``observe``/``n``/``total``), the tracer
    (or None) and the profiler's annotation type. The phases named in
    ``chained`` are one thread's and form a chain (:class:`Phase`);
    that thread calls :meth:`mark` as it starts. ``last`` is where the
    chain's last phase ended and ``open`` the name of the one under
    way, if any."""

    def __init__(self, sinks: dict, tracer: "Tracer | None" = None,
                 chained: tuple = ()):
        # Imported here, not at the top: this module stays importable
        # (request ids, the ring) by code that never loads JAX.
        from jax.profiler import TraceAnnotation

        self._annotate = TraceAnnotation
        self.sinks = sinks
        self.tracer = tracer
        self._chained = frozenset(chained)
        self.last = time.perf_counter()
        self.open = None

    def mark(self) -> float:
        """Start the chain now; returns the stamp."""
        self.last = time.perf_counter()
        return self.last

    def __call__(self, name: str, *, rid: str = "", ring: bool = True,
                 args: dict | None = None, chain=None) -> Phase:
        """``ring=False`` keeps an unsampled request's phase out of the
        ring (its other two sinks stay on). ``chain``: a chain other
        than the clock's own (a :class:`Hold`'s)."""
        if chain is None and name in self._chained:
            chain = self
        return Phase(name, self.sinks[name], self._annotate,
                     self.tracer if ring else None, rid, args, chain)

    def snapshot(self, now: float) -> dict:
        """name -> [count, total ms], for ``stats()``. The chain's
        phase under way at ``now`` is counted as far as it has got (a
        snapshot is taken with the work lock held, so what is open is
        the loop's wait for that lock or for work, seconds long): the
        chain's totals then differ between two snapshots by the time
        between them, whatever was open at either."""
        out = {name: [acc.n, acc.total]
               for name, acc in self.sinks.items()}
        name = self.open
        if name is not None:
            out[name][1] += max(0.0, now - self.last) * 1e3
        return out


# ---- the work lock's ledger ----------------------------------------------

# Who may hold the work lock: a site that takes it names itself one of
# these (a :class:`Hold`), and any other name is a KeyError.
LOCK_HOLDERS = ("loop", "admit/start", "admit/prefill_chunk",
                "admit/first_pick", "cancel", "stats", "control")
# What a request is doing, from submit to its last token: every
# millisecond of its life belongs to one of these (``request_ms``).
# ``first_write`` is the handler's, beside them: the first token's put
# on the stream to the flush of its line.
REQUEST_STATES = ("queued", "admit", "prefill_wait", "prefill", "pick",
                  "join_wait", "decode", "swapped")


class TimedLock:
    """A lock that keeps its own account: ``perf_counter()`` at every
    acquire and release, the difference added to ``held_ms_total``. It
    wraps the lock it is given (``threading.Lock``, or
    :class:`~kvedge_tpu.runtime.debuglock.DebugLock`, whose ownership
    probes pass through), and a ``Condition`` made on it releases and
    re-acquires through it, so a thread parked in ``wait`` holds
    nothing. The stamps are written with the lock held."""

    def __init__(self, inner):
        self.inner = inner
        self.t_acquired = 0.0
        self.held_ms_total = 0.0
        for probe in ("_is_owned", "assert_held"):
            if hasattr(inner, probe):
                setattr(self, probe, getattr(inner, probe))

    def acquire(self, blocking: bool = True, timeout: float = -1) -> bool:
        got = self.inner.acquire(blocking, timeout)
        if got:
            self.t_acquired = time.perf_counter()
        return got

    def release(self, now: float | None = None) -> None:
        """``now``: the holder's own stamp of this boundary, where it
        took one. Counted before the release: whoever asks first after
        it gets the lock, and nothing new stands between the two."""
        if now is None:
            now = time.perf_counter()
        self.held_ms_total += (now - self.t_acquired) * 1e3
        self.inner.release()

    __enter__ = acquire

    def __exit__(self, *exc) -> None:
        self.release()

    def locked(self) -> bool:
        return self.inner.locked()


class Hold:
    """One named hold of the work lock, entered where ``with lock:``
    stood. It is a chain of ``lock/<name>`` phases (it is their
    ``chain``: ``last``, ``open``) from the lock's own acquire stamp
    to the stamp the lock counts its release by: :meth:`pause` and
    :meth:`resume` stand around a ``Condition.wait`` inside it, and
    :meth:`switch` hands the rest of the hold to another name. The
    wait for the lock runs from the start of ``waited``, a phase the
    thread started when it asked (a hold may be made ahead, like its
    wait: nothing is built between a release and the next acquire),
    and from now without one. Where that phase is the wait and
    nothing more (``ends_wait``) it is stopped here, on the acquire's
    stamp, and is the record; otherwise the wait goes to
    ``lock_wait_ms[name]``."""

    __slots__ = ("name", "waited", "t0", "last", "open", "_ledger",
                 "_phase", "_ends_wait", "_rid", "_ring")

    def __init__(self, ledger: "LockLedger", name: str, waited,
                 ends_wait: bool, rid: str, ring: bool):
        ledger.held["lock/" + name]  # an unknown holder is an error
        self.name = name
        self.waited = waited
        self.t0 = self.last = 0.0
        self.open = None
        self._ledger = ledger
        self._phase = None
        self._ends_wait = ends_wait
        self._rid = rid
        self._ring = ring

    def acquire(self, timeout: float = -1) -> bool:
        """``timeout``: seconds to wait at most (False: not had, and
        nothing recorded); -1 waits for as long as it takes."""
        ledger = self._ledger
        waited = self.waited
        asked = time.perf_counter() if waited is None else waited.t0
        if not ledger.lock.acquire(True, timeout):
            return False
        self.t0 = self.last = t = ledger.lock.t_acquired
        if waited is not None and self._ends_wait:
            waited.stop(t)
        else:
            ledger.wait[self.name].observe((t - asked) * 1e3)
        self._begin()
        return True

    def _begin(self) -> None:
        self._ledger.current = self
        self._phase = self._ledger.clock(
            "lock/" + self.name, rid=self._rid, ring=self._ring,
            chain=self).start()

    def pause(self) -> None:
        """Before a ``Condition.wait`` (which releases the lock)."""
        self._phase.stop()
        self._ledger.current = None

    def resume(self) -> None:
        """After it: held again since the lock's own stamp."""
        self.last = self._ledger.lock.t_acquired
        self._begin()

    def switch(self, name: str) -> float:
        """The hold goes on under another name; returns the stamp of
        the boundary."""
        self._ledger.held["lock/" + name]
        self._phase.stop()
        self.name = name
        self._begin()
        return self.last

    def release(self) -> None:
        # One stamp for the hold's end and the lock's release: between
        # two stamps a thread can lose the interpreter for 5 ms.
        self._phase.stop()
        self._ledger.current = None
        self._ledger.lock.release(self.last)

    def reacquire(self, waited: "Phase") -> None:
        """The hold goes on after a :meth:`release` inside the block
        it was entered for: the holder let the lock go for a wait that
        needs nothing of what it guards (the loop's read of a window),
        and is the same holder again from the lock's own stamp.
        ``waited``, a phase the caller started when it asked, is the
        wait for the lock and its record, and ends on that stamp."""
        self._ledger.lock.acquire()
        self.last = t = self._ledger.lock.t_acquired
        waited.stop(t)
        self._begin()

    def __enter__(self) -> "Hold":
        self.acquire()
        return self

    def __exit__(self, *exc) -> bool:
        self.release()
        return False


class LockLedger:
    """The work lock's account by holder: the :class:`TimedLock`, the
    accumulators of every holder's waits and holds, and the hold under
    way (``current``, written by the thread that holds the lock).
    ``waits`` gives the holders whose wait an accumulator of the
    server's already measures (the loop's ``loop/lock_wait``, a
    chunk's ``admit/lock_wait``): one record, two names."""

    def __init__(self, lock: TimedLock, tracer: "Tracer | None" = None,
                 waits: dict | None = None):
        self.lock = lock
        self.held = {"lock/" + name: PhaseSum() for name in LOCK_HOLDERS}
        self.wait = {name: PhaseSum() for name in LOCK_HOLDERS}
        self.wait.update(waits or {})
        self.clock = PhaseClock(self.held, tracer)
        self.current: Hold | None = None

    def hold(self, name: str, *, waited: Phase | None = None,
             ends_wait: bool = True, rid: str = "",
             ring: bool = False) -> Hold:
        return Hold(self, name, waited, ends_wait, rid, ring)

    def snapshot(self, now: float) -> dict:
        """``lock_held_ms_total``, ``lock_held_ms`` and
        ``lock_wait_ms`` (name -> [count, total ms]) for ``stats()``,
        taken with the lock held: the hold under way is counted as far
        as it has got, in its name and in the total alike, so both
        gain the same between two snapshots."""
        held = {name[len("lock/"):]: [acc.n, acc.total]
                for name, acc in self.held.items()}
        total = self.lock.held_ms_total
        hold = self.current
        if hold is not None and hold.open is not None:
            held[hold.name][1] += max(0.0, now - hold.last) * 1e3
            total += max(0.0, now - self.lock.t_acquired) * 1e3
        return {
            "lock_held_ms_total": total,
            "lock_held_ms": held,
            "lock_wait_ms": {name: [acc.n, acc.total]
                             for name, acc in self.wait.items()},
        }
