"""Seeded fault injection for the SERVING path.

``testing/faults.py`` walks node kills against the fake cluster; this
module is its serving-layer sibling, exercising the failure taxonomy of
``runtime/failures.py`` end to end: faults fire at the *device seams* —
the exact boundaries where a real follower dies, a broadcast stalls, or
a device op raises — while concurrent requests are in flight, and the
harness then asserts the recovery contract the taxonomy promises:

* **Every request terminates** — tokens or a typed error, never a hang.
* **No token is emitted twice** and no stream over-emits its budget.
* **The server lock is never orphaned** (a wedged op must not exit
  holding it).
* **close() stays bounded** and the decode thread is actually gone.
* **Prefix-cache files are never torn** — absent or fully loadable,
  even when a dump is killed mid-write.

Deterministic per seed, same contract as the cluster harness: the plan
draws its fault kind and firing seam from ``random.Random(seed)`` and
records every seam it crosses in ``trace``, so a failing schedule
replays exactly from its seed + trace.
"""

from __future__ import annotations

import dataclasses
import json
import os
import random
import threading
import time

import numpy as np

from kvedge_tpu.models.kvcache import PagedKVCache
from kvedge_tpu.runtime.failures import ServingFailure
from kvedge_tpu.testing.faults import InvariantViolation

__all__ = [
    "FaultPlan",
    "FaultyCache",
    "FaultySliceTransport",
    "InjectedFault",
    "ServingFaultResult",
    "ServingFaultSchedule",
    "prefix_file_intact",
]


class InjectedFault(RuntimeError):
    """The raw (untyped) error a fault injector raises at a seam —
    deliberately NOT a ServingFailure, so runs also prove the
    classification path (classify_failure wraps it as PoolPoisoned)."""


class FaultPlan:
    """A seeded decision of WHAT fails and WHEN.

    The plan counts every seam crossing (device op on a
    :class:`FaultyCache`, broadcast on a
    :class:`FaultySliceTransport`) and fires once, at the drawn index:

    * ``"raise"`` — the seam raises :class:`InjectedFault` (a device op
      failing loudly);
    * ``"hang"`` — the seam parks until :meth:`close` (a dead follower:
      the op never returns, only the deadline watchdog can detect it);
    * ``"delay"`` — the seam sleeps ``delay_s`` then proceeds (a stalled
      broadcast: past-deadline completion must still surface typed).

    A parked seam raises after release rather than completing, so an
    orphaned op thread can never mutate cache state behind a pool that
    already poisoned.

    By default the plan fires ONCE — the original contract, under which
    every seam after ``fire_at`` succeeds. ``heal_at`` switches to
    **outage-window** semantics for recovery schedules: every seam in
    ``[fire_at, heal_at)`` fires (the follower is *gone*, not
    glitching), and seams from ``heal_at`` on succeed (the follower
    rejoined). ``heal_at`` far beyond any reachable seam count models a
    follower that never comes back — the escalation path.
    """

    def __init__(self, seed: int, *, kinds=("raise", "hang", "delay"),
                 fire_window: tuple[int, int] = (1, 12),
                 delay_s: float = 0.0, heal_at: int | None = None):
        rng = random.Random(seed)
        self.kind = rng.choice(list(kinds))
        self.fire_at = rng.randrange(*fire_window)
        self.heal_at = heal_at
        self.delay_s = delay_s
        self.count = 0
        self.fired_on: str | None = None
        self.trace: list[str] = [
            f"[plan] seed={seed} kind={self.kind} fire_at={self.fire_at}"
            + (f" heal_at={heal_at}" if heal_at is not None else "")
        ]
        self._release = threading.Event()
        self._lock = threading.Lock()

    def at_seam(self, label: str) -> None:
        """Called by the injectors at every seam crossing."""
        with self._lock:
            i = self.count
            self.count += 1
            if self.heal_at is None:
                fire = i == self.fire_at and self.fired_on is None
            else:
                fire = self.fire_at <= i < self.heal_at
            if fire and self.fired_on is None:
                self.fired_on = label
            self.trace.append(
                f"[{i}] {label}" + (f" <- {self.kind}" if fire else "")
            )
        if not fire:
            return
        if self.kind == "raise":
            raise InjectedFault(f"injected raise at seam {i} ({label})")
        if self.kind == "hang":
            # Park like a dead follower's collective. The watchdog
            # orphans this thread; the bounded wait below is the
            # harness's own leak guard, not part of the simulation.
            self._release.wait(timeout=120.0)
            raise InjectedFault(
                f"injected hang at seam {i} ({label}) released"
            )
        time.sleep(self.delay_s)

    def close(self) -> None:
        """Release any parked seam (end-of-run cleanup)."""
        self._release.set()


class FaultyCache(PagedKVCache):
    """A paged cache whose device seams consult a :class:`FaultPlan`
    before executing — fault injection at exactly the boundary where a
    real device/transport failure would surface, with the genuine
    kernels running everywhere the plan stays quiet."""

    def __init__(self, *args, plan: FaultPlan | None = None, **kwargs):
        self.plan = plan
        super().__init__(*args, **kwargs)

    def _seam(self, label: str) -> None:
        if self.plan is not None:
            self.plan.at_seam(label)

    def _device_prefill(self, params, tokens, slot: int, offset: int):
        self._seam(f"prefill[{np.asarray(tokens).shape[0]}]")
        return super()._device_prefill(params, tokens, slot, offset)

    def _device_step(self, params, tokens, active):
        self._seam("step")
        return super()._device_step(params, tokens, active)

    # Preemptive-swap seams (models/scheduler.py, SERVING.md rung 17):
    # a swap-out dies with the victim's pages still intact on device
    # (the poison path must not leak its snapshot-to-be); a swap-in
    # dies after the resume reservation was re-booked (revive must
    # return the pool to the idle fixpoint regardless).
    def _device_swapout(self, ids):
        self._seam("swapout")
        return super()._device_swapout(ids)

    def _device_swapin(self, ids, arrays):
        self._seam("swapin")
        return super()._device_swapin(ids, arrays)

    # Decode-window seams (models/serving.py _loop_once): dispatch
    # and harvest are SEPARATE failure boundaries — a dispatch can
    # die while an earlier window is still in flight, and
    # a harvest can die on a window that was dispatched healthy. Both
    # must drain cleanly into the poison path.
    def _device_window_dispatch(self, params, tokens, n_steps: int,
                                active, steps_left, stop_tokens):
        self._seam(f"windowp[{n_steps}]")
        return super()._device_window_dispatch(
            params, tokens, n_steps, active, steps_left, stop_tokens
        )

    def _device_window_sampled_dispatch(self, params, tokens,
                                        n_steps: int, active, key_data,
                                        base_steps, temps, top_ps,
                                        sampled_mask, steps_left,
                                        stop_tokens):
        self._seam(f"wsamplep[{n_steps}]")
        return super()._device_window_sampled_dispatch(
            params, tokens, n_steps, active, key_data, base_steps,
            temps, top_ps, sampled_mask, steps_left, stop_tokens,
        )

    def harvest_window(self, handle):
        self._seam("wharvest")
        return super().harvest_window(handle)


class FaultySliceTransport:
    """Route a ``SlicePagedKVCache``'s broadcasts through a plan.

    Instance-level patch of ``cache._bcast``: the seam fires on the
    DeadlineRunner's op thread (where the real collective would block),
    so a ``"hang"`` plan reproduces the dead-follower wedge exactly —
    the watchdog orphans the op and raises ``SliceFollowerLost``.
    """

    def __init__(self, cache, plan: FaultPlan):
        self._cache = cache
        self._orig = cache._bcast
        self.plan = plan
        cache._bcast = self._bcast

    def _bcast(self, tree):
        self.plan.at_seam("bcast")
        return self._orig(tree)

    def heal(self) -> None:
        """Unhook: restore the cache's real transport. Use with a
        fire-once plan to model 'the follower is back'; plans with
        ``heal_at`` model the rejoin inside the plan itself and don't
        need this."""
        self._cache._bcast = self._orig


def prefix_file_intact(path: str) -> bool:
    """True iff ``path`` is absent or a complete, parseable prefix-cache
    dump — the never-torn invariant (dump writes tmp + os.replace, so a
    kill mid-write may strand a ``.tmp`` but never a torn ``path``)."""
    if not os.path.exists(path):
        return True
    try:
        with np.load(path) as data:
            json.loads(bytes(data["doc"]).decode())
            _ = data["pool_k"].shape, data["pool_v"].shape
    except Exception:
        return False
    return True


@dataclasses.dataclass
class _Submission:
    prompt: list[int]
    n_new: int
    streaming: bool
    tokens: list[int] | None = None
    error: Exception | None = None
    finished: threading.Event = dataclasses.field(
        default_factory=threading.Event
    )


@dataclasses.dataclass
class ServingFaultResult:
    requests: int
    completed: int
    failed: int
    kind: str
    fired_on: str | None
    degraded: str | None
    close_s: float
    trace: list[str]


class ServingFaultSchedule:
    """Drive seeded concurrent traffic into a server wearing a
    :class:`FaultPlan`, then enforce the recovery invariants.

    ``run()`` submits ``n_requests`` (prompts drawn from the seed, a
    seeded mix of blocking and streaming consumers), joins every
    waiter with a hard bound, closes the server, and checks:
    termination, typed errors only, no over-emission, lock health,
    bounded close, decode thread gone. Raises
    :class:`~kvedge_tpu.testing.faults.InvariantViolation` carrying the
    full seam trace on any breach.
    """

    # Errors a request is ALLOWED to terminate with. InjectedFault is
    # legal only on the submit path (a prefill seam raises into the
    # submitting thread before classification); the decode loop always
    # classifies, so waiters see ServingFailure subtypes.
    _TYPED = (ServingFailure,)

    def __init__(self, server, plan: FaultPlan, *, seed: int,
                 join_timeout_s: float = 60.0):
        from kvedge_tpu.models.serving import (
            RequestCancelled,
            ServerBusy,
            ServerClosed,
        )

        self.server = server
        self.plan = plan
        self.rng = random.Random(seed)
        self.join_timeout_s = join_timeout_s
        self.trace = plan.trace
        self._allowed = self._TYPED + (
            ServerBusy, ServerClosed, RequestCancelled, InjectedFault,
        )

    # ---- schedule -------------------------------------------------------

    def run(self, n_requests: int = 3, n_new: int = 6, *,
            vocab: int = 64,
            prompt_len: tuple[int, int] = (2, 8)) -> ServingFaultResult:
        subs = [
            _Submission(
                prompt=[self.rng.randrange(1, vocab)
                        for _ in range(self.rng.randrange(*prompt_len))],
                n_new=n_new,
                streaming=self.rng.random() < 0.5,
            )
            for _ in range(n_requests)
        ]
        threads = []
        for i, sub in enumerate(subs):
            t = threading.Thread(
                target=self._drive, args=(sub,),
                name=f"fault-submit-{i}", daemon=True,
            )
            threads.append(t)
            self.trace.append(
                f"[submit {i}] len={len(sub.prompt)} n_new={sub.n_new} "
                f"{'stream' if sub.streaming else 'block'}"
            )
            t.start()

        for i, sub in enumerate(subs):
            if not sub.finished.wait(timeout=self.join_timeout_s):
                self.plan.close()  # free any parked seam before raising
                self._fail(
                    f"request {i} never terminated within "
                    f"{self.join_timeout_s:g}s — wedged waiter"
                )
        self._check_outcomes(subs)
        self._check_lock("after join")

        start = time.monotonic()
        self.server.close()
        close_s = time.monotonic() - start
        self.plan.close()
        if close_s > self.join_timeout_s:
            self._fail(f"close() took {close_s:.1f}s — unbounded teardown")
        if self.server._thread.is_alive():
            self.server._thread.join(timeout=10)
            if self.server._thread.is_alive():
                self._fail("decode thread still alive after close()")
        self._check_lock("after close")
        for t in threads:
            t.join(timeout=5)

        completed = sum(1 for s in subs if s.error is None)
        self.trace.append(
            f"[done] completed={completed} "
            f"failed={n_requests - completed} close={close_s:.2f}s"
        )
        return ServingFaultResult(
            requests=n_requests, completed=completed,
            failed=n_requests - completed, kind=self.plan.kind,
            fired_on=self.plan.fired_on, degraded=self.server.degraded,
            close_s=close_s, trace=self.trace,
        )

    def _drive(self, sub: _Submission) -> None:
        try:
            if sub.streaming:
                handle = self.server.submit_stream(
                    sub.prompt, sub.n_new, timeout=self.join_timeout_s
                )
                got = [tok for tok in handle]
                sub.tokens = sub.prompt + got
            else:
                sub.tokens = self.server.submit(
                    sub.prompt, sub.n_new, timeout=self.join_timeout_s
                )
        except Exception as e:
            sub.error = e
        finally:
            sub.finished.set()

    # ---- invariants -----------------------------------------------------

    def _fail(self, message: str) -> None:
        raise InvariantViolation(message, self.trace)

    def _check_outcomes(self, subs: list[_Submission]) -> None:
        for i, sub in enumerate(subs):
            if sub.error is not None:
                if not isinstance(sub.error, self._allowed):
                    self._fail(
                        f"request {i} died UNTYPED: "
                        f"{type(sub.error).__name__}: {sub.error}"
                    )
                self.trace.append(
                    f"[outcome {i}] {type(sub.error).__name__}"
                )
                continue
            want = len(sub.prompt) + sub.n_new
            if sub.tokens is None or len(sub.tokens) != want:
                got = None if sub.tokens is None else len(sub.tokens)
                self._fail(
                    f"request {i} over/under-emitted: {got} tokens, "
                    f"budget {want} — double emission or truncation"
                )
            self.trace.append(f"[outcome {i}] ok ({want} tokens)")

    def _check_lock(self, context: str) -> None:
        if not self.server._lock.acquire(timeout=10):
            self._fail(f"server lock orphaned ({context})")
        self.server._lock.release()
