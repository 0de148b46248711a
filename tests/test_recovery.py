"""Self-healing serving: the recovery supervisor (SERVING.md rung 15).

PR 1 made failure *detected and bounded* — typed taxonomy, deadline
watchdog, a pool that poisons instead of deadlocking, terminal 503.
This suite pins the recovery half: a poisoning failure now drives the
``healthy -> degraded -> recovering -> healthy`` machine in process —
slice reformation (fresh op stream + barrier SYNC), warm restart
(``revive`` + emergency prefix reload + checkpoint re-restore), backoff
under an attempt budget, and a PVC crash-loop breaker that escalates a
thrashing lineage straight to the old terminal/reschedule path.

The acceptance scenario: a follower outage window ends, the supervisor
re-forms the slice, and the SAME process serves bit-identical tokens
again — no restart, no recompile. Plus the escalation twin where the
follower never returns. All fixed-seed and fast: tier-1.
"""

import json
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from kvedge_tpu.models import TransformerConfig, generate, init_params
from kvedge_tpu.models.kvcache import PagedCacheError
from kvedge_tpu.models.serving import PagedGenerationServer
from kvedge_tpu.runtime import heartbeat
from kvedge_tpu.runtime.failures import (
    OpBudgets,
    PoolPoisoned,
    ServingFailure,
    SliceFollowerLost,
)
from kvedge_tpu.runtime.healthcheck import wait_healthy
from kvedge_tpu.runtime.recovery import (
    HEALTHY,
    RECOVERING,
    TERMINAL,
    RecoveryPolicy,
    RecoverySupervisor,
    sweep_stranded_tmp,
)
from kvedge_tpu.runtime.sliceserve import SlicePagedKVCache
from kvedge_tpu.runtime.status import StatusServer
from kvedge_tpu.testing.servingfaults import (
    FaultPlan,
    FaultyCache,
    FaultySliceTransport,
)

pytestmark = pytest.mark.recovery

CFG = TransformerConfig(
    vocab=128, d_model=32, n_heads=4, n_kv_heads=2, n_layers=2, d_ff=64,
    max_seq=64,
)

# Tight budgets so a wedged op surfaces in seconds, with enough compile
# headroom that a genuine first-trace on CPU never false-positives.
BUDGETS = dict(steady_s=3.0, compile_s=20.0)

# Fast retry discipline for tests: the machine's shape is what matters,
# not production's seconds-scale backoff.
FAST = dict(backoff_base_s=0.1, backoff_cap_s=0.2, jitter=0.0,
            barrier_budget_s=2.0, teardown_budget_s=30.0)


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


@pytest.fixture(scope="module")
def mesh():
    devs = np.array(jax.devices()).reshape(2, 4)
    return Mesh(devs, ("data", "model"))


def reference(params, prompt, n_new):
    out = generate(params, jnp.asarray([prompt], jnp.int32), CFG,
                   n_new=n_new)
    return [int(t) for t in np.asarray(out)[0]]


def _join_dying(thread):
    """The supervisor's _on_degraded runs ON the dying decode thread
    (called from _degrade on its way out), so joining that thread is
    the race-free 'the machine has left healthy' barrier — only then
    is wait_settled guaranteed to observe the transition."""
    thread.join(timeout=30)
    assert not thread.is_alive()


def _warm_slice_server(params, mesh):
    """Slice server with one healthy request already served, so every
    op key holds a compiled program and the STEADY budget — the state a
    long-running pool is in when a follower dies."""
    cache = SlicePagedKVCache(
        CFG, slots=3, pages=24, page_size=4, mesh=mesh,
        op_budgets=OpBudgets(**BUDGETS),
    )
    server = PagedGenerationServer(params, CFG, cache=cache)
    prompt = [3, 1, 4, 1, 5]
    want = reference(params, prompt, 6)
    assert server.submit(prompt, n_new=6) == want
    return cache, server, prompt, want


# ---- the acceptance scenario: outage -> reformation -> same tokens ------


def test_slice_outage_heals_in_process(params, mesh):
    """The full heal loop. A follower drops mid-request (its collectives
    park), the pool poisons with SliceFollowerLost, and the supervisor:
    tears down the dead stream, fails its first reformation barrier (the
    follower is still gone), backs off, re-forms on the second attempt
    once the outage window ends, revives the pool — and the SAME process
    then serves bit-identical tokens. No restart, no recompile.

    Seam math (post-warm): 0-1 admit SYNC passes, 2 prefill header hangs
    (fire_at=2), 3 attempt-1 barrier hangs, 4-5 attempt-2 barrier passes
    (heal_at=4 — the follower rejoined)."""
    cache, server, prompt, want = _warm_slice_server(params, mesh)
    plan = FaultPlan(seed=3, kinds=("hang",), fire_window=(2, 3),
                     heal_at=4)
    FaultySliceTransport(cache, plan)
    sup = RecoverySupervisor(
        server, policy=RecoveryPolicy(max_attempts=3, **FAST), seed=5,
    ).attach()
    dying = server._thread
    try:
        with pytest.raises(ServingFailure):
            server.submit(prompt, n_new=6)
        _join_dying(dying)
        assert sup.wait_settled(timeout=60.0) == HEALTHY
        assert server.degraded is None
        assert server._cache._ops.dead is None
        stats = sup.stats()
        assert stats["recovering"] == 0
        assert stats["recovery_state"] == HEALTHY
        assert stats["recoveries_total"] == 1
        assert stats["recovery_attempts_total"] == 2
        assert stats["recovery_failures_total"] == 0
        assert stats["last_recovery_s"] > 0
        # The healed pool, same process, same compiled programs:
        assert server.submit(prompt, n_new=6) == want
    finally:
        server.close()
        plan.close()


def test_slice_escalates_when_followers_never_return(params, mesh):
    """The escalation twin: the outage window never ends, every
    reformation barrier times out, and after the attempt budget the
    machine lands terminal — exactly the old reschedule contract, now
    with the attempts on the record."""
    cache, server, prompt, _ = _warm_slice_server(params, mesh)
    plan = FaultPlan(seed=3, kinds=("hang",), fire_window=(2, 3),
                     heal_at=10**9)
    FaultySliceTransport(cache, plan)
    sup = RecoverySupervisor(
        server, policy=RecoveryPolicy(max_attempts=2, **FAST), seed=5,
    ).attach()
    dying = server._thread
    try:
        with pytest.raises(ServingFailure):
            server.submit(prompt, n_new=6)
        _join_dying(dying)
        assert sup.wait_settled(timeout=60.0) == TERMINAL
        health = sup.health()
        assert health["terminal"] is True
        assert health["state"] == TERMINAL
        stats = sup.stats()
        assert stats["recoveries_total"] == 0
        assert stats["recovery_attempts_total"] == 2
        assert stats["recovery_failures_total"] == 1
        # The pool stays poisoned and keeps refusing with the typed,
        # retryable error — terminal for the pod, not for the client.
        with pytest.raises(PoolPoisoned):
            server.submit(prompt, n_new=6)
    finally:
        server.close()
        plan.close()


def test_single_host_revive_reloads_prefix_and_params(params, tmp_path):
    """Single-host heal: no reform step (plain cache), but the warm
    restart reloads the emergency prefix dump _degrade() wrote on the
    way down and re-runs the checkpoint restore hook. The prior
    on_degraded observer (the failure-record writer's seat) still fires
    first — attach() chains, it does not replace."""
    path = str(tmp_path / "prefix.npz")
    plan = FaultPlan(seed=1, kinds=("raise",), fire_window=(3, 4))
    cache = FaultyCache(CFG, slots=3, pages=24, page_size=4, plan=plan)
    server = PagedGenerationServer(params, CFG, cache=cache)
    server._persist_path, server._persist_fp = path, "fp-1"
    observed = []
    server.on_degraded = lambda reason, failure: observed.append(reason)
    restores = []

    def restore_params():
        restores.append(1)
        return params

    sup = RecoverySupervisor(
        server, policy=RecoveryPolicy(max_attempts=2, **FAST),
        prefix_path=path, prefix_fingerprint="fp-1",
        restore_params=restore_params, seed=5,
    ).attach()
    prompt = [7, 7, 7, 7, 2, 4, 6, 8, 1]  # 2 full pages -> 2 prefixes
    want = reference(params, prompt, 8)
    dying = server._thread
    try:
        with pytest.raises(ServingFailure):
            server.submit(prompt, n_new=8)
        _join_dying(dying)
        assert sup.wait_settled(timeout=60.0) == HEALTHY
        assert observed, "chained observer must have fired first"
        assert restores == [1]
        assert server.stats()["prefix_entries"] == 2
        # Prefix-sharing path against the reloaded entries, and the
        # tokens still match the contiguous reference exactly:
        assert server.submit(prompt, n_new=8) == want
        assert server.stats()["prefix_hits"] >= 1
    finally:
        server.close()
        plan.close()


def test_revive_installs_the_cast_tree_through_the_server(tmp_path):
    """The re-restore the serve payload wires in
    (workload._restore_serving_params) hands over the tree serve holds,
    cast once at load, not the float32 masters; and the supervisor
    installs it through the server's own method, which keeps
    ``stats()`` telling which tree the programs read."""
    import dataclasses

    from kvedge_tpu.config.runtime_config import RuntimeConfig
    from kvedge_tpu.runtime.workload import (
        _restore_serving_params, train_model_config,
    )

    rcfg = dataclasses.replace(
        RuntimeConfig(), state_dir=str(tmp_path / "state"), train_seq=32)
    tcfg, _ = train_model_config(rcfg)
    assert tcfg.dtype == "bfloat16"

    def restore_params():
        return _restore_serving_params(rcfg, tcfg)[1]

    masters = init_params(jax.random.PRNGKey(0), tcfg)
    plan = FaultPlan(seed=1, kinds=("raise",), fire_window=(3, 4))
    cache = FaultyCache(tcfg, slots=2, pages=16, page_size=4, plan=plan)
    # Handed float32 masters, the server still runs, and says so.
    server = PagedGenerationServer(masters, tcfg, cache=cache)
    assert server.stats()["weights_dtype"] == "float32"
    installed = []
    set_params = server.set_params
    server.set_params = lambda tree: (installed.append(tree),
                                      set_params(tree))
    sup = RecoverySupervisor(
        server, policy=RecoveryPolicy(max_attempts=2, **FAST),
        restore_params=restore_params, seed=5,
    ).attach()
    prompt = [7, 7, 7, 7, 2, 4, 6, 8, 1]
    dying = server._thread
    try:
        with pytest.raises(ServingFailure):
            server.submit(prompt, n_new=8)
        _join_dying(dying)
        assert sup.wait_settled(timeout=60.0) == HEALTHY
        (tree,) = installed
        assert server._params is tree
        assert set(tree) == set(masters)
        for name, leaf in tree.items():
            want = jnp.float32 if name.startswith("ln_") else jnp.bfloat16
            assert leaf.dtype == want, (name, leaf.dtype)
        stats = server.stats()
        assert stats["weights_dtype"] == "bfloat16"
        assert stats["weights_gb"] == pytest.approx(
            sum(leaf.nbytes for leaf in tree.values()) / 1e9)
        # The same rounding, made once: the tokens are the masters'.
        out = generate(masters, jnp.asarray([prompt], jnp.int32), tcfg,
                       n_new=8)
        assert server.submit(prompt, n_new=8) == [
            int(t) for t in np.asarray(out)[0]]
    finally:
        server.close()
        plan.close()


def test_revive_requires_a_poisoned_pool(params):
    cache = FaultyCache(CFG, slots=2, pages=16, page_size=4, plan=None)
    server = PagedGenerationServer(params, CFG, cache=cache)
    try:
        # Healthy pool, loop running: the thread-gone precondition
        # refuses first (two loops over one pool would interleave).
        with pytest.raises(RuntimeError, match="still running"):
            server.revive()
    finally:
        server.close()
    # Cleanly closed (loop gone, nothing poisoned): still not revivable.
    with pytest.raises(RuntimeError, match="not poisoned"):
        server.revive()


# ---- rung 22: boundary checkpoints + resume-after-revive ----------------


def _stream_in_background(server, prompt, n_new):
    """Drive a stream from a daemon thread; returns (got, done, errs).
    No consumer timeout on purpose: a journaled request PARKS across
    poison/revive (rung 22), and the test owns the deadline."""
    got: list[int] = []
    errs: list[Exception] = []
    done = threading.Event()

    def consume():
        try:
            for tok in server.submit_stream(prompt, n_new):
                got.append(tok)
        except Exception as e:
            errs.append(e)
        finally:
            done.set()

    threading.Thread(target=consume, daemon=True).start()
    return got, done, errs


def _wait_degraded(server, timeout_s=30.0):
    deadline = time.monotonic() + timeout_s
    while server.degraded is None:
        assert time.monotonic() < deadline, "pool never poisoned"
        time.sleep(0.01)


def test_single_host_revive_restores_in_flight(params):
    """The rung-22 acceptance scenario, single host: a pool poisoned
    MID-DECODE (two windows already streamed and checkpointed) revives
    with the in-flight request re-admitted from its boundary
    checkpoint, and the stream completes gap-free and bit-identical to
    an uninterrupted run — delivered tokens are never replayed."""
    cache = FaultyCache(CFG, slots=2, pages=16, page_size=4)
    server = PagedGenerationServer(params, CFG, cache=cache, window=2,
                                   checkpoint_every=1,
                                   prefix_cache=False)
    prompt = [3, 1, 4, 1, 5]
    want = reference(params, prompt, 8)
    real = cache.harvest_window
    calls = [0]

    def dying(handle):
        calls[0] += 1
        if calls[0] == 3:  # windows 1+2 harvested -> 2 checkpoints done
            raise RuntimeError("injected: harvest died mid-decode")
        return real(handle)

    dying_thread = server._thread
    try:
        cache.harvest_window = dying
        got, done, errs = _stream_in_background(server, prompt, 8)
        _wait_degraded(server)
        cache.harvest_window = real
        _join_dying(dying_thread)
        # The journaled request is PARKED, not failed: its waiter stays
        # blocked while the checkpoint holds its pages + stream offset.
        assert not done.is_set()
        assert server.stats()["journal_entries"] == 1
        assert server.revive() == 1
        assert done.wait(timeout=60)
        assert not errs, errs
        assert prompt + got == want
        stats = server.stats()
        assert stats["journal_restores_total"] == 1
        assert stats["journal_entries"] == 0
        assert server.degraded is None
    finally:
        server.close()


def test_slice_reformation_restores_in_flight(params, mesh):
    """The slice twin: a follower's broadcast dies mid-decode on a
    checkpointing slice server, the supervisor re-forms the op stream
    and revives — and the journaled request is restored THROUGH the
    re-formed transport (admit + swapin replay on the rejoined
    followers), completing bit-identical in the same process."""
    cache = SlicePagedKVCache(
        CFG, slots=3, pages=24, page_size=4, mesh=mesh,
        op_budgets=OpBudgets(**BUDGETS),
    )
    server = PagedGenerationServer(params, CFG, cache=cache, window=2,
                                   checkpoint_every=1,
                                   prefix_cache=False)
    prompt = [3, 1, 4, 1, 5]
    want = reference(params, prompt, 8)
    # Warm: every op key compiled and on the STEADY budget — the state
    # a long-lived pool fails in (and the seam count below starts AFTER
    # this request, so the fire index is stable).
    assert server.submit(prompt, n_new=8) == want
    plan = FaultPlan(seed=3, kinds=("raise",), fire_window=(8, 9))
    FaultySliceTransport(cache, plan)
    sup = RecoverySupervisor(
        server, policy=RecoveryPolicy(max_attempts=3, **FAST), seed=5,
    ).attach()
    dying = server._thread
    try:
        got, done, errs = _stream_in_background(server, prompt, 8)
        # No _wait_degraded poll here: with coalesced broadcasts (rung
        # 23) the reform+revive completes faster than a 10ms poll tick,
        # so `degraded` can flip back to None between observations. The
        # dying thread's exit is the LATCHING proof the pool poisoned.
        _join_dying(dying)
        assert sup.wait_settled(timeout=60.0) == HEALTHY
        assert done.wait(timeout=60)
        assert not errs, errs
        assert prompt + got == want
        stats = server.stats()
        assert stats["journal_restores_total"] == 1
        assert stats["journal_entries"] == 0
        assert server.degraded is None
    finally:
        server.close()
        plan.close()


def test_revive_restores_prepoison_bucket_without_retrace(params):
    """Satellite of rung 22: a pool poisoned while the capacity bucket
    is stepped UP revives at the pre-poison rung — the journal
    re-admissions need the width, and the compiled programs for it
    survived — so an identical post-revive round triggers ZERO new
    traces. The 2-entry restore is itself the rung proof: admit refuses
    any slot at or above the bucket, so both re-admissions succeeding
    means revive set rung 2 back before touching the cache."""
    from kvedge_tpu.models import kvcache as kvcache_mod

    # page_size 16 >> any request here: every slot holds exactly ONE
    # page, so checkpoint gathers and restore scatters are shape-stable
    # across rounds regardless of where the boundary clock lands.
    cache = FaultyCache(CFG, slots=2, pages=8, page_size=16,
                        min_bucket=1)
    server = PagedGenerationServer(params, CFG, cache=cache, window=2,
                                   checkpoint_every=1,
                                   prefix_cache=False)
    prompts = ([5, 9, 2], [1, 4, 3])
    wants = [reference(params, p, 12) for p in prompts]
    real = cache._device_window_dispatch
    state = {"arm": False}

    def dying(*args):
        # Fire only once BOTH live requests hold a checkpoint (the
        # boundary just crossed checkpointed everything live): the
        # restore is then deterministically 2 entries wide, however
        # the admission interleaving fell this round.
        if state["arm"] and len(server._journal) == 2:
            state["arm"] = False
            raise RuntimeError("injected: died with bucket stepped up")
        return real(*args)

    cache._device_window_dispatch = dying

    def round_trip():
        state["arm"] = True
        dying_thread = server._thread
        drives = [_stream_in_background(server, p, 12)
                  for p in prompts]
        _wait_degraded(server)
        _join_dying(dying_thread)
        assert server.revive() == 2
        for got, done, errs in drives:
            assert done.wait(timeout=60)
            assert not errs, errs
        for (got, _, _), (p, want) in zip(drives, zip(prompts, wants)):
            assert list(p) + got == want

    try:
        # Warm every program shape a round can touch: the solo run
        # compiles rung 1 (and its checkpoint gather), the first
        # poison/revive round compiles rung 2 plus the restore path.
        server.submit(prompts[0], n_new=12)
        round_trip()
        pinned = kvcache_mod.trace_count()
        round_trip()
        assert kvcache_mod.trace_count() == pinned, (
            "revive lost the pre-poison bucket rung: the replay round "
            "recompiled"
        )
    finally:
        server.close()


# ---- crash-loop breaker + the init-events record ------------------------


def test_crash_loop_breaker_escalates_without_attempting(params, tmp_path):
    """A volume that already witnessed repeated failed recoveries vetoes
    in-process healing: the machine goes straight to terminal with ZERO
    attempts, and writes its own escalation strike for the next
    generation to read."""
    state_dir = str(tmp_path)
    for _ in range(3):
        heartbeat.append_init_event(
            state_dir, {"event": "serve-recovery", "outcome": "escalated"}
        )
    plan = FaultPlan(seed=1, kinds=("raise",), fire_window=(1, 2))
    cache = FaultyCache(CFG, slots=2, pages=16, page_size=4, plan=plan)
    server = PagedGenerationServer(params, CFG, cache=cache)
    sup = RecoverySupervisor(
        server, policy=RecoveryPolicy(max_attempts=3, **FAST),
        state_dir=state_dir, seed=5,
    ).attach()
    dying = server._thread
    try:
        with pytest.raises(ServingFailure):
            server.submit([5, 9, 2, 7, 1], n_new=4)
        _join_dying(dying)
        assert sup.wait_settled(timeout=60.0) == TERMINAL
        assert sup.stats()["recovery_attempts_total"] == 0
        assert sup.stats()["recovery_failures_total"] == 1
        events = heartbeat.read_init_events(state_dir)
        assert events[-1]["event"] == "serve-recovery"
        assert events[-1]["outcome"] == "escalated"
        assert "crash-loop" in events[-1]["detail"]
    finally:
        server.close()
        plan.close()


def test_healed_outcomes_are_recorded_but_not_strikes(params, tmp_path):
    """A lineage that heals cleanly never trips the breaker: 'healed'
    outcomes land in init-events.jsonl (the cross-generation record)
    without counting as strikes."""
    state_dir = str(tmp_path)
    for _ in range(5):
        heartbeat.append_init_event(
            state_dir, {"event": "serve-recovery", "outcome": "healed"}
        )
    plan = FaultPlan(seed=1, kinds=("raise",), fire_window=(1, 2))
    cache = FaultyCache(CFG, slots=2, pages=16, page_size=4, plan=plan)
    server = PagedGenerationServer(params, CFG, cache=cache)
    sup = RecoverySupervisor(
        server, policy=RecoveryPolicy(max_attempts=2, **FAST),
        state_dir=state_dir, seed=5,
    ).attach()
    dying = server._thread
    try:
        with pytest.raises(ServingFailure):
            server.submit([5, 9, 2, 7, 1], n_new=4)
        _join_dying(dying)
        assert sup.wait_settled(timeout=60.0) == HEALTHY
        # The 'healed' record lands just after the machine settles;
        # poll briefly rather than racing the worker's last write.
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            events = heartbeat.read_init_events(state_dir)
            if events and events[-1].get("outcome") == "healed":
                break
            time.sleep(0.05)
        assert events[-1]["outcome"] == "healed"
        assert "ts" in events[-1] and "boot_count" in events[-1]
    finally:
        server.close()
        plan.close()


def test_strike_classification():
    is_strike = RecoverySupervisor._is_strike
    assert is_strike({"event": "give-up"})
    assert is_strike({"event": "serve-recovery", "outcome": "failed"})
    assert is_strike({"event": "serve-recovery", "outcome": "escalated"})
    assert not is_strike({"event": "serve-recovery", "outcome": "healed"})
    assert not is_strike({"event": "start", "attempt": 1})
    assert not is_strike("not a dict")


# ---- retry-after: configured knob + measured hint -----------------------


def test_refusal_carries_configured_retry_after(params):
    plan = FaultPlan(seed=1, kinds=("raise",), fire_window=(1, 2))
    cache = FaultyCache(CFG, slots=2, pages=16, page_size=4, plan=plan)
    server = PagedGenerationServer(params, CFG, cache=cache,
                                   retry_after_s=7.5)
    try:
        with pytest.raises(ServingFailure):
            server.submit([5, 9, 2, 7, 1], n_new=4)
        server._thread.join(timeout=30)
        with pytest.raises(PoolPoisoned) as exc_info:
            server.submit([1, 2, 3], n_new=2)
        assert exc_info.value.retry_after_s == 7.5
    finally:
        server.close()
        plan.close()


def test_refusal_prefers_measured_recovery_hint(params):
    """While a recovery is actually running, the supervisor's measured
    hint (last heal's duration minus time already spent) overrides the
    static knob — clients get an honest seconds-scale estimate instead
    of the reschedule-window default."""
    plan = FaultPlan(seed=1, kinds=("raise",), fire_window=(1, 2))
    cache = FaultyCache(CFG, slots=2, pages=16, page_size=4, plan=plan)
    server = PagedGenerationServer(params, CFG, cache=cache,
                                   retry_after_s=30.0)
    try:
        with pytest.raises(ServingFailure):
            server.submit([5, 9, 2, 7, 1], n_new=4)
        server._thread.join(timeout=30)
        # Attach AFTER the poisoning so no recovery auto-starts; put the
        # machine in the recovering state by hand with a known history.
        sup = RecoverySupervisor(server).attach()
        assert sup.retry_after_hint() is None  # at rest: fall back
        sup.state = RECOVERING
        sup._last_recovery_s = 4.0
        sup._recovering_since = time.monotonic()
        with pytest.raises(PoolPoisoned) as exc_info:
            server.submit([1, 2, 3], n_new=2)
        assert 1.0 <= exc_info.value.retry_after_s <= 4.0
    finally:
        server.close()
        plan.close()


# ---- /healthz while recovering: 503 but NOT terminal --------------------


def test_wait_healthy_rides_out_recovering_then_fast_fails_terminal():
    state = {
        "healthy": False,
        "detail": {"reason": "pool poisoned", "terminal": False,
                   "recovering": True, "retry_after_s": 1.0},
    }
    srv = StatusServer(
        "127.0.0.1", 0, snapshot=lambda: {},
        healthy=lambda: state["healthy"],
        health_detail=lambda: state["detail"],
    )
    srv.start()
    url = f"http://127.0.0.1:{srv.port}/healthz"
    try:
        # Recovering: non-terminal 503 -> the probe keeps polling and
        # catches the heal.
        threading.Timer(0.4, state.__setitem__, ("healthy", True)).start()
        ok, _ = wait_healthy(url, deadline_s=15, interval_s=0.1)
        assert ok
        # Escalated: terminal 503 -> fail in seconds, not the deadline.
        state["healthy"] = False
        state["detail"] = {"reason": "pool poisoned", "terminal": True}
        start = time.monotonic()
        ok, detail = wait_healthy(url, deadline_s=60, interval_s=0.1)
        assert not ok
        assert time.monotonic() - start < 10
        assert "terminal" in detail
    finally:
        srv.shutdown()


# ---- slice reformation as a unit ----------------------------------------


def test_reform_replaces_dead_stream(params, mesh):
    cache = SlicePagedKVCache(
        CFG, slots=2, pages=16, page_size=4, mesh=mesh,
        op_budgets=OpBudgets(**BUDGETS),
    )
    wedge = threading.Event()
    try:
        with pytest.raises(SliceFollowerLost):
            cache._ops.run(("wedge",), lambda: wedge.wait(60),
                           budget_s=0.2)
        assert cache._ops.dead is not None
        cache.reform(budget_s=5.0)
        assert cache._ops.dead is None
        assert cache._ops.run(("noop",), lambda: 42, budget_s=5.0) == 42
    finally:
        wedge.set()
        cache.stop()
    with pytest.raises(PagedCacheError, match="stopped"):
        cache.reform()


# ---- satellite: init-events tail reader edge cases ----------------------


def _write_events(path, lines):
    with open(path, "w", encoding="utf-8") as fh:
        fh.write(lines)


def test_read_init_events_skips_truncated_final_line(tmp_path):
    path = tmp_path / heartbeat.INIT_EVENTS_FILE
    _write_events(
        str(path),
        '{"event": "start", "i": 0}\n'
        '{"event": "start", "i": 1}\n'
        '{"event": "sta',  # crash mid-append: no newline, invalid JSON
    )
    events = heartbeat.read_init_events(str(tmp_path))
    assert [e["i"] for e in events] == [0, 1]


def test_read_init_events_bounded_window_cut_mid_record(tmp_path):
    """The reader must stay O(1) on an unbounded crash-loop history:
    only the last 64 KiB are read, the record the window boundary cuts
    in half is skipped (not a parse error), and the tail is the true
    tail. Records are exactly 100 bytes so the cut provably lands
    mid-record (64 KiB is not a multiple of 100)."""
    path = tmp_path / heartbeat.INIT_EVENTS_FILE
    n = 3000  # ~300 KB, ~4.5x the read window
    lines = []
    for i in range(n):
        doc = json.dumps({"event": "start", "i": i, "pad": ""})
        doc = doc[:-2] + "x" * (99 - len(doc)) + '"}'
        assert len(doc) == 99
        lines.append(doc + "\n")
    _write_events(str(path), "".join(lines))
    events = heartbeat.read_init_events(str(tmp_path), tail=10**6)
    # Bounded: nowhere near 3000 records came back, and the head of the
    # file was never decoded.
    assert len(events) <= 64 * 1024 // 100 + 1
    ids = [e["i"] for e in events]
    assert ids[-1] == n - 1
    assert ids[0] > 0
    assert ids == list(range(ids[0], n))  # contiguous true tail
    # Default tail still returns the most recent few, oldest first.
    assert [e["i"] for e in heartbeat.read_init_events(str(tmp_path))] \
        == list(range(n - heartbeat.INIT_EVENTS_TAIL, n))


def test_read_init_events_missing_file(tmp_path):
    assert heartbeat.read_init_events(str(tmp_path)) == []


# ---- satellite: boot-time tmp sweep -------------------------------------


def test_sweep_stranded_tmp_removes_only_top_level_tmp(tmp_path):
    (tmp_path / "prefix-cache.npz.tmp").write_bytes(b"x" * 128)
    (tmp_path / "heartbeat.json.tmp").write_text("{}")
    (tmp_path / "keep.json").write_text("{}")
    sub = tmp_path / "sub.tmp"
    sub.mkdir()
    (sub / "nested.tmp").write_text("x")
    removed = sweep_stranded_tmp(str(tmp_path))
    assert removed == ["heartbeat.json.tmp", "prefix-cache.npz.tmp"]
    assert (tmp_path / "keep.json").exists()
    assert sub.is_dir() and (sub / "nested.tmp").exists()
    assert not (tmp_path / "prefix-cache.npz.tmp").exists()


def test_sweep_stranded_tmp_tolerates_absent_dir(tmp_path):
    assert sweep_stranded_tmp("") == []
    assert sweep_stranded_tmp(str(tmp_path / "never-made")) == []


# ---- satellite: config knobs --------------------------------------------


def test_recovery_config_knobs_round_trip_and_validate():
    from kvedge_tpu.config.runtime_config import (
        RuntimeConfig,
        RuntimeConfigError,
    )

    cfg = RuntimeConfig.parse(
        "[payload]\nserving_retry_after_s = 12.5\n"
        "serving_recovery_attempts = 0\n"
    )
    assert cfg.serving_retry_after_s == 12.5
    assert cfg.serving_recovery_attempts == 0
    assert RuntimeConfig.parse(cfg.to_toml()) == cfg
    default = RuntimeConfig.parse("")
    assert default.serving_retry_after_s == 30.0
    assert default.serving_recovery_attempts == 2
    for bad in ("serving_retry_after_s = 0",
                "serving_retry_after_s = -1.0",
                "serving_recovery_attempts = -1"):
        with pytest.raises(RuntimeConfigError):
            RuntimeConfig.parse(f"[payload]\n{bad}\n")
