"""SLO-aware admission scheduler (models/scheduler.py, SERVING.md rung 17).

The pinned contract: priority admission is ordered and fair (ticketed
FIFO within a class — the notify_all ordering race is gone), preemptive
KV swap-to-host is EXACT (a preempted-and-resumed request's tokens are
bit-identical to a never-preempted run — greedy and sampled, with and
without a shared prefix, overlap on and off), overload shedding rejects
early with a measured hint, and no scheduling path — including cancel
while parked, cancel while swapped out, and a fault-injected swap
failure through poison and revive — leaks a slot, a page reservation,
or a host snapshot.

All fixed-seed and fast: these run in the tier-1 gate.
"""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kvedge_tpu.models import TransformerConfig, generate, init_params
from kvedge_tpu.models.scheduler import AdmissionScheduler
from kvedge_tpu.models.serving import (
    PagedGenerationServer,
    RequestCancelled,
    ServerBusy,
    ServerOverloaded,
)
from kvedge_tpu.runtime.failures import PoolPoisoned, ServingFailure
from kvedge_tpu.testing.servingfaults import FaultyCache, InjectedFault

pytestmark = pytest.mark.sched

CFG = TransformerConfig(
    vocab=128, d_model=32, n_heads=4, n_kv_heads=2, n_layers=2, d_ff=64,
    max_seq=64,
)


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


@pytest.fixture(scope="module")
def ref_server(params):
    """A plain, never-contended server: the sampled-decode reference
    (contiguous generate covers greedy, but sampled streams are pinned
    paged-vs-paged, same discipline as test_serving)."""
    server = PagedGenerationServer(params, CFG, slots=2, pages=24,
                                   page_size=4, prefix_cache=False)
    yield server
    server.close()


def reference(params, prompt, n_new):
    out = generate(params, jnp.asarray([prompt], jnp.int32), CFG,
                   n_new=n_new)
    return [int(t) for t in np.asarray(out)[0]]


def sched_server(params, **kw):
    """slots=1 forces every pair of requests into contention — the
    deterministic preemption recipe."""
    kw.setdefault("slots", 1)
    kw.setdefault("pages", 16)
    kw.setdefault("page_size", 4)
    kw.setdefault("window", 4)
    kw.setdefault("sched_policy", "strict")
    kw.setdefault("sched_swap_budget_mb", 64)
    return PagedGenerationServer(params, CFG, **kw)


def wait_for(predicate, timeout=60.0, what="condition"):
    deadline = time.monotonic() + timeout
    while not predicate():
        if time.monotonic() > deadline:
            raise AssertionError(f"timed out waiting for {what}")
        time.sleep(0.002)


def parked_depth(server):
    with server._lock:
        return server._sched.depth_locked()


def assert_idle_fixpoint(server, pages):
    """Nothing leaked: no reservation, no snapshot, and every page is
    free or pinned by the prefix registry (a finished request registers
    its whole committed stream, so an idle pool keeps those pages by
    design; each is counted once however many entries share it)."""
    stats = server.stats()
    assert stats["in_flight"] == 0
    assert stats["reserved_pages"] == 0
    with server._lock:
        pinned = {page for entry in server._prefix_entry_nodes.values()
                  for page in entry["pages"]}
    assert stats["free_pages"] + len(pinned) == pages
    assert stats["sched_swapped_out"] == 0
    assert stats["sched_swap_bytes_host"] == 0
    with server._lock:
        assert server._sched.depth_locked() == 0


# ---- exactness under preemption (the tentpole contract) ------------------


@pytest.mark.parametrize("window", [1, 4], ids=["w1", "w4"])
@pytest.mark.parametrize("sampled", [False, True])
@pytest.mark.parametrize("shared_prefix", [False, True])
def test_preempt_resume_bit_identical(params, ref_server, window,
                                      sampled, shared_prefix):
    """A batch stream preempted for an interactive request (KV swapped
    to host, slot released, later swapped back in) must produce EXACTLY
    the tokens of a never-preempted decode — the whole matrix: greedy
    and sampled, with and without a shared prefix under the victim,
    at one-step windows (a trip a token) and at longer ones."""
    server = sched_server(params, window=window)
    base = [1, 2, 3, 4, 5, 6, 7, 8]  # two full 4-token pages
    victim_prompt = (base + [2]) if shared_prefix else [9, 8, 7]
    v_key = jax.random.PRNGKey(11)
    i_key = jax.random.PRNGKey(23)
    v_sampling = ((v_key, jnp.float32(0.8), jnp.float32(0.9))
                  if sampled else None)
    i_sampling = ((i_key, jnp.float32(0.7), jnp.float32(0.95))
                  if sampled else None)
    try:
        if shared_prefix:
            # Register base's pages so the victim admits via a prefix
            # hit — its swapped pages then started life as shared pins.
            server.submit(base + [1], n_new=2)
        victim = server.submit_stream(victim_prompt, n_new=40,
                                      sampling=v_sampling,
                                      priority="batch")
        first = next(victim)
        # The interactive submit parks (slots=1), the decode loop swaps
        # the batch victim out at the next boundary, and this returns
        # the interactive result while the victim waits in host RAM.
        got_i = server.submit([40, 41, 42], n_new=6,
                              sampling=i_sampling)
        got_v = victim_prompt + [first] + list(victim)

        stats = server.stats()
        assert stats["sched_preemptions_total"] >= 1
        assert stats["sched_resumes_total"] >= 1

        if sampled:
            want_v = ref_server.submit(victim_prompt, n_new=40,
                                       sampling=v_sampling)
            want_i = ref_server.submit([40, 41, 42], n_new=6,
                                       sampling=i_sampling)
        else:
            want_v = reference(params, victim_prompt, 40)
            want_i = reference(params, [40, 41, 42], 6)
        assert got_i == want_i
        assert got_v == want_v, "resumed stream diverged"
        assert server.stats()["sched_swap_bytes_host"] == 0
    finally:
        server.close()


def test_preempt_resume_quantized_kv_is_exact(params):
    """int8 KV pages swap AS STORED — quantized values AND the fp32
    scale slabs move verbatim, so no dequant/requant error enters a
    preempted request's stream: its tokens match an int8 server that
    was never preempted."""
    server = sched_server(params, kv_dtype="int8")
    ref = PagedGenerationServer(params, CFG, slots=2, pages=24,
                                page_size=4, prefix_cache=False,
                                kv_dtype="int8")
    try:
        victim = server.submit_stream([9, 8, 7], n_new=40,
                                      priority="batch")
        first = next(victim)
        got_i = server.submit([40, 41, 42], n_new=6)
        got_v = [9, 8, 7] + [first] + list(victim)
        assert server.stats()["sched_preemptions_total"] >= 1
        assert got_v == ref.submit([9, 8, 7], n_new=40)
        assert got_i == ref.submit([40, 41, 42], n_new=6)
    finally:
        ref.close()
        server.close()


def test_preempt_resume_on_slice_cache_is_exact(params):
    """The swap ops cross the slice wire protocol (OP_SWAPOUT gathers
    the model-sharded pool replicated to the leader, OP_SWAPIN
    scatters it back): a preempted request on a slice cache resumes
    bit-identically too."""
    from jax.sharding import Mesh
    from kvedge_tpu.runtime.sliceserve import SlicePagedKVCache

    devs = np.array(jax.devices()).reshape(2, 4)
    mesh = Mesh(devs, ("data", "model"))
    cache = SlicePagedKVCache(CFG, slots=1, pages=16, page_size=4,
                              mesh=mesh)
    server = PagedGenerationServer(params, CFG, cache=cache, window=4,
                                   sched_policy="strict",
                                   sched_swap_budget_mb=64)
    try:
        victim = server.submit_stream([9, 8, 7], n_new=40,
                                      priority="batch")
        first = next(victim)
        got_i = server.submit([40, 41, 42], n_new=6)
        got_v = [9, 8, 7] + [first] + list(victim)
        assert server.stats()["sched_preemptions_total"] >= 1
        assert server.stats()["sched_resumes_total"] >= 1
        assert got_v == reference(params, [9, 8, 7], 40)
        assert got_i == reference(params, [40, 41, 42], 6)
    finally:
        server.close()


# ---- fairness: ticketed same-class ordering (satellite 1) ----------------


def gate_the_loop(server) -> threading.Event:
    """While the returned event is set the decode loop stands still
    where it has released the lock, between two trips or, with a
    window in flight (it then goes round inside one trip), in its wait
    for that window: an occupier cannot finish and hand its slot on
    before every waiter of a test has parked, however slowly a loaded
    machine runs the waiters' threads."""
    held = threading.Event()

    def gated(step):
        def stand_then(*args):
            while held.is_set():
                time.sleep(0.001)
            return step(*args)
        return stand_then

    server._loop_once = gated(server._loop_once)
    server._cache.await_window = gated(server._cache.await_window)
    return held


def test_same_class_waiters_admit_in_arrival_order(params):
    """Two same-class waiters must admit in ARRIVAL order. Under the
    old Condition.notify_all herd, admission order was whatever the
    lock handed out; the ticketed queue makes it the queue's order.
    The assertion reads each request's admit_seq (assigned under the
    lock at admission) rather than thread completion order, which a
    loaded machine can invert by starving the earlier waiter's thread
    after its decode already finished."""
    server = sched_server(params, sched_swap_budget_mb=0)
    seqs = {}
    held = gate_the_loop(server)
    try:
        occ = server.submit_stream([7, 7, 7], n_new=30)
        next(occ)
        held.set()

        def worker(tag, prompt):
            h = server.submit_stream(prompt, n_new=2)
            list(h)
            seqs[tag] = h._req.admit_seq

        a = threading.Thread(target=worker, args=("A", [1, 2]))
        a.start()
        wait_for(lambda: parked_depth(server) == 1, what="A parked")
        b = threading.Thread(target=worker, args=("B", [3, 4]))
        b.start()
        wait_for(lambda: parked_depth(server) == 2, what="B parked")
        occ.cancel()
        held.clear()
        a.join(timeout=120)
        b.join(timeout=120)
        assert not a.is_alive() and not b.is_alive()
        assert seqs["A"] < seqs["B"]
    finally:
        held.clear()
        server.close()


def test_strict_policy_admits_interactive_before_earlier_batch(params):
    """Across classes the strict policy inverts arrival order: an
    interactive request that arrives AFTER a parked batch request
    admits first (no preemption needed — just the queue head).
    Asserted on admit_seq, not thread completion order (see
    test_same_class_waiters_admit_in_arrival_order)."""
    server = sched_server(params, sched_swap_budget_mb=0)
    seqs = {}
    # The occupier cannot finish and hand the slot to the batch
    # request before the interactive one has queued.
    held = gate_the_loop(server)
    try:
        occ = server.submit_stream([7, 7, 7], n_new=30)
        next(occ)
        held.set()

        def worker(tag, prompt, priority):
            h = server.submit_stream(prompt, n_new=2,
                                     priority=priority)
            list(h)
            seqs[tag] = h._req.admit_seq

        b = threading.Thread(target=worker,
                             args=("batch", [1, 2], "batch"))
        b.start()
        wait_for(lambda: parked_depth(server) == 1, what="batch parked")
        i = threading.Thread(target=worker,
                             args=("interactive", [3, 4], "interactive"))
        i.start()
        wait_for(lambda: parked_depth(server) == 2,
                 what="interactive parked")
        occ.cancel()
        held.clear()
        b.join(timeout=120)
        i.join(timeout=120)
        assert seqs["interactive"] < seqs["batch"]
    finally:
        held.clear()
        server.close()


# ---- cancel while parked / while swapped out (satellite 3) ---------------


def test_cancel_while_parked_leaks_nothing(params):
    server = sched_server(params, sched_swap_budget_mb=0)
    errors = []
    # The occupier (30 tokens) must still be there when it is counted.
    held = gate_the_loop(server)
    try:
        occ = server.submit_stream([7, 7], n_new=30)
        next(occ)
        held.set()

        def worker():
            try:
                server.submit([1, 2, 3], n_new=4)
            except Exception as e:
                errors.append(e)

        t = threading.Thread(target=worker)
        t.start()
        wait_for(lambda: parked_depth(server) == 1, what="parked ticket")
        with server._lock:
            parked_req = server._sched.head_locked().req
        server.cancel(parked_req)
        t.join(timeout=60)
        assert not t.is_alive()
        assert len(errors) == 1
        assert isinstance(errors[0], RequestCancelled)
        # The ticket is gone and the occupier is untouched.
        assert parked_depth(server) == 0
        assert server.stats()["in_flight"] == 1
        occ.cancel()
        held.clear()
        with pytest.raises(RequestCancelled):
            list(occ)
        wait_for(lambda: server.stats()["in_flight"] == 0,
                 what="occupier release")
        assert_idle_fixpoint(server, pages=16)
    finally:
        held.clear()
        server.close()


def test_cancel_while_swapped_out_frees_host_snapshot(params):
    server = sched_server(params)
    result = {}
    try:
        victim = server.submit_stream([9, 8, 7], n_new=40,
                                      priority="batch")
        next(victim)
        t = threading.Thread(
            target=lambda: result.setdefault(
                "i", server.submit([1, 2], n_new=50)
            )
        )
        t.start()
        wait_for(lambda: server.stats()["sched_swapped_out"] == 1,
                 what="victim swapped out")
        assert server.stats()["sched_swap_bytes_host"] > 0
        victim.cancel()
        with pytest.raises(RequestCancelled, match="swapped out"):
            list(victim)
        stats = server.stats()
        assert stats["sched_swapped_out"] == 0
        assert stats["sched_swap_bytes_host"] == 0
        assert stats["sched_preemptions_total"] == 1
        assert stats["sched_resumes_total"] == 0
        t.join(timeout=120)
        assert result["i"] == reference(params, [1, 2], 50)
        assert_idle_fixpoint(server, pages=16)
    finally:
        server.close()


# ---- overload shedding (tentpole pillar 3 + satellite 2) -----------------


def test_depth_watermark_sheds_with_queue_depth_and_hint(params):
    server = sched_server(params, sched_swap_budget_mb=0,
                          sched_max_queue_depth=1)
    try:
        occ = server.submit_stream([5, 5], n_new=30)
        next(occ)
        t = threading.Thread(
            target=lambda: server.submit([1, 2], n_new=2)
        )
        t.start()
        wait_for(lambda: parked_depth(server) == 1, what="parked ticket")
        with pytest.raises(ServerOverloaded) as exc_info:
            server.submit([9], n_new=2)
        msg = str(exc_info.value)
        assert "shed" in msg
        assert "queue depth [interactive=1, batch=0]" in msg
        # ServerOverloaded IS a ServerBusy: the HTTP layer's retriable
        # mapping (503 + retry hint) applies unchanged.
        assert isinstance(exc_info.value, ServerBusy)
        assert server.stats()["sched_shed_total"] == 1
        occ.cancel()
        t.join(timeout=120)
        assert not t.is_alive()
    finally:
        server.close()


class _SlowWindows:
    """Duck-typed FaultPlan: stretch every decode window so queue-wait
    behavior is deterministic on any machine."""

    def __init__(self, delay_s):
        self.delay_s = delay_s

    def at_seam(self, label):
        if label.startswith("window") or label.startswith("wsample"):
            time.sleep(self.delay_s)


def test_deadline_ms_bounds_the_queue_wait(params):
    cache = FaultyCache(CFG, slots=1, pages=16, page_size=4,
                        plan=_SlowWindows(0.05))
    server = PagedGenerationServer(params, CFG, cache=cache, window=1,
                                   sched_policy="strict")
    try:
        occ = server.submit_stream([5, 5], n_new=55)
        next(occ)
        t0 = time.monotonic()
        with pytest.raises(ServerBusy) as exc_info:
            server.submit([1], n_new=2, deadline_ms=300)
        assert time.monotonic() - t0 < 30.0  # deadline, not the 120s timeout
        assert "queue depth [" in str(exc_info.value)
        occ.cancel()
        with pytest.raises(RequestCancelled):
            list(occ)
    finally:
        server.close()


# ---- swap fault -> poison -> revive: the no-leak cycle -------------------


class _SeamRaise:
    """Duck-typed FaultPlan: raise InjectedFault ONCE, at the first
    crossing of the named swap seam (every other seam runs clean)."""

    def __init__(self, label):
        self.label = label
        self.fired = False

    def at_seam(self, label):
        if label == self.label and not self.fired:
            self.fired = True
            raise InjectedFault(f"injected raise at seam {label}")


@pytest.mark.parametrize("seam", ["swapout", "swapin"])
def test_swap_fault_poisons_then_revive_restores_fixpoint(params, seam):
    """A device fault on the swap path (gather out or scatter back)
    poisons the pool like any device fault — every waiter, including
    the swapped-out set, terminates typed — and revive() restores the
    idle fixpoint: no page, reservation, or host-snapshot leak after a
    full preempt -> fault -> recovery cycle."""
    plan = _SeamRaise(seam)
    cache = FaultyCache(CFG, slots=1, pages=16, page_size=4, plan=plan)
    server = PagedGenerationServer(params, CFG, cache=cache, window=4,
                                   sched_policy="strict",
                                   sched_swap_budget_mb=64)
    errors = []
    result = {}
    try:
        victim = server.submit_stream([9, 8, 7], n_new=40,
                                      priority="batch")
        next(victim)

        def worker():
            try:
                result["i"] = server.submit([1, 2], n_new=6)
            except Exception as e:
                errors.append(e)

        t = threading.Thread(target=worker)
        t.start()
        # The victim terminates typed either way: swapout faults while
        # it is active; swapin faults while it is being re-admitted.
        with pytest.raises(ServingFailure):
            list(victim)
        t.join(timeout=120)
        assert not t.is_alive()
        assert plan.fired
        if seam == "swapout":
            # The parked interactive was woken into the refusal path.
            assert len(errors) == 1
            assert isinstance(errors[0], PoolPoisoned)
        else:
            # Swapout succeeded, the interactive ran to completion;
            # the fault hit the victim's swap-in afterwards.
            assert not errors
            assert result["i"] == reference(params, [1, 2], 6)
        # Degraded refusals carry the per-class queue depth
        # (satellite 2).
        with pytest.raises(PoolPoisoned, match=r"queue depth \["):
            server.submit([3], n_new=2)
        server._thread.join(timeout=60)
        assert not server._thread.is_alive()
        server.revive()
        assert_idle_fixpoint(server, pages=16)
        prompt = [4, 5, 6]
        assert server.submit(prompt, n_new=5) == reference(
            params, prompt, 5
        )
        assert_idle_fixpoint(server, pages=16)
    finally:
        server.close()


# ---- pure policy unit tests (no server, no devices) ----------------------


def _mk(policy, **kw):
    return AdmissionScheduler(threading.Lock(), policy=policy, **kw)


def _park(sched, pclass):
    return sched.enqueue_locked(object(), pclass, pages_needed=1)


def test_policy_head_orders():
    fifo = _mk("fifo")
    b = _park(fifo, "batch")
    _park(fifo, "interactive")
    assert fifo.head_locked() is b  # global arrival order

    strict = _mk("strict")
    _park(strict, "batch")
    i = _park(strict, "interactive")
    assert strict.head_locked() is i  # class rank beats arrival

    with pytest.raises(ValueError, match="unknown priority class"):
        strict.rank("bulk")
    with pytest.raises(ValueError, match="policy"):
        _mk("lifo")


def test_weighted_policy_shares_deterministically():
    """weights 3:1 -> admissions interleave 3 interactive per batch,
    deterministically, and batch is never starved."""
    sched = _mk("weighted", weights={"interactive": 3.0, "batch": 1.0})
    for _ in range(6):
        _park(sched, "interactive")
    for _ in range(2):
        _park(sched, "batch")
    admitted = []
    for _ in range(8):
        head = sched.head_locked()
        admitted.append(head.pclass)
        with sched._lock:  # wake_head notifies ticket conditions
            sched.admit_locked(head)
    assert admitted == ["interactive", "interactive", "interactive",
                        "batch", "interactive", "interactive",
                        "interactive", "batch"]
    assert sched.head_locked() is None


def test_stale_wait_estimate_decays_instead_of_shedding_forever():
    """Regression (shed livelock): shed requests never enqueue, so
    nothing feeds the EWMA after a transient spike — the estimate must
    not freeze above the watermark and shed the class forever. Two
    guards: wait/deadline sheds are bypassed while the class queue is
    empty (the arrival would be head immediately, and admitting it is
    the only source of fresh samples), and the estimate ages toward
    zero from the last admission."""
    sched = _mk("strict", max_queue_wait_s=0.5)
    now = time.monotonic()
    sched._wait_ewma["interactive"] = 4.0  # frozen post-spike estimate
    sched._last_admit["interactive"] = now
    # Empty class queue: never shed on the wait/deadline watermarks,
    # no matter how high the stale estimate reads.
    assert sched.shed_check_locked("interactive", None) is None
    assert sched.shed_check_locked("interactive", 100) is None
    # With a parked same-class waiter the fresh estimate DOES shed...
    _park(sched, "interactive")
    assert sched.shed_check_locked("interactive", None) is not None
    # ...but ages toward zero without admissions: one estimate-width
    # of grace, then halving per estimate-width (4s estimate, 40s of
    # silence -> 4 * 0.5^9 ~ 8ms), so the shed ends on its own.
    sched._last_admit["interactive"] = now - 40.0
    est = sched.wait_estimate_locked("interactive")
    assert est is not None and est < 0.5
    assert sched.shed_check_locked("interactive", None) is None
    assert sched.shed_check_locked("interactive", 100) is None
    assert sched.shed == 1


def test_depth_watermark_counts_only_classes_ahead():
    """Regression (priority inversion in shedding): a flood of parked
    batch tickets must not trip the depth watermark for an interactive
    arrival that strict policy would admit ahead of all of them —
    only tickets at or above the arrival's class count. Under fifo
    every ticket is genuinely ahead, so the global depth applies."""
    sched = _mk("strict", max_queue_depth=2)
    for _ in range(3):
        _park(sched, "batch")
    assert sched.shed_check_locked("interactive", None) is None
    assert sched.shed_check_locked("batch", None) is not None
    fifo = _mk("fifo", max_queue_depth=2)
    for _ in range(3):
        _park(fifo, "batch")
    assert fifo.shed_check_locked("interactive", None) is not None


def test_swap_residency_has_its_own_histogram():
    """Swapped-out residency (enqueued_at resets at swap-out) must not
    inflate the admission queue-wait histogram the EWMA mirrors — it
    lands in sched_swap_residency_ms instead."""
    sched = _mk("strict", swap_budget_mb=1)
    with sched._lock:
        early = _park(sched, "batch")
        req = early.req
        sched.remove_locked(early)
        entry = sched.record_swapout_locked(
            req, "batch", early.no, pages_needed=2, saved_len=8,
            arrays=(np.zeros((4,), np.int8),),
        )
        sched.pop_resume_locked(entry)
        stats = sched.stats_locked()
    assert stats["sched_queue_wait_ms_batch"]["count"] == 0
    assert stats["sched_swap_residency_ms_batch"]["count"] == 1


def test_frozen_high_wait_estimate_does_not_livelock(params):
    """Server-level livelock regression: an idle server whose EWMA was
    left high by a drained transient must still admit new requests
    (and their admissions are what refresh the estimate)."""
    server = sched_server(params, sched_max_queue_wait_s=0.1)
    try:
        with server._lock:
            server._sched._wait_ewma["interactive"] = 60.0
        prompt = [1, 2]
        assert server.submit(prompt, n_new=3) == reference(
            params, prompt, 3
        )
        assert server.stats()["sched_shed_total"] == 0
    finally:
        server.close()


def test_resume_entry_keeps_original_ticket_order():
    """A preempted request re-enters AHEAD of later arrivals of its
    class: the resume entry carries its original ticket number."""
    sched = _mk("strict", swap_budget_mb=1)
    with sched._lock:  # wake_head notifies ticket conditions
        early = _park(sched, "batch")
        req = early.req
        sched.remove_locked(early)  # it admitted, then got preempted
        _park(sched, "batch")  # a later arrival
        entry = sched.record_swapout_locked(
            req, "batch", early.no, pages_needed=2, saved_len=8,
            arrays=(np.zeros((4,), np.int8),),
        )
        assert sched.head_locked() is entry
        assert sched.swap_bytes == 4
        assert sched.depth_locked() == 1  # resume entries hold no thread
        sched.pop_resume_locked(entry)
        assert sched.swap_bytes == 0
        assert sched.resumes == 1
