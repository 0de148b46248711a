"""Device-resident speculative decode windows (SERVING.md rung 20).

One dispatched program runs W draft+verify passes — n-gram drafting
over a device-resident context, accept/reject, KV commits, budget
freezing, and the pending-token chain — so the host round trip
amortizes over up to W*(1+K) tokens instead of taxing every pass. The
pinned contract is that windows are a SCHEDULING change only: token
streams are bit-identical to the legacy per-pass speculative path and
to plain greedy decode, and the pipeline composes with everything the
overlap loop already guarantees — sampled co-tenants (on-device
accept/reject since rung 23; legacy fallback only when the
spec_sampled_window knob is off), scheduler preemption,
poison-drain-revive recovery, and the slice broadcast protocol
(OP_SPECW/OP_SPECWS, tested in test_sliceserve.py).
All fixed-seed and fast: these run in the tier-1 gate.
"""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kvedge_tpu.models import TransformerConfig, generate, init_params
from kvedge_tpu.models.kvcache import PagedCacheError, PagedKVCache
from kvedge_tpu.models.serving import PagedGenerationServer
from kvedge_tpu.runtime.failures import ServingFailure
from kvedge_tpu.testing.servingfaults import FaultPlan, FaultyCache

pytestmark = pytest.mark.window

CFG = TransformerConfig(
    vocab=128, d_model=32, n_heads=4, n_kv_heads=2, n_layers=2, d_ff=64,
    max_seq=64,
)

REQUESTS = [
    ([5, 9, 2], 17),
    ([7, 7, 7, 7, 7, 1, 4], 9),
    ([3, 1, 4, 1, 5], 23),
]


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


def reference(params, prompt, n_new):
    out = generate(params, jnp.asarray([prompt], jnp.int32), CFG,
                   n_new=n_new)
    return [int(t) for t in np.asarray(out)[0]]


def run_concurrent(server, requests=REQUESTS):
    results: dict[int, list[int]] = {}
    errors: list[Exception] = []

    def worker(i, prompt, n_new):
        try:
            results[i] = server.submit(prompt, n_new)
        except Exception as e:
            errors.append(e)

    threads = [
        threading.Thread(target=worker, args=(i, p, n))
        for i, (p, n) in enumerate(requests)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    assert not errors, errors
    return results


# ---- bit-identity: windowed == legacy per-pass == plain greedy -----------


def test_windowed_spec_matches_legacy_and_greedy(params):
    """The tentpole contract: under greedy verify, windowed spec emits
    the SAME tokens as the legacy host-loop spec path and as plain
    (non-speculative) decode — speculation and windowing are latency
    optimizations, never semantic ones."""
    outs = {}
    for name, kw in (
        ("greedy", {}),
        ("legacy", {"speculative": 3}),
        ("windowed", {"speculative": 3, "spec_window": 4}),
    ):
        server = PagedGenerationServer(params, CFG, slots=4, pages=64,
                                       page_size=4, **kw)
        try:
            outs[name] = run_concurrent(server)
            if name == "windowed":
                stats = server.stats()
        finally:
            server.close()
    assert outs["legacy"] == outs["greedy"]
    assert outs["windowed"] == outs["greedy"]
    for i, (prompt, n_new) in enumerate(REQUESTS):
        assert outs["windowed"][i] == reference(params, prompt, n_new), i
    # The windows actually ran (this was not a silent legacy fallback).
    assert stats["spec_windows_total"] >= 1
    hist = stats["spec_window_emitted_tokens"]
    assert hist["count"] == sum(hist["counts"]) >= 1
    # Every emitted token is accounted to some window, except a
    # request's final token when its budget happens to fill at a
    # boundary (the finish sweep emits the pending token steplessly —
    # at most one per request).
    total = sum(n for _, n in REQUESTS)
    assert total - len(REQUESTS) <= hist["sum"] <= total


SAMPLING = (jax.random.fold_in(jax.random.PRNGKey(7), 0),
            jnp.float32(0.8), jnp.float32(0.9))
PROMPT_G, PROMPT_S = [5, 9, 2, 7], [1, 2, 3, 4]


def _mixed_references(params):
    plain = PagedGenerationServer(params, CFG, slots=2, pages=32,
                                  page_size=4)
    try:
        want_s = plain.submit(PROMPT_S, 12, sampling=SAMPLING)
    finally:
        plain.close()
    return reference(params, PROMPT_G, 9), want_s


def _run_mixed(server):
    """Guaranteed co-residency: stream the sampled request first (one
    yielded token proves it is admitted and mid-flight), THEN submit
    the greedy one — the spec boundary sees a genuinely mixed batch,
    which is the only state where the sampled-window path (or its
    counted fallback) can trigger."""
    stream = server.submit_stream(PROMPT_S, n_new=12, sampling=SAMPLING)
    first = next(stream)
    got_g = server.submit(PROMPT_G, 9)
    got_s = PROMPT_S + [first] + list(stream)
    return {"g": got_g, "s": got_s}


def test_sampled_cotenant_stays_windowed_bit_identical(params):
    """Rung 23: a sampled request in the batch no longer collapses the
    window — its accept/reject runs IN the scan with per-row keys split
    on device, advancing exactly one token per pass on the legacy key
    schedule. Both streams stay bit-identical to their references, the
    windows actually ran, and the "sampled" fallback counter stays 0
    (the ISSUE acceptance bar for mixed steady state)."""
    want_g, want_s = _mixed_references(params)
    # window=2 keeps solo stretches short: admission boundaries come
    # every couple of tokens, so the greedy arrival genuinely joins
    # the sampled request mid-stream instead of racing its finish.
    server = PagedGenerationServer(params, CFG, slots=2, pages=32,
                                   page_size=4, window=2,
                                   speculative=3, spec_window=4)
    try:
        results = _run_mixed(server)
        stats = server.stats()
        assert results["g"] == want_g
        assert results["s"] == want_s
        assert stats["spec_window_sampled"] == 1
        assert stats["spec_windows_total"] >= 1
        assert stats["spec_window_fallbacks"]["sampled"] == 0
    finally:
        server.close()


def test_sampled_window_knob_off_falls_back_counted(params):
    """spec_sampled_window=False restores the rung-20 collapse: a
    sampled co-tenant sends the whole batch through the legacy
    per-pass path. Tokens are bit-identical either way — the knob is
    purely a scheduling escape hatch — and every collapse is counted
    under cause="sampled"."""
    want_g, want_s = _mixed_references(params)
    server = PagedGenerationServer(params, CFG, slots=2, pages=32,
                                   page_size=4, window=2,
                                   speculative=3, spec_window=4,
                                   spec_sampled_window=False)
    try:
        results = _run_mixed(server)
        stats = server.stats()
        assert results["g"] == want_g
        assert results["s"] == want_s
        assert stats["spec_window_sampled"] == 0
        assert stats["spec_window_fallbacks"]["sampled"] >= 1
    finally:
        server.close()


# ---- composition: preemption and recovery --------------------------------


def test_spec_window_preempt_resume_bit_identical(params):
    """Scheduler preemption composes with spec windows: a batch victim
    swapped to host mid-stream and resumed later still emits exactly
    its never-preempted tokens, and the interactive request that
    preempted it is exact too."""
    server = PagedGenerationServer(
        params, CFG, slots=1, pages=16, page_size=4, window=4,
        speculative=3, spec_window=2, sched_policy="strict",
        sched_swap_budget_mb=64,
    )
    victim_prompt, inter_prompt = [9, 8, 7], [40, 41, 42]
    try:
        victim = server.submit_stream(victim_prompt, n_new=40,
                                      priority="batch")
        first = next(victim)
        got_i = server.submit(inter_prompt, n_new=6)
        got_v = victim_prompt + [first] + list(victim)
        stats = server.stats()
        assert stats["sched_preemptions_total"] >= 1
        assert stats["sched_resumes_total"] >= 1
        assert got_i == reference(params, inter_prompt, 6)
        assert got_v == reference(params, victim_prompt, 40)
        assert server.stats()["sched_swap_bytes_host"] == 0
    finally:
        server.close()


def test_poison_mid_spec_window_drains_inflight_then_revives(params):
    """A FaultPlan raise at the spec-window HARVEST seam — with the
    next spec window already dispatched — must drain the in-flight
    window exactly once (bookkeeping AND the device handle), poison
    typed, and revive() must drop the spec carry and the worst-case
    unharvested reservations so the restarted pipeline serves
    bit-identical tokens."""
    # Seam order for a lone spec-window request: prefill, specw,
    # specw (pipelined), specwharvest, ... — fire_at=3 lands the raise
    # on the first harvest, with window 2 in flight.
    plan = FaultPlan(0, kinds=("raise",), fire_window=(3, 4))
    cache = FaultyCache(CFG, slots=2, pages=24, page_size=4, plan=plan)
    server = PagedGenerationServer(params, CFG, cache=cache,
                                   speculative=3, spec_window=2)
    prompt = [3, 1, 4, 1, 5]
    try:
        dying_thread = server._thread
        with pytest.raises(ServingFailure):
            server.submit(prompt, n_new=40)
        dying_thread.join(timeout=30)
        assert not dying_thread.is_alive()
        assert server.degraded is not None
        assert plan.fired_on == "specwharvest", plan.trace
        # The in-flight spec window was drained on the way out: its
        # handle was forced (a second specwharvest seam crossing) and
        # no stale record survives into recovery.
        assert server._inflight is None
        crossings = [t for t in plan.trace if "specwharvest" in t]
        assert len(crossings) >= 2, plan.trace
        server.revive()
        assert server.degraded is None
        assert cache._spec_carry is None
        assert cache._spec_unharvested == [0] * cache.slots
        assert server.submit(prompt, n_new=8) == reference(
            params, prompt, 8)
        stats = server.stats()
        assert stats["in_flight"] == 0
        assert stats["reserved_pages"] == 0
    finally:
        plan.close()
        server.close()


def test_revive_drops_spec_carry_and_unharvested(params):
    """drop_carry() (revive/reform path) clears BOTH pipelines: the
    plain window carry and the spec carry + worst-case reservations."""
    cache = PagedKVCache(CFG, slots=2, pages=24, page_size=4)
    prompt = [5, 9, 2]
    cache.admit(0, len(prompt))
    logits = cache.prefill(params, 0, jnp.asarray(prompt, jnp.int32))
    pend = np.zeros((2,), np.int32)
    pend[0] = int(jnp.argmax(logits))
    s_ctx = CFG.max_seq + 8
    ctx = np.zeros((2, s_ctx), np.int32)
    seq = prompt + [int(pend[0])]
    ctx[0, :len(seq)] = seq
    ctx_len = np.zeros((2,), np.int32)
    ctx_len[0] = len(seq)
    cache.dispatch_spec_window(
        params, pend, 2, 3, np.array([10, 0], np.int32),
        ctx=ctx, ctx_len=ctx_len,
    )
    assert cache._spec_carry is not None
    assert cache._spec_unharvested[0] > 0
    cache.drop_carry()
    assert cache._spec_carry is None
    assert cache._spec_unharvested == [0, 0]
    with pytest.raises(PagedCacheError):
        cache.dispatch_spec_window(params, None, 2, 3,
                                   np.array([10, 0], np.int32))


# ---- cache-level contract ------------------------------------------------


def test_spec_window_dispatch_needs_context_or_carry(params):
    cache = PagedKVCache(CFG, slots=2, pages=16, page_size=4)
    budgets = np.array([4, 0], np.int32)
    with pytest.raises(PagedCacheError):
        cache.dispatch_spec_window(params, None, 2, 3, budgets)
    with pytest.raises(PagedCacheError):
        cache.dispatch_spec_window(
            params, np.zeros((2,), np.int32), 2, 3, budgets
        )


def test_spec_window_caps_are_worst_case():
    cache = PagedKVCache(CFG, slots=3, pages=16, page_size=4)
    caps = cache.spec_window_caps(4, 3, np.array([20, 1, 0], np.int32))
    # min(budget + K, W*(K+1)); zero-budget rows reserve nothing.
    assert caps.tolist() == [16, 4, 0]


def test_spec_window_knob_validation(params):
    with pytest.raises(ValueError):
        PagedGenerationServer({}, CFG, spec_window=-1)
    with pytest.raises(ValueError):
        # Windows without spec mode have no drafts to run.
        PagedGenerationServer({}, CFG, spec_window=4, speculative=0)


# ---- observability -------------------------------------------------------


def test_spec_window_stats_and_histogram_shape(params):
    server = PagedGenerationServer(params, CFG, slots=2, pages=32,
                                   page_size=4, speculative=3,
                                   spec_window=4)
    try:
        server.submit([5, 9, 2], n_new=12)
        deadline = time.monotonic() + 30
        while (server.stats()["in_flight"]
               and time.monotonic() < deadline):
            time.sleep(0.01)
        stats = server.stats()
        assert stats["spec_window"] == 4
        assert stats["spec_windows_total"] >= 1
        assert stats["spec_passes"] >= 1
        hist = stats["spec_window_emitted_tokens"]
        assert len(hist["counts"]) == len(hist["edges"]) + 1
        assert hist["count"] == sum(hist["counts"]) >= 1
        assert hist["sum"] >= 1.0
        # The /metrics exposition carries the window series: gauges
        # plus a conformant Prometheus histogram.
        from kvedge_tpu.runtime.status import render_metrics

        body = render_metrics({"serving": stats})
        assert "kvedge_serve_spec_window 4" in body
        assert "kvedge_serve_spec_windows_total" in body
        name = "kvedge_serve_spec_window_emitted_tokens"
        assert f"# TYPE {name} histogram" in body
        assert f'{name}_bucket{{le="+Inf"}} {hist["count"]}' in body
    finally:
        server.close()
