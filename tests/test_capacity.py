"""Capacity-driven continuous batching (SERVING.md rung 21).

The pinned contract: slot count and page-pool size are RUNTIME capacity
decisions. The device batch dim runs at a power-of-two compile bucket —
admissions within a bucket cause ZERO retraces (compile-counter pin),
bucket steps happen only at quiescent boundaries and preserve
bit-identity with the slots-pinned path; the page pool can be sized
from an HBM byte budget with free-page watermarks feeding the
scheduler's shed/resume decisions; ingress row ceilings derive from the
page budget, not a bare slot multiple; and every refusal reports
page-capacity terms. All fixed-seed and fast: these run in the tier-1
gate.
"""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kvedge_tpu.config.runtime_config import (
    RuntimeConfig,
    RuntimeConfigError,
)
from kvedge_tpu.models import TransformerConfig, generate, init_params
from kvedge_tpu.models import kvcache as kvcache_mod
from kvedge_tpu.models.kvcache import PagedCacheError, PagedKVCache
from kvedge_tpu.models.serving import (
    PagedGenerationServer,
    ServerBusy,
    ServerOverloaded,
)
from kvedge_tpu.runtime.failures import ServingFailure
from kvedge_tpu.runtime.status import render_metrics
from kvedge_tpu.runtime.workload import (
    MeshConfigError,
    _parse_generate_request,
    _serve_max_rows,
    _serving_page_bytes,
    _serving_pool_dims,
)

pytestmark = pytest.mark.capacity

CFG = TransformerConfig(
    vocab=128, d_model=32, n_heads=4, n_kv_heads=2, n_layers=2, d_ff=64,
    max_seq=64,
)


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


def reference(params, prompt, n_new):
    out = generate(params, jnp.asarray([prompt], jnp.int32), CFG,
                   n_new=n_new)
    return [int(t) for t in np.asarray(out)[0]]


def run_concurrent(server, requests, timeout=300.0):
    """Submit ``requests`` = [(prompt, n_new), ...] from one thread
    each; return {index: tokens}. Any worker exception fails the test."""
    results: dict[int, list[int]] = {}
    errors: list[Exception] = []

    def worker(i, prompt, n_new):
        try:
            results[i] = server.submit(prompt, n_new, timeout=timeout)
        except Exception as e:
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(i, p, n))
               for i, (p, n) in enumerate(requests)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=timeout)
    assert not errors, errors
    return results


# ---- the bucket ladder (cache-level invariants) --------------------------


def test_bucket_ladder_and_validation():
    cache = PagedKVCache(CFG, slots=6, pages=24, page_size=4,
                         min_bucket=2)
    # Powers of two from min_bucket, capped at slots (top rung = slots
    # even when slots is not itself a power of two).
    assert cache.bucket == 2
    assert [cache.bucket_for(n) for n in (0, 1, 2, 3, 4, 5, 6)] == \
        [2, 2, 2, 4, 4, 6, 6]
    cache.set_bucket(4)
    assert cache.bucket == 4
    with pytest.raises(PagedCacheError, match="ladder"):
        cache.set_bucket(3)
    with pytest.raises(PagedCacheError, match="ladder"):
        cache.set_bucket(8)
    # Admitting above the bucket is a serving-layer bug, caught loudly.
    with pytest.raises(PagedCacheError, match="outside the current"):
        cache.admit(5, 4)
    # A resize below an admitted slot is refused.
    cache.admit(3, 4)
    with pytest.raises(PagedCacheError, match="admitted"):
        cache.set_bucket(2)
    cache.release(3)
    cache.set_bucket(2)
    assert cache.bucket == 2


def test_bucketing_disabled_pins_to_slots():
    cache = PagedKVCache(CFG, slots=4, pages=16, page_size=4)
    assert cache.min_bucket == 0 and cache.bucket == 4
    assert cache.bucket_for(1) == 4
    with pytest.raises(PagedCacheError, match="disabled"):
        cache.set_bucket(2)


def test_device_arrays_are_bucket_sized(params):
    cache = PagedKVCache(CFG, slots=8, pages=32, page_size=4,
                         min_bucket=2)
    assert cache.state.tables.shape[0] == 2
    assert cache.state.lengths.shape[0] == 2
    cache.set_bucket(4)
    assert cache.state.tables.shape[0] == 4
    # Host bookkeeping stays slots-sized throughout — the resize only
    # rebuilds the device view, never the pool or the books.
    assert len(cache._host_lengths) == 8
    assert cache.state.pool_k.shape[1] == 32


# ---- zero retraces within a bucket (the compile-counter pin) -------------


def test_within_bucket_admissions_zero_retraces(params):
    """After one warmup request per program shape, serving any number
    of additional requests WITHIN the same bucket triggers zero new
    traces — growth and shrink of active concurrency reuse the
    compiled, dead-row-masked programs."""
    server = PagedGenerationServer(params, CFG, slots=4, pages=32,
                                   page_size=4, min_bucket=4,
                                   prefix_cache=False)
    prompts = [[5, 9, 2], [1, 4, 3], [7, 7, 7], [100, 50, 2]]
    try:
        assert server._cache.bucket == 4  # ladder [4]: one rung
        # Warm every program shape the pinned runs can touch: the
        # window ladder is power-of-two-floored ({1, 2, 4} for an
        # 8-token budget), so one solo request plus one full batch
        # visits all of it.
        server.submit(prompts[0], n_new=8)
        run_concurrent(server, [(p, 8) for p in prompts])
        pinned = kvcache_mod.trace_count()
        got = run_concurrent(server, [(p, 8) for p in prompts])
        server.submit(prompts[1], n_new=8)
        assert kvcache_mod.trace_count() == pinned, (
            "an admission inside a warm bucket recompiled"
        )
        for i, p in enumerate(prompts):
            assert got[i] == reference(params, p, 8)
    finally:
        server.close()


def test_bucket_step_retraces_once_then_caches(params):
    """Stepping to a NEW bucket traces once; coming back to a bucket
    already visited reuses its programs (jit keys on the device batch
    dim, so each rung compiles at most once per shape).

    Which shapes a round of two racing threads visits hangs on the
    host's timing: with the default window a round met another set of
    (bucket, window) pairs than the one before it, or a prefill at the
    other bucket, when five busy test workers ran beside it (red under
    ``-n 6``, green alone). So the shapes are made a closed set and
    counted: ``window=1`` leaves one window program a bucket, the
    caches start empty, and a rung is three trace events (its prefill,
    its window and the step inside it). Two rungs are six events, each
    at most once, in whatever order the rounds meet them (a round can
    step up for a row that then prefills after the bucket stepped down
    again: a window at bucket 2 before any prefill there), and none
    after the sixth, however many rounds follow."""
    jax.clear_caches()  # programs other tests compiled at these shapes
    server = PagedGenerationServer(params, CFG, slots=2, pages=16,
                                   page_size=4, min_bucket=1, window=1,
                                   prefix_cache=False)
    reqs = [([5, 9, 2], 8), ([1, 4, 3], 8)]
    cold = kvcache_mod.trace_count()

    def traced():
        return kvcache_mod.trace_count() - cold

    try:
        server.submit(reqs[0][0], n_new=8)       # bucket 1 warm
        assert traced() == 3
        for _ in range(200):                     # until bucket 2 is too
            run_concurrent(server, reqs)
            assert traced() <= 6
            if traced() == 6:
                break
        assert traced() == 6
        for _ in range(3):                       # both rungs warm now
            run_concurrent(server, reqs)
            server.submit(reqs[0][0], n_new=8)
        assert traced() == 6
    finally:
        server.close()


def test_bucket_steps_down_when_load_drains(params):
    """After a full batch drains, a solo request's boundaries step the
    bucket back DOWN (lazily — only when nothing is queued above it),
    so a traffic spike doesn't pin the big-batch programs forever."""
    server = PagedGenerationServer(params, CFG, slots=4, pages=32,
                                   page_size=4, window=2, min_bucket=1,
                                   prefix_cache=False)
    requests = [([5, 9, 2], 6), ([1, 4], 6), ([7], 6), ([9, 9, 9], 6)]
    try:
        run_concurrent(server, requests)  # peaks at bucket 4
        got = server.submit([3, 1, 4], n_new=8)
        assert got == reference(params, [3, 1, 4], 8)
        deadline = time.monotonic() + 30
        while server._cache.bucket > 1:
            if time.monotonic() > deadline:
                raise AssertionError("bucket never stepped down")
            time.sleep(0.01)
    finally:
        server.close()


# ---- bit-identity across bucket transitions ------------------------------


@pytest.mark.parametrize("block", ["plain", "recurrent", "window-block"])
@pytest.mark.parametrize("window", [1, 64], ids=["w1", "w64"])
def test_bucketed_tokens_match_pinned_path(params, probe_blocks, window,
                                           block):
    """The same request set through a bucketed server (stepping 1->2->4
    under load, and down again under the solo request that follows) and
    a slots-pinned server produces IDENTICAL tokens — and, on the plain
    block, both match contiguous generate. Carries migrate or drop at
    bucket steps without moving a single token, at one-step windows
    (a trip a token) and at the default. On a patterned block
    (``probe_blocks``; the pinned path is the reference there) a step
    carries each live row's recurrent state, or both pools' tables."""
    cfg = CFG
    if block != "plain":
        cfg, params = probe_blocks[block]
    requests = [
        ([5, 9, 2], 8),
        ([1, 1, 4, 3, 7, 7], 4),
        ([100, 50], 12),
        ([42], 9),
    ]
    outs = []
    for min_bucket in (0, 1):
        server = PagedGenerationServer(
            params, cfg, slots=4, pages=32, page_size=4,
            min_bucket=min_bucket, window=window, prefix_cache=False,
        )
        try:
            outs.append((run_concurrent(server, requests),
                         server.submit([3, 1, 4], 8)))
        finally:
            server.close()
    pinned, bucketed = outs
    assert pinned == bucketed
    if block == "plain":
        for i, (prompt, n_new) in enumerate(requests):
            assert bucketed[0][i] == reference(params, prompt, n_new)


# ---- preempt/resume and poison/revive at a bucket boundary ---------------


def test_preempt_resume_across_bucket_steps(params):
    """Preemptive swap composes with bucketing: a batch victim swapped
    out while the bucket was high resumes bit-identically even after
    the pool stepped down in between (resume steps the bucket back up
    before re-admitting)."""
    server = PagedGenerationServer(
        params, CFG, slots=2, pages=24, page_size=4, window=4,
        min_bucket=1, sched_policy="strict", sched_swap_budget_mb=64,
        prefix_cache=False,
    )
    victim_prompt = [9, 8, 7]
    try:
        # Two 11-page victims fill both slots (bucket steps 1 -> 2)
        # and leave only 2 free pages, so the 3-page interactive
        # arrival below cannot admit without a preemption.
        victims = [server.submit_stream(victim_prompt, n_new=40,
                                        priority="batch")
                   for _ in range(2)]
        firsts = [next(v) for v in victims]  # both slots held: bucket 2
        got_i = server.submit([40, 41, 42], n_new=6,
                              priority="interactive")
        got_v = [victim_prompt + [f] + list(v)
                 for f, v in zip(firsts, victims)]
        assert server.stats()["sched_preemptions_total"] >= 1
        assert got_i == reference(params, [40, 41, 42], 6)
        want_v = reference(params, victim_prompt, 40)
        assert got_v[0] == want_v and got_v[1] == want_v
        assert server.stats()["sched_swap_bytes_host"] == 0
    finally:
        server.close()


def test_poison_revive_resets_bucket(params):
    """A pool poisoned while the bucket is stepped up revives at the
    SMALLEST rung (empty pool, nothing compiled is lost) and serves
    bit-identically afterwards."""
    server = PagedGenerationServer(params, CFG, slots=2, pages=16,
                                   page_size=4, min_bucket=1,
                                   prefix_cache=False)
    prompt = [3, 1, 4, 1, 5]
    try:
        run_concurrent(server, [(prompt, 4), ([2, 7], 4)])  # bucket 2
        cache = server._cache
        real = cache.harvest_window

        def dying(handle):
            raise RuntimeError("injected: harvest died")

        cache.harvest_window = dying
        dying_thread = server._thread
        with pytest.raises(ServingFailure):
            server.submit(prompt, n_new=8)
        dying_thread.join(timeout=30)
        cache.harvest_window = real
        server.revive()
        assert server.degraded is None
        assert cache.bucket == cache.bucket_for(0) == 1
        assert server.submit(prompt, n_new=6) == reference(
            params, prompt, 6)
    finally:
        server.close()


# ---- page-capacity refusals ----------------------------------------------


def test_server_busy_reports_page_terms(params):
    server = PagedGenerationServer(params, CFG, slots=1, pages=16,
                                   page_size=4, window=4,
                                   prefix_cache=False)
    try:
        src = server.submit_stream([1, 2, 3], n_new=40)
        next(src)
        # Stall harvests so the stream deterministically holds the one
        # slot past the probe's timeout (warm compile caches otherwise
        # finish the 40 tokens inside it).
        cache = server._cache
        real = cache.harvest_window

        def slow(handle):
            time.sleep(0.4)
            return real(handle)

        cache.harvest_window = slow
        try:
            with pytest.raises(ServerBusy) as exc:
                server.submit([4, 5], n_new=4, timeout=0.2)
        finally:
            cache.harvest_window = real
        msg = str(exc.value)
        assert "pages unreserved" in msg and "bucket" in msg
        src.cancel()
        with pytest.raises(Exception):
            list(src)
    finally:
        server.close()


def test_page_low_watermark_sheds_non_top_priority(params):
    """Below the low watermark, batch arrivals shed with page terms;
    the top class still parks (it is what preemption frees pages FOR)."""
    server = PagedGenerationServer(
        params, CFG, slots=2, pages=16, page_size=4,
        page_low_watermark=0.95, prefix_cache=False,
    )
    try:
        with pytest.raises(ServerOverloaded) as exc:
            server.submit([5, 9, 2], n_new=4, priority="batch")
        msg = str(exc.value)
        assert "low watermark" in msg and "pages unreserved" in msg
        assert server.stats()["sched_shed_total"] >= 1
        got = server.submit([5, 9, 2], n_new=4, priority="interactive")
        assert got == reference(params, [5, 9, 2], 4)
    finally:
        server.close()


def test_page_high_watermark_gates_resume(params):
    server = PagedGenerationServer(
        params, CFG, slots=2, pages=16, page_size=4,
        page_high_watermark=0.5, prefix_cache=False,
    )
    try:
        with server._lock:
            assert server._resume_pages_ok_locked(4)  # 12 free >= 8
            server._reserved = 10
            assert not server._resume_pages_ok_locked(4)  # 2 free < 8
            server._reserved = 0
    finally:
        server.close()


def test_watermark_knobs_validate(params):
    with pytest.raises(ValueError, match="watermark"):
        PagedGenerationServer(params, CFG, slots=1, pages=8,
                              page_size=4, page_low_watermark=1.5)
    with pytest.raises(ValueError, match="watermark"):
        PagedGenerationServer(params, CFG, slots=1, pages=8,
                              page_size=4, page_low_watermark=0.6,
                              page_high_watermark=0.3)


# ---- ingress row ceiling derives from the page budget --------------------


def _payload_cfg(**payload):
    return RuntimeConfig.from_mapping({"payload": payload})


def test_max_rows_matches_legacy_for_auto_pools():
    cfg = _payload_cfg(serving_slots=4, serving_page_size=4)
    assert _serve_max_rows(cfg, CFG) == 4 * 4  # pages//mpps == slots


def test_max_rows_follows_page_budget():
    # serving_pages holds 2 worst-case requests on 4 slots: the ceiling
    # tracks the POOL (4 x 2), not the slot count (4 x 4).
    mpps = -(-CFG.max_seq // 4)
    cfg = _payload_cfg(serving_slots=4, serving_page_size=4,
                       serving_pages=2 * mpps)
    assert _serve_max_rows(cfg, CFG) == 4 * 2
    # ...and never collapses to zero for a one-request pool.
    cfg = _payload_cfg(serving_slots=4, serving_page_size=4,
                       serving_pages=mpps)
    assert _serve_max_rows(cfg, CFG) == 4


def test_hbm_budget_sizes_pool():
    page_bytes = _serving_page_bytes(
        _payload_cfg(serving_page_size=4), CFG)
    # K+V across layers; int8 adds two fp32 scale slabs per page.
    assert page_bytes > 0
    mpps = -(-CFG.max_seq // 4)
    budget_mb = -(-3 * mpps * page_bytes // 2**20)  # >= 3 requests
    cfg = _payload_cfg(serving_slots=8, serving_page_size=4,
                       serving_hbm_budget_mb=int(budget_mb))
    slots, pages, page_size, got_mpps = _serving_pool_dims(cfg, CFG)
    assert (slots, page_size, got_mpps) == (8, 4, mpps)
    assert pages == budget_mb * 2**20 // page_bytes
    assert pages >= 3 * mpps
    # int8 pools buy MORE pages from the same budget (smaller K/V),
    # but less than the raw dtype ratio (the fp32 scales ride along).
    int8_bytes = _serving_page_bytes(
        _payload_cfg(serving_page_size=4, serving_kv_dtype="int8"), CFG)
    assert int8_bytes < page_bytes


def test_hbm_budget_too_small_fails_loudly():
    cfg = _payload_cfg(serving_slots=4, serving_page_size=4,
                       serving_hbm_budget_mb=1)
    if _serving_page_bytes(cfg, CFG) * (-(-CFG.max_seq // 4)) <= 2**20:
        pytest.skip("tiny model: 1 MiB already fits a request")
    with pytest.raises(MeshConfigError, match="worst-case request"):
        _serving_pool_dims(cfg, CFG)


def test_ingress_refusal_reports_page_terms():
    with pytest.raises(ValueError, match="page pool"):
        _parse_generate_request(
            {"tokens": [[1, 2]] * 3}, CFG, max_rows=2, paged=True,
        )


# ---- config knobs --------------------------------------------------------


def test_capacity_knobs_round_trip():
    cfg = _payload_cfg(serving_hbm_budget_mb=64, serving_min_bucket=4,
                       serving_page_low_watermark=0.1,
                       serving_page_high_watermark=0.25)
    cfg.validate()
    toml = cfg.to_toml()
    for needle in ("serving_hbm_budget_mb = 64",
                   "serving_min_bucket = 4",
                   "serving_page_low_watermark = 0.1",
                   "serving_page_high_watermark = 0.25"):
        assert needle in toml
    again = RuntimeConfig.from_toml_str(toml) if hasattr(
        RuntimeConfig, "from_toml_str") else None
    if again is not None:
        assert again.serving_hbm_budget_mb == 64


def test_capacity_knobs_validate():
    with pytest.raises(RuntimeConfigError, match="mutually exclusive"):
        _payload_cfg(serving_hbm_budget_mb=64,
                     serving_pages=10).validate()
    with pytest.raises(RuntimeConfigError, match="watermark"):
        _payload_cfg(serving_page_low_watermark=1.2).validate()
    with pytest.raises(RuntimeConfigError, match="watermark"):
        _payload_cfg(serving_page_low_watermark=0.5,
                     serving_page_high_watermark=0.2).validate()
    with pytest.raises(RuntimeConfigError, match="min_bucket"):
        _payload_cfg(serving_min_bucket=-1).validate()


# ---- rung 22 x capacity: checkpoints under preemption + watermarks -------


def _stream_in_background(server, prompt, n_new, **kw):
    """Drive a stream from a daemon thread; returns (got, done, errs).
    No consumer timeout: a journaled request PARKS across poison/revive
    (rung 22) and the test owns the deadline."""
    got: list[int] = []
    errs: list[Exception] = []
    done = threading.Event()

    def consume():
        try:
            for tok in server.submit_stream(prompt, n_new, **kw):
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


def _arm_kill(server, ready, message):
    """Raise at the first window harvest where ``ready()`` holds."""
    cache = server._cache
    real_h = cache.harvest_window
    state = {"arm": True}

    def dying_h(handle):
        if state["arm"] and ready():
            state["arm"] = False
            raise RuntimeError(message)
        return real_h(handle)

    cache.harvest_window = dying_h


def test_poison_with_swapped_victim_revives_all(params):
    """Rung 22 x rung 17: the pool poisons while a preempted victim
    sits in the swap set. Its host snapshot is ALREADY a verbatim
    checkpoint, so revive brings back all three requests — the two
    checkpointed actives refill the slots and the swapped victim
    re-queues under its original ticket (more checkpoints than slots)
    to resume at a boundary — and every one completes bit-identical."""
    server = PagedGenerationServer(
        params, CFG, slots=2, pages=24, page_size=4, window=4,
        min_bucket=1, sched_policy="strict", sched_swap_budget_mb=64,
        checkpoint_every=1, prefix_cache=False,
    )
    victim_prompt = [9, 8, 7]
    dying_thread = server._thread
    try:
        victims = [server.submit_stream(victim_prompt, n_new=40,
                                        priority="batch")
                   for _ in range(2)]
        firsts = [next(v) for v in victims]  # both slots held
        # Fire only once the interactive arrival has preempted a victim
        # (swap bytes parked) AND everything holds a checkpoint: both
        # actives plus the victim's pre-swap entry.
        _arm_kill(
            server,
            lambda: (server._sched.swap_bytes > 0
                     and len(server._journal) >= 3),
            "injected: died with a swapped-out victim",
        )
        tails: list[list[int]] = [[], []]

        def drain(i):
            for tok in victims[i]:
                tails[i].append(tok)

        threads = [threading.Thread(target=drain, args=(i,),
                                    daemon=True) for i in range(2)]
        for t in threads:
            t.start()
        inter: dict = {}

        def interactive():
            try:
                inter["tokens"] = server.submit(
                    [40, 41, 42], n_new=6, priority="interactive")
            except Exception as e:
                inter["error"] = e

        it = threading.Thread(target=interactive, daemon=True)
        it.start()
        _wait_degraded(server)
        dying_thread.join(timeout=30)
        assert not dying_thread.is_alive()
        assert server.revive() == 3
        it.join(timeout=120)
        for t in threads:
            t.join(timeout=120)
        assert "error" not in inter, inter
        assert inter["tokens"] == reference(params, [40, 41, 42], 6)
        want_v = reference(params, victim_prompt, 40)
        for f, tail in zip(firsts, tails):
            assert victim_prompt + [f] + tail == want_v
        stats = server.stats()
        assert stats["journal_restores_total"] == 3
        assert stats["journal_entries"] == 0
        assert stats["sched_swap_bytes_host"] == 0
    finally:
        server.close()


def test_revive_under_low_watermark_keeps_shedding(params):
    """Rung 22 x rung 21 watermarks: a checkpointed interactive request
    survives poison/revive in a watermark-tight pool, the revived pool
    still sheds batch arrivals below the low watermark, and the
    restored request completes bit-identical."""
    server = PagedGenerationServer(
        params, CFG, slots=2, pages=16, page_size=4, window=2,
        page_low_watermark=0.95, checkpoint_every=1,
        prefix_cache=False,
    )
    prompt = [5, 9, 2]
    want = reference(params, prompt, 8)
    _arm_kill(server, lambda: len(server._journal) >= 1,
              "injected: died under the low watermark")
    dying_thread = server._thread
    try:
        got, done, errs = _stream_in_background(
            server, prompt, 8, priority="interactive")
        _wait_degraded(server)
        dying_thread.join(timeout=30)
        assert not dying_thread.is_alive()
        assert server.revive() == 1
        # The revived pool keeps the watermark discipline: batch sheds
        # with page terms while the restored request still runs.
        with pytest.raises(ServerOverloaded, match="low watermark"):
            server.submit([1, 2], n_new=4, priority="batch")
        assert done.wait(timeout=60)
        assert not errs, errs
        assert prompt + got == want
        assert server.stats()["sched_shed_total"] >= 1
    finally:
        server.close()


# ---- observability -------------------------------------------------------


def test_capacity_stats_and_metrics(params):
    server = PagedGenerationServer(
        params, CFG, slots=4, pages=32, page_size=4, min_bucket=2,
        page_low_watermark=0.1, page_high_watermark=0.25,
        prefix_cache=False,
    )
    try:
        stats = server.stats()
        assert stats["pages_total"] == 32
        assert stats["slots_total"] == 4
        assert stats["bucket"] == 2
        assert stats["bucket_min"] == 2
        assert stats["page_low_watermark"] == 0.1
        assert stats["page_high_watermark"] == 0.25
        text = render_metrics({"serving": stats})
        for gauge in ("kvedge_serve_pages_total 32",
                      "kvedge_serve_slots_total 4",
                      "kvedge_serve_bucket 2",
                      "kvedge_serve_bucket_min 2",
                      "kvedge_serve_page_low_watermark 0.1",
                      "kvedge_serve_page_high_watermark 0.25"):
            assert gauge in text
    finally:
        server.close()
