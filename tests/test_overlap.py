"""Overlapped-window decode pipeline (SERVING.md rung 16) exactness.

The decode loop dispatches window N+1 on a device-resident carry
BEFORE window N's tokens are read back. The contract is that this is a
pure latency optimization: greedy and sampled token streams are
BIT-IDENTICAL to ``decode.generate`` — at ``window = 1`` (a program of
its own that harvests one token a trip) and at longer windows, under
chunked prefill, mid-window cancellation, and mid-overlap pool
poisoning — where recovery must drain the in-flight window before the
pool reforms; and a window is n calls of the one-step program. All
fixed-seed and fast: these run in the tier-1 gate.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kvedge_tpu.models import TransformerConfig, generate, init_params
from kvedge_tpu.models.kvcache import PagedCacheError, PagedKVCache
from kvedge_tpu.models.serving import (
    PagedGenerationServer,
    RequestCancelled,
)
from kvedge_tpu.runtime.failures import ServingFailure

pytestmark = pytest.mark.overlap

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


def _served(params, fn, **server_kw):
    """``fn(server)`` on a fresh server, closed afterwards."""
    server = PagedGenerationServer(params, CFG, **server_kw)
    try:
        return fn(server)
    finally:
        server.close()


def _single_steps(cache, params, pend, n, active=None):
    """The reference a window is held to: ``n`` calls of the one-step
    logits program, the greedy pick fed back by the host."""
    toks = jnp.asarray(pend, jnp.int32)
    rows = []
    for _ in range(n):
        logits = cache.step(params, toks, active=active)
        toks = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        rows.append(np.asarray(toks))
    return np.stack(rows)


# window = 1 is a compiled program of its own and harvests one token a
# trip; the product's default is 64.
WINDOWS = pytest.mark.parametrize("window", [1, 64], ids=["w1", "w64"])


# ---- bit-identity: the loop == contiguous generate -----------------------


@WINDOWS
def test_greedy_pipelined_matches_generate(params, window):
    requests = [
        ([5, 9, 2], 8),
        ([1, 1, 4, 3, 7, 7], 4),
        ([100, 50], 12),
        ([42], 9),
    ]

    def run(server):
        import threading

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

    pipelined = _served(params, run, slots=3, pages=24, window=window)
    for i, (prompt, n_new) in enumerate(requests):
        assert pipelined[i] == reference(params, prompt, n_new), (
            f"request {i} diverged from contiguous generate"
        )


def test_sampled_pipelined_matches_generate(params):
    """The sampled key schedule fold_in(seed, base+i) is positional, so
    windowing under the pipeline must not move a single sample: the
    server's sampled stream is contiguous generate's."""
    key = jax.random.fold_in(jax.random.PRNGKey(3), 0)
    sampling = (key, jnp.float32(0.8), jnp.float32(0.9))

    def run(server):
        greedy = server.submit([5, 9, 2, 7], n_new=9)
        sampled = server.submit([1, 2, 3, 4], n_new=24,
                                sampling=sampling)
        return greedy, sampled

    greedy, sampled = _served(params, run, slots=2, pages=16)
    assert greedy == reference(params, [5, 9, 2, 7], 9)
    want = generate(
        params, jnp.asarray([[1, 2, 3, 4]], jnp.int32), CFG, n_new=24,
        sampling=(key[None], jnp.float32(0.8), jnp.float32(0.9)),
        sampled=True,
    )
    assert sampled == [int(t) for t in np.asarray(want)[0]]


@WINDOWS
def test_chunked_prefill_pipelined_matches_generate(params, window):
    prompt = list(np.asarray(jax.random.randint(
        jax.random.PRNGKey(2), (11,), 0, 128)).tolist())

    def run(server):
        return server.submit(prompt, n_new=10)

    served = _served(params, run, slots=2, pages=16, prefill_chunk=3,
                     window=window)
    assert served == reference(params, prompt, 10)


def test_mid_window_cancellation_under_overlap(params):
    """A cancel landing while a speculative window is in flight frees
    the slot at the next boundary; the co-tenant that takes the freed
    capacity decodes unperturbed."""
    import time

    server = PagedGenerationServer(params, CFG, slots=1, pages=8)
    try:
        src = server.submit_stream([1, 2, 3], n_new=60)
        next(src)  # windows (plural, pipelined) are in flight now
        src.cancel()
        deadline = time.monotonic() + 30
        while server.stats()["in_flight"] and time.monotonic() < deadline:
            time.sleep(0.01)
        stats = server.stats()
        assert stats["in_flight"] == 0 and stats["free_slots"] == 1
        assert stats["reserved_pages"] == 0
        got = server.submit([4, 5], n_new=3, timeout=5.0)
        assert got == reference(params, [4, 5], 3)
        with pytest.raises(RequestCancelled):
            list(src)
    finally:
        server.close()


# ---- the capped kernel: stops frozen inside the scan ---------------------


def test_capped_window_freezes_finished_rows(params):
    """dispatch_window with per-slot step caps: a row past its cap
    re-emits its last token, stops advancing its length, and writes no
    KV — the live prefix is bit-identical to n single steps."""
    prompts = {0: [5, 9, 2], 2: [7, 7, 7, 7, 7]}  # slot 1 inactive

    def fresh():
        cache = PagedKVCache(CFG, slots=3, pages=24, page_size=4)
        pend = np.zeros((3,), np.int32)
        for slot, prompt in prompts.items():
            cache.admit(slot, len(prompt))
            logits = cache.prefill(
                params, slot, jnp.asarray(prompt, jnp.int32))
            pend[slot] = int(jnp.argmax(logits))
        return cache, pend

    n = 7
    cache_u, pend = fresh()
    full = _single_steps(cache_u, params, pend, n)

    cache_c, pend = fresh()
    caps = np.array([3, 0, 7], np.int32)
    handle = cache_c.dispatch_window(params, jnp.asarray(pend), n,
                                     steps_left=caps)
    capped = np.asarray(cache_c.harvest_window(handle))
    cache_c.drop_carry()

    # The harvest block is [n_steps + 2, slots]: the produced tokens
    # plus the packed [fin, stop_at] finish-bookkeeping rows (rung 23).
    assert capped.shape[0] == n + 2
    # Live prefixes match the one-step program exactly.
    assert capped[:3, 0].tolist() == full[:3, 0].tolist()
    assert capped[:n, 2].tolist() == full[:, 2].tolist()
    # Past its cap the frozen row re-emits its last live token.
    assert all(int(t) == int(capped[2, 0]) for t in capped[3:n, 0])
    # Finish reasons: both active rows froze on their caps (1); the
    # inactive row reports 0 and no stop was configured anywhere.
    assert capped[n].tolist() == [1, 0, 1]
    assert capped[n + 1].tolist() == [0, 0, 0]
    # Lengths advanced by the CAP, not the window.
    assert (cache_c._host_lengths[0]
            == cache_u._host_lengths[0] - (n - 3))
    assert cache_c._host_lengths[2] == cache_u._host_lengths[2]
    assert cache_c._host_lengths[1] == 0


def test_pipeline_carry_matches_single_steps(params):
    """Two pipelined windows — the second dispatched on the device
    carry BEFORE the first is harvested — equal as many single steps
    as their combined length."""
    prompt = [3, 1, 4, 1, 5]

    def fresh():
        cache = PagedKVCache(CFG, slots=2, pages=16, page_size=4)
        cache.admit(0, len(prompt))
        logits = cache.prefill(params, 0, jnp.asarray(prompt, jnp.int32))
        pend = np.zeros((2,), np.int32)
        pend[0] = int(jnp.argmax(logits))
        return cache, pend

    active = np.array([True, False])
    cache_s, pend = fresh()
    stepped = _single_steps(cache_s, params, pend, 8, active=active)

    cache_p, pend = fresh()
    h1 = cache_p.dispatch_window(params, jnp.asarray(pend), 4,
                                 active=active)
    # Second window rides the carry; the host has NOT seen h1 yet.
    h2 = cache_p.dispatch_window(params, None, 4, active=active)
    # Token rows only — each harvest block carries two extra packed
    # finish-bookkeeping rows past its n_steps tokens (rung 23).
    got = np.concatenate([np.asarray(cache_p.harvest_window(h1))[:4],
                          np.asarray(cache_p.harvest_window(h2))[:4]])
    cache_p.drop_carry()
    assert got[:, 0].tolist() == stepped[:, 0].tolist()
    assert cache_p._host_lengths == cache_s._host_lengths


def test_carry_requires_a_window_in_flight(params):
    cache = PagedKVCache(CFG, slots=2, pages=16, page_size=4)
    with pytest.raises(PagedCacheError):
        cache.dispatch_window(params, None, 4)
    cache.drop_carry()  # idempotent on an empty pipeline


# ---- failure mid-overlap: drain, poison, revive --------------------------


def test_poison_mid_overlap_drains_inflight_then_revives(params):
    """A harvest that dies with a second window already dispatched must
    drain the in-flight window (bookkeeping AND the device handle)
    before the pool poisons — and revive() restarts the pipeline from
    host tokens (carry dropped), serving bit-identical afterwards."""
    server = PagedGenerationServer(params, CFG, slots=2, pages=24)
    prompt = [3, 1, 4, 1, 5]
    try:
        assert server.submit(prompt, n_new=4) == reference(
            params, prompt, 4)
        cache = server._cache
        real = cache.harvest_window
        calls = []

        def dying(handle):
            calls.append(1)
            if len(calls) == 2:  # die with window 3 already dispatched
                raise RuntimeError("injected: harvest died mid-overlap")
            return real(handle)

        cache.harvest_window = dying
        dying_thread = server._thread
        with pytest.raises(ServingFailure):
            server.submit(prompt, n_new=40)
        dying_thread.join(timeout=30)
        assert not dying_thread.is_alive()
        assert server.degraded is not None
        # The in-flight window was drained on the way out: no stale
        # bookkeeping survives into recovery.
        assert server._inflight is None
        assert len(calls) >= 3  # the drain forced the in-flight handle
        cache.harvest_window = real
        server.revive()
        assert server.degraded is None
        assert cache._carry is None  # pipeline restarts from host tokens
        assert server.submit(prompt, n_new=6) == reference(
            params, prompt, 6)
    finally:
        server.close()


# ---- observability -------------------------------------------------------


def test_overlap_stats_and_histograms(params):
    server = PagedGenerationServer(params, CFG, slots=2, pages=16)
    try:
        server.submit([5, 9, 2], n_new=8)
        stats = server.stats()
        assert stats["overlap_windows_total"] >= 1
        assert stats["overlap_inflight_depth"] in (0, 1)
        for key in ("window_dispatch_harvest_ms", "window_host_ms",
                    "window_inflight_depth"):
            hist = stats[key]
            assert len(hist["counts"]) == len(hist["edges"]) + 1
            assert hist["count"] == sum(hist["counts"]) >= 1
            assert hist["sum"] >= 0.0
    finally:
        server.close()
