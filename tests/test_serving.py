"""Continuous-batching server (models/serving.py) vs contiguous generate.

Core property: greedy decode through the paged continuous-batching loop
produces exactly the tokens the contiguous :func:`generate` produces for
the same prompt — for every request, regardless of what else is in
flight, when it joined, or how the batch composition changed around it.
That invariance IS continuous batching working.
"""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kvedge_tpu.models import TransformerConfig, generate, init_params
from kvedge_tpu.models.serving import (
    PagedGenerationServer,
    ServerBusy,
    ServerClosed,
)

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


def test_single_request_matches_generate(params):
    server = PagedGenerationServer(params, CFG, slots=2, pages=16)
    try:
        prompt = [5, 9, 2, 7, 1]
        got = server.submit(prompt, n_new=6)
        assert got == reference(params, prompt, 6)
    finally:
        server.close()


def test_concurrent_ragged_requests_each_match_generate(params):
    """Requests with different prompt lengths and budgets, submitted from
    concurrent threads, all share the pool — and each result equals its
    own single-request contiguous decode."""
    server = PagedGenerationServer(params, CFG, slots=3, pages=24)
    requests = [
        ([5, 9, 2], 8),
        ([1, 1, 4, 3, 7, 7], 4),
        ([100, 50], 12),
        ([8, 6, 7, 5, 3, 0, 9], 5),
        ([42], 9),
    ]
    results: dict[int, list[int]] = {}
    errors: list[Exception] = []

    def worker(i, prompt, n_new):
        try:
            results[i] = server.submit(prompt, n_new)
        except Exception as e:  # surface in the main thread
            errors.append(e)

    try:
        threads = [
            threading.Thread(target=worker, args=(i, p, n))
            for i, (p, n) in enumerate(requests)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        assert not errors, errors
        assert len(results) == len(requests)
        for i, (prompt, n_new) in enumerate(requests):
            assert results[i] == reference(params, prompt, n_new), (
                f"request {i} diverged from contiguous generate"
            )
    finally:
        server.close()


def test_mid_stream_admission_does_not_perturb_in_flight(params):
    """A request that joins while another decodes must not change the
    earlier request's tokens (slot isolation under a shared step)."""
    server = PagedGenerationServer(params, CFG, slots=2, pages=24)
    try:
        long_result: list[list[int]] = []
        t = threading.Thread(
            target=lambda: long_result.append(
                server.submit([3, 1, 4, 1, 5], n_new=20)
            )
        )
        t.start()
        short = server.submit([2, 7], n_new=3)  # joins mid-stream
        t.join(timeout=300)
        assert short == reference(params, [2, 7], 3)
        assert long_result[0] == reference(params, [3, 1, 4, 1, 5], 20)
    finally:
        server.close()


def test_request_admitted_mid_window_matches_generate(params):
    """The device-side decode window (kvcache.dispatch_window) must re-sync
    with admission between windows: a request submitted while another is
    mid-decode (windows running — proven by consuming streamed tokens
    first) joins the batch and BOTH results equal their own contiguous
    decodes."""
    server = PagedGenerationServer(params, CFG, slots=2, pages=24)
    try:
        src = server.submit_stream([3, 1, 4, 1, 5], n_new=40)
        first = [next(src) for _ in range(3)]  # windows are in flight now
        short = server.submit([2, 7], n_new=5)  # admitted mid-decode
        rest = list(src)
        long_ref = reference(params, [3, 1, 4, 1, 5], 40)
        assert [3, 1, 4, 1, 5] + first + rest == long_ref
        assert short == reference(params, [2, 7], 5)
    finally:
        server.close()


def test_window_steps_equal_single_steps():
    """A dispatched and harvested window is the SAME program as n
    repeated step()s: same tokens out, same lengths, same page growth."""
    from kvedge_tpu.models.kvcache import PagedKVCache

    cfg = TransformerConfig(
        vocab=64, d_model=16, n_heads=2, n_kv_heads=2, n_layers=2,
        d_ff=32, max_seq=64,
    )
    p = init_params(jax.random.PRNGKey(1), cfg)
    prompts = {0: [5, 9, 2], 2: [7, 7, 7, 7, 7]}  # slot 1 stays inactive

    def fresh():
        cache = PagedKVCache(cfg, slots=3, pages=24, page_size=4)
        pend = np.zeros((3,), np.int32)
        for slot, prompt in prompts.items():
            cache.admit(slot, len(prompt))
            logits = cache.prefill(p, slot, jnp.asarray(prompt, jnp.int32))
            pend[slot] = int(jnp.argmax(logits))
        return cache, pend

    n = 7  # crosses a page boundary (page_size=4) inside the window
    cache_w, pend = fresh()
    window = cache_w.harvest_window(
        cache_w.dispatch_window(p, jnp.asarray(pend), n))[:n]
    cache_w.drop_carry()

    cache_s, toks = fresh()
    singles = []
    for _ in range(n):
        logits = cache_s.step(p, jnp.asarray(toks))
        toks = np.asarray(jnp.argmax(logits, axis=-1), np.int32)
        singles.append(toks.copy())

    for slot in prompts:
        assert window[:, slot].tolist() == [s[slot] for s in singles], slot
    assert cache_w._host_lengths == cache_s._host_lengths
    assert cache_w.free_pages() == cache_s.free_pages()
    # Inactive slot untouched either way.
    assert cache_w._host_lengths[1] == 0


def test_chunked_prefill_matches_whole_prefill():
    """kvcache.prefill_chunk: a prompt landed in chunks must leave the
    cache in the same state as one whole-prompt prefill — same final
    logits, then same decode tokens."""
    from kvedge_tpu.models.kvcache import PagedKVCache

    cfg = TransformerConfig(
        vocab=64, d_model=16, n_heads=2, n_kv_heads=2, n_layers=2,
        d_ff=32, max_seq=64,
    )
    p = init_params(jax.random.PRNGKey(1), cfg)
    prompt = list(
        np.asarray(jax.random.randint(
            jax.random.PRNGKey(2), (11,), 0, 64)).tolist()
    )

    def decode_from(cache, logits, n):
        toks = [int(jnp.argmax(logits))]
        pend = np.zeros((2,), np.int32)
        for _ in range(n - 1):
            pend[0] = toks[-1]
            step_logits = cache.step(p, jnp.asarray(pend))
            toks.append(int(jnp.argmax(step_logits[0])))
        return toks

    whole = PagedKVCache(cfg, slots=2, pages=16, page_size=4)
    whole.admit(0, len(prompt))
    logits_w = whole.prefill(p, 0, jnp.asarray(prompt, jnp.int32))
    want = decode_from(whole, logits_w, 6)

    chunked = PagedKVCache(cfg, slots=2, pages=16, page_size=4)
    chunked.admit(0, len(prompt))
    off = 0
    for size in (3, 3, 3, 2):  # 11 tokens, uneven final chunk
        piece = jnp.asarray(prompt[off:off + size], jnp.int32)
        logits_c = chunked.prefill_chunk(p, 0, piece, off)
        off += size
    got = decode_from(chunked, logits_c, 6)
    assert got == want


def test_chunked_admission_equivalence_and_interleaving(params):
    """Serving with a tiny prefill chunk: tokens still equal the
    contiguous decode, and an in-flight request keeps DECODING while a
    long prompt's chunks land (the admission lock releases between
    chunks; the decode loop's active mask protects the half-prefilled
    slot)."""
    import time

    server = PagedGenerationServer(params, CFG, slots=2, pages=24,
                                   prefill_chunk=2)
    try:
        # Equivalence with chunked admission (prompt of 7 -> 4 chunks).
        prompt = [5, 9, 2, 7, 1, 3, 3]
        assert server.submit(prompt, n_new=6) == reference(
            params, prompt, 6
        )

        # Interleaving: request A streams with a large budget; during
        # B's chunked prefill (each chunk artificially slowed to 0.15s),
        # the decode loop must keep stepping A — by the time B's submit
        # returns, A's tokens are BUFFERED in its stream queue. Under
        # the old whole-prefill-under-the-lock behavior A would be
        # frozen for the entire admission and have almost nothing.
        src = server.submit_stream([3, 1, 4], n_new=61)
        a_tokens = [next(src)]
        real_chunk = server._cache.prefill_chunk

        def slow_chunk(*args, **kwargs):
            time.sleep(0.15)
            return real_chunk(*args, **kwargs)

        server._cache.prefill_chunk = slow_chunk
        long_prompt = [1, 2, 3, 4, 5, 6, 7, 8, 9, 10]  # 5 slow chunks
        got_b = server.submit(long_prompt, n_new=3)
        server._cache.prefill_chunk = real_chunk
        buffered = src._req.stream.qsize()
        assert buffered >= 30, (
            f"only {buffered} of A's tokens buffered during B's slowed "
            "admission — the decode loop did not interleave"
        )
        a_tokens += list(src)
        assert len(a_tokens) == 61
        assert [3, 1, 4] + a_tokens == reference(params, [3, 1, 4], 61)
        assert got_b == reference(params, long_prompt, 3)
    finally:
        server.close()


def test_slot_reuse_after_release(params):
    server = PagedGenerationServer(params, CFG, slots=1, pages=8)
    try:
        for prompt in ([9, 9], [1, 2, 3], [64]):
            assert server.submit(prompt, n_new=4) == reference(
                params, prompt, 4
            )
        stats = server.stats()
        assert stats["in_flight"] == 0
        assert stats["free_slots"] == 1
        assert stats["reserved_pages"] == 0
        assert stats["free_pages"] == 8
    finally:
        server.close()


def test_admission_control_rejects_impossible_and_times_out(params):
    server = PagedGenerationServer(params, CFG, slots=1, pages=3,
                                   page_size=16)
    try:
        with pytest.raises(ValueError, match="max_seq"):
            server.submit([1] * 60, n_new=10)
        with pytest.raises(ValueError, match="pool size"):
            # 50 + 14 = 64 positions = 4 pages > the 3-page pool
            server.submit([1] * 50, n_new=14)
        # Occupy the only slot, then a second submit must time out.
        # Two determinism measures: (a) the occupier's decode is
        # artificially slowed — a warm 30-token budget finishes in
        # milliseconds, faster than any competitor timeout; (b) every
        # program the occupier needs is COMPILED FIRST by an identical
        # request. Without the warmup, a loaded machine spends tens of
        # seconds compiling the first window while the decode loop holds
        # the lock — the competitor's expired wait can then only recheck
        # at 2-3 widely-spaced window boundaries and can lose every
        # lock race until the occupier finishes (observed flake).
        import time as time_mod

        server.submit([9, 9, 9], n_new=44)  # compile prefill + windows

        real_dispatch = server._cache.dispatch_window

        def slow_dispatch(*args, **kwargs):
            # Sleep > the competitor's full timeout: even a single
            # window outlasts it, so scheduling jitter cannot let the
            # occupier finish early.
            time_mod.sleep(0.25)
            return real_dispatch(*args, **kwargs)

        server._cache.dispatch_window = slow_dispatch
        t = threading.Thread(
            target=lambda: server.submit([1, 2, 3], n_new=44)
        )
        t.start()
        deadline = time_mod.monotonic() + 30
        # Dirty read on purpose: stats() takes the server lock, which
        # the slowed decode loop holds ~continuously, so the poll
        # itself could lose the lock race for most of the occupier's
        # lifetime and start the competitor too late to ever observe
        # an occupied boundary (seen with the overlapped loop). A
        # lock-free peek at _active starts the competitor immediately.
        while (not server._active
               and time_mod.monotonic() < deadline):
            time_mod.sleep(0.005)  # occupier must hold the slot first
        with pytest.raises(ServerBusy):
            server.submit([4, 5], n_new=2, timeout=0.2)
        t.join(timeout=300)
        server._cache.dispatch_window = real_dispatch
    finally:
        server.close()


def test_cancel_frees_capacity_before_budget_exhaustion(params):
    """VERDICT r3 #5a: a cancelled stream releases its slot and pages at
    the next decode boundary — well before its reserved budget runs out
    — so a waiting request admits immediately."""
    import time

    server = PagedGenerationServer(params, CFG, slots=1, pages=8)
    try:
        src = server.submit_stream([1, 2, 3], n_new=60)
        next(src)  # decoding is under way
        src.cancel()
        deadline = time.monotonic() + 30
        while server.stats()["in_flight"] and time.monotonic() < deadline:
            time.sleep(0.01)
        stats = server.stats()
        assert stats["in_flight"] == 0 and stats["free_slots"] == 1
        assert stats["reserved_pages"] == 0
        # The freed capacity is genuinely usable, and the result is
        # unperturbed by the cancelled co-tenant having left early.
        got = server.submit([4, 5], n_new=3, timeout=5.0)
        assert got == reference(params, [4, 5], 3)
        # The cancelled consumer's iterator surfaces the cancellation.
        from kvedge_tpu.models.serving import RequestCancelled

        with pytest.raises(RequestCancelled):
            list(src)
    finally:
        server.close()


def test_drain_close_finishes_accepted_requests(params):
    """VERDICT r3 #5b: close(drain=True) stops admission immediately but
    every accepted request decodes out its full budget."""
    server = PagedGenerationServer(params, CFG, slots=2, pages=16)
    results: dict[int, list[int]] = {}
    errors: list[Exception] = []

    def worker(i, prompt, n_new):
        try:
            results[i] = server.submit(prompt, n_new)
        except Exception as e:
            errors.append(e)

    reqs = [([5, 9, 2], 20), ([1, 1, 4], 25)]
    threads = [
        threading.Thread(target=worker, args=(i, p, n))
        for i, (p, n) in enumerate(reqs)
    ]
    for t in threads:
        t.start()
    import time

    deadline = time.monotonic() + 30
    while (server.stats()["in_flight"] < 2
           and time.monotonic() < deadline):
        time.sleep(0.005)  # both accepted before the drain begins
    server.close(drain=True)
    for t in threads:
        t.join(timeout=60)
    assert not errors, errors
    for i, (prompt, n_new) in enumerate(reqs):
        assert results[i] == reference(params, prompt, n_new), i
    # Admission is closed from the drain call onward.
    with pytest.raises(ServerClosed):
        server.submit([7], n_new=2)


def test_prefix_sharing_exact_and_skips_shared_prefill(params):
    """Two requests with a common page-aligned prefix: the second
    prefills ONLY its suffix (observed via prefill_chunk call counts),
    and both results equal their own contiguous decodes — reuse is
    exact, including for a sampled request sharing the greedy request's
    prefix pages."""
    import jax

    server = PagedGenerationServer(params, CFG, slots=2, pages=24,
                                   page_size=4, prefill_chunk=4)
    calls: list = []
    real_chunk = server._cache.prefill_chunk

    def counting_chunk(params_, slot, tokens, offset):
        calls.append((int(offset), int(tokens.shape[0])))
        return real_chunk(params_, slot, tokens, offset)

    server._cache.prefill_chunk = counting_chunk
    try:
        base = [7, 3, 9, 1, 5, 5, 2, 8]  # two full 4-token pages
        first = server.submit(base + [4, 6], n_new=4)
        assert first == reference(params, base + [4, 6], 4)
        stats = server.stats()
        # 1-, 2-, and 3-page prefixes: finish registers the COMMITTED
        # tokens (prompt + generated, 14 here), not just the prompt.
        assert stats["prefix_entries"] == 3
        assert stats["prefix_hits"] == 0

        calls.clear()
        second = server.submit(base + [9, 9, 9], n_new=4)
        assert second == reference(params, base + [9, 9, 9], 4)
        # Only the 3-token suffix prefilled: one chunk at offset 8.
        assert calls == [(8, 3)], calls
        stats = server.stats()
        assert stats["prefix_hits"] == 1
        assert stats["prefix_tokens_saved"] == 8

        # Sampled request on the same prefix: prefix K/V are
        # sampling-independent, so tokens match a fresh server that
        # never shared anything.
        calls.clear()
        key = jax.random.PRNGKey(42)
        sampled = server.submit(
            base + [2], n_new=5,
            sampling=(key, jnp.float32(0.8), jnp.float32(0.9)),
        )
        assert calls == [(8, 1)], calls
        fresh = PagedGenerationServer(params, CFG, slots=2, pages=24,
                                      page_size=4, prefix_cache=False)
        try:
            want = fresh.submit(
                base + [2], n_new=5,
                sampling=(key, jnp.float32(0.8), jnp.float32(0.9)),
            )
        finally:
            fresh.close()
        assert sampled == want
    finally:
        server.close()


def test_prefix_pins_evict_under_pool_pressure(params):
    """Registry pins must never block an admission that fits its
    reservation: a new request that needs the pinned pages evicts them
    LRU and proceeds."""
    server = PagedGenerationServer(params, CFG, slots=1, pages=6,
                                   page_size=4)
    try:
        a = [1, 2, 3, 4, 5, 6, 7, 8]  # 2 full committed pages
        assert server.submit(a, n_new=4) == reference(params, a, 4)
        # Committed length is 11 (the final emitted token is never fed
        # back), so 2 full pages register.
        assert server.stats()["prefix_entries"] == 2
        # After A's release the registry pins its 2 committed pages, so
        # 4 of 6 pages are free. B (unrelated prompt) needs
        # ceil((8+12)/4) = 5 pages: admission must evict A's pins and
        # proceed.
        b = [9, 9, 8, 8, 7, 7, 6, 6]
        assert server.submit(b, n_new=12) == reference(params, b, 12)
        # A's prefixes were evicted (a lookup for them finds nothing)...
        _, _, shared, _ = server._prefix_lookup(a + [0])
        assert shared == 0
        # ...and B's own prefixes (19 committed tokens, 4 full pages)
        # registered after it completed.
        assert server.stats()["prefix_entries"] == 4
        _, _, shared, _ = server._prefix_lookup(b + [0])
        assert shared == 8
    finally:
        server.close()


def test_grow_under_registry_pressure_evicts_instead_of_poisoning(params):
    """Registry pins live outside every request's reservation, so a
    mid-decode grow can find the free list empty even though the grow
    is within its own reserved budget. The cache's pressure-relief
    callback must evict pins and continue — before the fix this raised
    'pool exhausted mid-decode' in the decode loop, failing every
    in-flight request and closing the server."""
    import time

    # window=page_size pins the r3-era window cadence: pages must grow
    # GRADUALLY between windows for the C-cycles to pin pages in the
    # gaps — the wide default window would front-load B's allocation
    # and never reach the pressure this test exists to exercise.
    server = PagedGenerationServer(params, CFG, slots=2, pages=18,
                                   page_size=4, window=4)
    # Compile the C-cycles' prefill first: compiled inside the first
    # cycle it would hand B seconds in which to finish unpressed.
    server.submit([9] * 8, n_new=4)
    relief_calls = [0]
    orig_relief = server._relieve_pool_pressure_locked

    def counting_relief(needed=1):
        relief_calls[0] += 1
        return orig_relief(needed)

    server._cache.pressure_relief = counting_relief
    real_window = server._cache.dispatch_window

    def slow_window(*args, **kwargs):
        time.sleep(0.25)  # keep B in flight while C-cycles pin pages
        return real_window(*args, **kwargs)

    server._cache.dispatch_window = slow_window
    # References first: compiling one between two C-cycles would hand
    # B seconds in which to finish before the later cycles pin pages.
    cycles = [[10 + i] * 8 for i in range(4)]
    c_want = [reference(params, c, 4) for c in cycles]
    b_want = reference(params, [3, 1, 4, 1], 56)
    try:
        b_result: list = []
        b_errors: list = []

        def b_worker():
            try:
                b_result.append(server.submit([3, 1, 4, 1], n_new=56))
            except Exception as e:
                b_errors.append(e)

        t = threading.Thread(target=b_worker)
        t.start()
        deadline = time.monotonic() + 30
        while (server.stats()["in_flight"] < 1
               and time.monotonic() < deadline):
            time.sleep(0.005)
        # Distinct 2-page prompts complete while B decodes; each
        # completion pins pages the registry holds beyond any
        # reservation. B's later grows must reclaim them.
        for c, want in zip(cycles, c_want):
            assert server.submit(c, n_new=4) == want
        t.join(timeout=180)
        assert not b_errors, b_errors
        assert b_result[0] == b_want
        assert relief_calls[0] >= 1, (
            "the scenario never exercised pool-pressure relief — "
            "tighten it"
        )
        # The server survived: a fresh request still serves.
        assert server.submit([9, 9], n_new=2) == reference(
            params, [9, 9], 2
        )
    finally:
        server._cache.dispatch_window = real_window
        server.close()


def test_prefix_cache_disabled_shares_nothing(params):
    server = PagedGenerationServer(params, CFG, slots=2, pages=24,
                                   page_size=4, prefix_cache=False)
    try:
        a = [1, 2, 3, 4, 5, 6, 7, 8]
        assert server.submit(a, n_new=3) == reference(params, a, 3)
        stats = server.stats()
        assert stats["prefix_entries"] == 0
        assert stats["free_pages"] == 24  # nothing pinned after release
    finally:
        server.close()


def test_drain_during_chunked_prefill_serves_the_request(params):
    """A drain that begins while an admission's chunks are still landing
    must still serve that request (it was accepted — its slot is
    granted): the decode loop may not exit while a prefill is in
    flight, or the waiter would hang on a request no loop serves."""
    import time

    server = PagedGenerationServer(params, CFG, slots=2, pages=16,
                                   prefill_chunk=1)
    real_chunk = server._cache.prefill_chunk

    def slow_chunk(*args, **kwargs):
        time.sleep(0.05)
        return real_chunk(*args, **kwargs)

    server._cache.prefill_chunk = slow_chunk
    result: list = []
    errors: list = []

    def worker():
        try:
            result.append(server.submit([5, 9, 2, 7, 1, 3], n_new=4))
        except Exception as e:
            errors.append(e)

    t = threading.Thread(target=worker)
    t.start()
    deadline = time.monotonic() + 30
    while server._prefilling == 0 and time.monotonic() < deadline:
        time.sleep(0.005)
    assert server._prefilling == 1  # drain begins MID-prefill
    server.close(drain=True)
    t.join(timeout=60)
    assert not errors, errors
    assert result and result[0] == reference(params, [5, 9, 2, 7, 1, 3], 4)


def test_serving_soak_randomized(params):
    """Round-4 machinery under randomized concurrent load: windows,
    chunked prefill, prefix sharing, sampling, streams, cancels, and a
    drain-close — every completed request must equal its contiguous
    reference, every cancelled stream must have produced a prefix of
    its reference, and the pool accounting must return to a consistent
    idle state. Fixed seed: failures reproduce."""
    import random
    import time

    rng = random.Random(0)
    server = PagedGenerationServer(params, CFG, slots=3, pages=40,
                                   page_size=4, prefill_chunk=3)
    # A tiny alphabet + shared stems make prefix-cache hits frequent.
    stems = [[7, 3, 9, 1], [2, 2, 5, 8]]
    failures: list = []

    def one_request(i):
        try:
            stem = rng.choice(stems) * rng.randint(1, 2)
            prompt = stem + [rng.randrange(CFG.vocab)
                             for _ in range(rng.randint(1, 4))]
            n_new = rng.randint(1, 8)
            mode = rng.random()
            if mode < 0.25:  # sampled
                seed_key = jax.random.PRNGKey(i)
                sampling = (seed_key, jnp.float32(0.7), jnp.float32(0.9))
                got = server.submit(prompt, n_new, sampling=sampling)
                want = generate(
                    params, jnp.asarray([prompt], jnp.int32), CFG,
                    n_new=n_new,
                    sampling=(seed_key[None], jnp.float32(0.7),
                              jnp.float32(0.9)),
                    sampled=True,
                )
                want = [int(t) for t in np.asarray(want)[0]]
                if got != want:
                    failures.append((i, "sampled mismatch", got, want))
            elif mode < 0.5:  # streamed, maybe cancelled early
                src = server.submit_stream(prompt, n_new)
                take = rng.randint(0, n_new)
                got = []
                for _ in range(take):
                    got.append(next(src))
                if take < n_new and rng.random() < 0.5:
                    src.cancel()
                else:
                    for tok in src:
                        got.append(tok)
                want = reference(params, prompt, n_new)
                if prompt + got != want[:len(prompt) + len(got)]:
                    failures.append((i, "stream prefix mismatch",
                                     got, want))
            else:  # plain greedy
                got = server.submit(prompt, n_new)
                if got != reference(params, prompt, n_new):
                    failures.append((i, "greedy mismatch", got))
        except ServerBusy:
            pass  # a capacity refusal is a legal outcome under load
        except Exception as e:
            failures.append((i, "error", repr(e)))

    threads = [threading.Thread(target=one_request, args=(i,))
               for i in range(24)]
    # Staggered starts: admissions overlap decodes, prefills, releases.
    for t in threads:
        t.start()
        time.sleep(0.01)
    for t in threads:
        t.join(timeout=300)
    assert not failures, failures[:5]

    server.close(drain=True)
    stats = server.stats()
    assert stats["in_flight"] == 0
    assert stats["reserved_pages"] == 0
    # Refcount integrity: every page is free (ref 0) or held only by
    # registry pins; the pinned count matches what the trie holds.
    cache = server._cache
    pinned_pages = {
        p for e in server._prefix_entry_nodes.values() for p in e["pages"]
    }
    for page, refs in enumerate(cache._refs):
        if page in pinned_pages:
            assert refs >= 1, (page, refs)
        else:
            assert refs == 0, (page, refs)
    assert stats["free_pages"] + len(pinned_pages) == 40


def test_close_fails_pending_requests(params):
    server = PagedGenerationServer(params, CFG, slots=1, pages=8)
    errors: list[Exception] = []

    def worker():
        try:
            server.submit([1, 2, 3], n_new=40)
        except Exception as e:
            errors.append(e)

    t = threading.Thread(target=worker)
    t.start()
    import time

    time.sleep(0.5)  # let it get in flight
    server.close()
    t.join(timeout=60)
    # Either it finished before close landed, or it failed loudly.
    assert not errors or isinstance(errors[0], ServerClosed)


def test_submit_stream_yields_same_tokens_incrementally(params):
    server = PagedGenerationServer(params, CFG, slots=2, pages=16)
    try:
        prompt = [5, 9, 2, 7]
        want = reference(params, prompt, 6)
        got = list(server.submit_stream(prompt, n_new=6))
        assert prompt + got == want
        assert len(got) == 6
    finally:
        server.close()


def test_submit_stream_concurrent_with_blocking_request(params):
    server = PagedGenerationServer(params, CFG, slots=2, pages=24)
    try:
        blocking: list[list[int]] = []
        t = threading.Thread(
            target=lambda: blocking.append(
                server.submit([3, 1, 4], n_new=10)
            )
        )
        t.start()
        streamed = list(server.submit_stream([2, 7, 7], n_new=8))
        t.join(timeout=300)
        assert [2, 7, 7] + streamed == reference(params, [2, 7, 7], 8)
        assert blocking[0] == reference(params, [3, 1, 4], 10)
    finally:
        server.close()


# ---- prefix-cache persistence (round 4) ----------------------------------


def test_prefix_cache_dump_load_round_trip(params, tmp_path):
    """A dumped registry re-pins into a fresh server: the first request
    after the reload shares the persisted prefix immediately (zero
    recomputation for the cached pages) and decodes exactly the tokens
    a cold server would."""
    path = str(tmp_path / "prefix.npz")
    base = [7, 3, 9, 1, 5, 5, 2, 8]  # two full 4-token pages
    server = PagedGenerationServer(params, CFG, slots=2, pages=24,
                                   page_size=4)
    try:
        warm = server.submit(base + [4, 6], n_new=4)
        # 13 committed tokens (prompt + 3 fed-back generated): 1-, 2-,
        # and 3-page prefixes registered and dumped.
        assert server.dump_prefix_cache(path, "fp-1") == 3
    finally:
        server.close()

    revived = PagedGenerationServer(params, CFG, slots=2, pages=24,
                                    page_size=4, prefill_chunk=4)
    calls: list = []
    real_chunk = revived._cache.prefill_chunk

    def counting_chunk(params_, slot, tokens, offset):
        calls.append((int(offset), int(tokens.shape[0])))
        return real_chunk(params_, slot, tokens, offset)

    revived._cache.prefill_chunk = counting_chunk
    try:
        assert revived.load_prefix_cache(path, "fp-1") == 3
        stats = revived.stats()
        assert stats["prefix_entries"] == 3
        got = revived.submit(base + [4, 6], n_new=4)
        assert got == warm == reference(params, base + [4, 6], 4)
        # 9 tokens came off the persisted pages: the 8 full-block
        # tokens PLUS one token of the 3-page entry's partial last
        # block ([4, 6, ...] — capped at len(prompt)-1), which the
        # admission COW-copied before prefilling the final token.
        assert calls == [(9, 1)], calls
        assert revived.stats()["prefix_hits"] == 1
        assert revived.stats()["prefix_tokens_saved"] == 9
        assert revived.stats()["prefix_cow_copies"] == 1
    finally:
        revived.close()


def test_prefix_cache_load_rejects_stale_and_respects_capacity(
        params, tmp_path):
    """A fingerprint mismatch ignores the file wholesale (K/V from
    other params must never serve); a pool too small for the dump loads
    ancestors-first and stops instead of evicting or failing."""
    path = str(tmp_path / "prefix.npz")
    server = PagedGenerationServer(params, CFG, slots=2, pages=24,
                                   page_size=4)
    try:
        server.submit([1, 1, 1, 1, 9], n_new=4)           # 2 entries
        server.submit([2, 2, 2, 2, 3, 3, 3, 3, 9], n_new=4)  # 3 entries
        assert server.dump_prefix_cache(path, "fp-1") == 5
    finally:
        server.close()

    stale = PagedGenerationServer(params, CFG, slots=2, pages=24,
                                  page_size=4)
    try:
        assert stale.load_prefix_cache(path, "fp-OTHER") == 0
        assert stale.stats()["prefix_entries"] == 0
    finally:
        stale.close()

    # 2 pages total: the two 1-page entries load (ancestors first); the
    # 2-page entry's fresh page finds the free list empty and the load
    # STOPS — it never evicts what it just pinned and never fails.
    tiny = PagedGenerationServer(params, CFG, slots=1, pages=2,
                                 page_size=4)
    try:
        assert tiny.load_prefix_cache(path, "fp-1") == 2
        stats = tiny.stats()
        assert stats["prefix_entries"] == 2
        assert stats["free_pages"] == 0
        # The surviving entries still serve: this request shares the
        # [2,2,2,2] page, and its admission evicts the OTHER pin (LRU,
        # never the matched entry) to cover its private budget — the
        # live eviction discipline applies to revived pins unchanged.
        got = tiny.submit([2, 2, 2, 2, 5], n_new=3)
        assert got == reference(params, [2, 2, 2, 2, 5], 3)
        assert tiny.stats()["prefix_hits"] == 1
    finally:
        tiny.close()


def test_prefix_cache_load_is_boot_time_only(params, tmp_path):
    path = str(tmp_path / "prefix.npz")
    server = PagedGenerationServer(params, CFG, slots=2, pages=24,
                                   page_size=4)
    try:
        server.submit([7, 3, 9, 1, 5], n_new=4)
        assert server.dump_prefix_cache(path, "fp-1") == 2
        # Live registry present: a (second) load must refuse — it would
        # double-pin shared pages.
        assert server.load_prefix_cache(path, "fp-1") == 0
    finally:
        server.close()


# ---- wide windows --------------------------------------------------------


def test_multipage_window_matches_generate(params):
    """Windows wider than a page (the r5 serving_window knob): a greedy
    request whose device windows span multiple pages per dispatch still
    matches contiguous decode exactly, and the loop really took
    multi-page windows (window calls < token count / page_size would
    prove amortization, asserted via call spying)."""
    server = PagedGenerationServer(params, CFG, slots=2, pages=32,
                                   page_size=4, window=16)
    windows: list[int] = []
    real_dispatch = server._cache.dispatch_window

    def spy_dispatch(params_, tokens, n_steps, active=None,
                     steps_left=None, stop_tokens=None):
        windows.append(n_steps)
        return real_dispatch(params_, tokens, n_steps, active=active,
                             steps_left=steps_left,
                             stop_tokens=stop_tokens)

    server._cache.dispatch_window = spy_dispatch
    try:
        prompt = [11, 3, 8]
        got = server.submit(prompt, n_new=40)
        assert got == reference(params, prompt, 40)
        # 39 decode steps (pending token emits free): with window=16
        # the plan is 16+16+4+2+1 — at least one window spans 4 pages.
        assert max(windows) == 16
        assert len(windows) <= 6
    finally:
        server._cache.dispatch_window = real_dispatch
        server.close()


def test_admission_joins_between_wide_windows(params):
    """A request admitted while another decodes through wide windows
    joins at a window boundary and both match their references — the
    serving_window tradeoff (admission waits at most one window) must
    not cost correctness."""
    server = PagedGenerationServer(params, CFG, slots=2, pages=32,
                                   page_size=4, window=16)
    results: dict = {}
    errors: list = []

    def worker(name, prompt, n_new):
        try:
            results[name] = server.submit(prompt, n_new)
        except Exception as e:
            errors.append((name, e))

    try:
        a = threading.Thread(target=worker, args=("a", [2, 4, 6], 48))
        a.start()
        deadline = __import__("time").monotonic() + 30
        while (server.stats()["in_flight"] < 1
               and __import__("time").monotonic() < deadline):
            __import__("time").sleep(0.005)
        b = threading.Thread(target=worker, args=("b", [9, 1], 20))
        b.start()
        a.join(timeout=300)
        b.join(timeout=300)
        assert not errors, errors
        assert results["a"] == reference(params, [2, 4, 6], 48)
        assert results["b"] == reference(params, [9, 1], 20)
    finally:
        server.close()


def test_periodic_dump_survives_sigkill(params, tmp_path):
    """The kill drill (VERDICT r4 #10): a server with periodic prefix
    persistence is SIGKILL'd mid-serve — no drain, no close — and a
    fresh server still re-pins the dumped prefixes and reuses them
    exactly."""
    import os
    import signal
    import subprocess
    import sys
    import time

    path = str(tmp_path / "prefix-cache.npz")
    script = f"""
import jax
jax.config.update('jax_platforms', 'cpu')
import time
from kvedge_tpu.models import TransformerConfig, init_params
from kvedge_tpu.models.serving import PagedGenerationServer

cfg = TransformerConfig(vocab=128, d_model=32, n_heads=4, n_kv_heads=2,
                        n_layers=2, d_ff=64, max_seq=64)
params = init_params(jax.random.PRNGKey(0), cfg)
server = PagedGenerationServer(params, cfg, slots=2, pages=24,
                               page_size=4)
server.start_prefix_persistence({path!r}, "kill-drill", interval=0.2)
server.submit([7, 3, 9, 1, 5, 5, 2, 8], n_new=4)
print("SERVING", flush=True)
while True:  # hold the pool live until the parent SIGKILLs us
    time.sleep(1)
"""
    proc = subprocess.Popen(
        [sys.executable, "-c", script],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
    )
    try:
        deadline = time.monotonic() + 240
        while not os.path.exists(path):
            assert proc.poll() is None, (
                "server process died before dumping: "
                + proc.communicate()[1]
            )
            assert time.monotonic() < deadline, "no dump within deadline"
            time.sleep(0.1)
    finally:
        if proc.poll() is None:
            proc.send_signal(signal.SIGKILL)
        proc.communicate()

    fresh = PagedGenerationServer(params, CFG, slots=2, pages=24,
                                  page_size=4)
    try:
        n = fresh.load_prefix_cache(path, "kill-drill")
        assert n == 2  # both page-aligned prefixes of the 8-token prompt
        base = [7, 3, 9, 1, 5, 5, 2, 8]
        got = fresh.submit(base + [4, 6], n_new=6)
        assert got == reference(params, base + [4, 6], 6)
        assert fresh.stats()["prefix_hits"] == 1
    finally:
        fresh.close()


