"""Overlapped-window decode pipeline (SERVING.md rung 16) exactness.

The decode loop dispatches window N+1 on a device-resident carry
BEFORE window N's tokens are read back. The contract is that this is a
pure latency optimization: greedy and sampled token streams are
BIT-IDENTICAL to ``decode.generate`` — at ``window = 1`` (a program of
its own that harvests one token a trip) and at longer windows, under
chunked prefill, mid-window cancellation, and mid-overlap pool
poisoning — where recovery must drain the in-flight window before the
pool reforms; and a window is n calls of the one-step program. An
admission does not drain the pipeline (ISSUE 37): a newcomer joins the
next overlapped window from the host's row, beside rows on the carry,
and its tokens are generate's all the same; what still forces a
boundary is counted by cause. All fixed-seed and fast: these run in
the tier-1 gate.
"""

import dataclasses
import functools
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kvedge_tpu.models import TransformerConfig, generate, init_params
from kvedge_tpu.models import kvcache as kvcache_mod
from kvedge_tpu.models.kvcache import PagedCacheError, PagedKVCache
from kvedge_tpu.models.serving import (
    PagedGenerationServer,
    RequestCancelled,
    ServerClosed,
)
from kvedge_tpu.runtime.failures import DeviceOpTimeout, ServingFailure
from kvedge_tpu.runtime.tracing import LOCK_HOLDERS
from kvedge_tpu.runtime.status import render_metrics

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


# ---- an admission joins on the carry (ISSUE 37) --------------------------

# Room for a request that outlives every newcomer's admission.
LONG_CFG = dataclasses.replace(CFG, max_seq=256)
LONG = ([3, 1, 4], 200)
KEY = jax.random.fold_in(jax.random.PRNGKey(3), 0)
SAMPLING = (KEY, jnp.float32(0.8), jnp.float32(0.9))
NEWCOMERS = [
    ([5, 9, 2, 7, 1, 1, 4], 21, None),
    ([1, 2, 3, 4], 24, SAMPLING),
    ([100, 50, 7, 7, 7, 2, 9, 9, 4, 1, 6], 12, None),
]


def long_reference(params, prompt, n_new, sampling=None):
    kw = {}
    if sampling is not None:
        kw = {"sampling": (sampling[0][None],) + sampling[1:],
              "sampled": True}
    out = generate(params, jnp.asarray([prompt], jnp.int32), LONG_CFG,
                   n_new=n_new, **kw)
    return [int(t) for t in np.asarray(out)[0]]


def _slowed(server, seconds):
    """Every harvest takes ``seconds`` longer, lock held as the loop
    holds it: a window is then in flight for as long as it takes a
    newcomer to be admitted, on any machine."""
    real = server._cache.harvest_window

    def slow(handle):
        time.sleep(seconds)
        return real(handle)

    server._cache.harvest_window = slow


def _join_run(params, newcomers, *, slow_s, **server_kw):
    """The long request streams; once its first token is out (windows
    are in flight from then on) the newcomers are submitted at once.
    Returns everyone's tokens and the server's last stats. Each
    newcomer has been served alone before (its programs are compiled:
    its admission then takes milliseconds of the long request's
    second or more), with the same tokens."""
    server = PagedGenerationServer(params, LONG_CFG, **server_kw)
    try:
        alone = [server.submit(prompt, n_new, sampling=sampling)
                 for prompt, n_new, sampling in newcomers]
        _slowed(server, slow_s)
        stream = server.submit_stream(*LONG)
        first = next(stream)
        got: dict = {}
        errors: list = []

        def worker(i, prompt, n_new, sampling):
            try:
                got[i] = server.submit(prompt, n_new, sampling=sampling)
            except Exception as e:
                errors.append(e)

        threads = [threading.Thread(target=worker, args=(i, *req))
                   for i, req in enumerate(newcomers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        assert not errors, errors
        assert [got[i] for i in range(len(newcomers))] == alone
        got["long"] = LONG[0] + [first] + list(stream)
        return got, server.stats()
    finally:
        server.close()


@pytest.mark.parametrize("chunk", [0, 3], ids=["whole", "chunked"])
@pytest.mark.parametrize("window", [1, 4, 16])
def test_newcomers_join_the_pipeline_and_match_generate(params, window,
                                                        chunk):
    """Greedy and sampled newcomers admitted while the long request's
    windows are in flight: nobody's tokens depend on which window a
    row joined, no admission collapsed the pipeline, and every
    newcomer entered an overlapped window from the host's row."""
    got, stats = _join_run(
        params, NEWCOMERS, slow_s=1.0 / (LONG[1] // window), slots=4,
        pages=80, window=window, prefill_chunk=chunk)
    assert got["long"] == long_reference(params, *LONG)
    for i, (prompt, n_new, sampling) in enumerate(NEWCOMERS):
        assert got[i] == long_reference(params, prompt, n_new, sampling), (
            f"newcomer {i} diverged from contiguous generate")
    assert stats["pipeline_joins_total"] == len(NEWCOMERS)
    assert not any(stats["pipeline_collapses"].values())
    # One window was handed to an empty device, the long request's
    # first; every other one, the newcomers' first among them, was
    # queued behind a window in flight.
    # (the three served alone beforehand took a boundary each.)
    depth = stats["window_inflight_depth"]
    assert depth["counts"][0] == 1 + len(NEWCOMERS)
    assert depth["sum"] == depth["count"] - depth["counts"][0]


def test_a_joined_row_rides_the_second_window_of_a_pair(params):
    """The cache's half, without a server: window two is dispatched
    before window one is read, a row that sat window one out states
    its token and the other rides the carry; both equal as many single
    steps. Done twice over fresh pools, the second time traces no
    window program anew."""
    prompts = {0: [3, 1, 4, 1, 5], 1: [2, 7]}

    def fresh():
        cache = PagedKVCache(CFG, slots=2, pages=16, page_size=4)
        pend = np.zeros((2,), np.int32)
        for slot, prompt in prompts.items():
            cache.admit(slot, len(prompt))
            logits = cache.prefill(params, slot,
                                   jnp.asarray(prompt, jnp.int32))
            pend[slot] = int(jnp.argmax(logits))
        return cache, pend

    only0 = np.array([True, False])
    cache_s, pend = fresh()
    first = _single_steps(cache_s, params, pend, 4, active=only0)
    both = _single_steps(cache_s, params,
                         np.array([first[-1, 0], pend[1]]), 4)

    def pair():
        cache_p, pend = fresh()
        h1 = cache_p.dispatch_window(params, pend, 4, active=only0)
        h2 = cache_p.dispatch_window(
            params, np.array([-1, pend[1]], np.int32), 4)
        one = np.asarray(cache_p.harvest_window(h1))[:4]
        two = np.asarray(cache_p.harvest_window(h2))[:4]
        cache_p.drop_carry()
        return cache_p, one, two

    cache_p, one, two = pair()
    assert one[:, 0].tolist() == first[:, 0].tolist()
    assert two.tolist() == both.tolist()
    assert cache_p._host_lengths == cache_s._host_lengths
    pinned = kvcache_mod.trace_count()
    _, one_again, two_again = pair()
    assert kvcache_mod.trace_count() == pinned
    assert (one_again.tolist(), two_again.tolist()) == (one.tolist(),
                                                        two.tolist())


def test_a_warm_server_traces_nothing_when_a_newcomer_joins(params):
    """Round two of the same admissions into a running pipeline finds
    every window and prefill program round one traced: a row fed from
    the host beside rows on the carry is no new program."""
    server = PagedGenerationServer(params, LONG_CFG, slots=4, pages=80,
                                   window=4, prefix_cache=False)
    try:
        alone = server.submit([5, 9, 2, 7], 9)
        _slowed(server, 0.02)

        def round_trip():
            stream = server.submit_stream(*LONG)
            first = next(stream)
            got = server.submit([5, 9, 2, 7], 9)
            return [first] + list(stream), got

        want = round_trip()
        assert want[1] == alone
        assert server.stats()["pipeline_joins_total"] == 1
        pinned = kvcache_mod.trace_count()
        assert round_trip() == want
        assert kvcache_mod.trace_count() == pinned
        assert server.stats()["pipeline_joins_total"] == 2
    finally:
        server.close()


def test_a_cancel_still_collapses_the_pipeline(params):
    server = PagedGenerationServer(params, LONG_CFG, slots=2, pages=80,
                                   window=4)
    try:
        _slowed(server, 0.02)
        keeper = server.submit_stream(*LONG)
        next(keeper)
        src = server.submit_stream([1, 2, 3], 150)
        next(src)
        src.cancel()
        with pytest.raises(RequestCancelled):
            list(src)
        stats = server.stats()
        assert stats["pipeline_collapses"]["cancel"] >= 1
        assert stats["pipeline_joins_total"] == 1
        text = render_metrics({"serving": stats})
        assert "kvedge_serve_pipeline_joins_total 1" in text
        assert ('kvedge_serve_pipeline_collapses_total{cause="cancel"} '
                f'{stats["pipeline_collapses"]["cancel"]}') in text
        assert 'pipeline_collapses_total{cause="newcomer"} 0' in text
        keeper.cancel()
    finally:
        server.close()


def test_a_bucket_step_still_collapses_the_pipeline(params):
    """The newcomer's row lies above the device bucket: the resize
    needs nothing in flight, so the pipeline falls to a boundary, for
    that cause; the newcomer, parked until then, is prefilled beside
    the windows dispatched after it."""
    got, stats = _join_run(params, NEWCOMERS[:1], slow_s=0.02, slots=2,
                           pages=80, window=4, min_bucket=1)
    prompt, n_new, _ = NEWCOMERS[0]
    assert got["long"] == long_reference(params, *LONG)
    assert got[0] == long_reference(params, prompt, n_new)
    assert stats["pipeline_collapses"]["bucket"] >= 1
    assert stats["pipeline_collapses"]["newcomer"] == 0


@pytest.mark.parametrize("server_kw", [{"checkpoint_every": 1}],
                         ids=["checkpointing"])
def test_a_newcomer_still_collapses_such_a_pipeline(params, server_kw):
    """A server that checkpoints journals a newcomer at the boundary it
    joins at (rung 22): it joins at a boundary, as before."""
    got, stats = _join_run(params, NEWCOMERS[:1], slow_s=0.02, slots=2,
                           pages=80, window=4, **server_kw)
    prompt, n_new, _ = NEWCOMERS[0]
    assert got["long"] == long_reference(params, *LONG)
    assert got[0] == long_reference(params, prompt, n_new)
    # at a cadence of 1 every other iteration is a boundary anyway:
    # the newcomer may find one open and collapse nothing itself
    assert stats["checkpoints_total"] >= 2
    assert stats["pipeline_collapses"]["checkpoint"] >= 1
    assert stats["pipeline_joins_total"] == 0


def test_one_token_newcomer_finishes_beside_a_running_pipeline(params):
    """A newcomer asked for one token needs no step: its finish is the
    boundary sweep's, so it collapses the pipeline (cause ``stop``) and
    does not wait for the long request to end."""
    server = PagedGenerationServer(params, LONG_CFG, slots=2, pages=80,
                                   window=4)
    try:
        alone = server.submit([5, 9, 2], 1)
        assert alone == long_reference(params, [5, 9, 2], 1)
        _slowed(server, 0.02)
        stream = server.submit_stream(*LONG)
        first = next(stream)
        assert server.submit([5, 9, 2], 1, timeout=20.0) == alone
        stats = server.stats()
        assert stats["in_flight"] == 1  # the long request lives on
        assert stats["pipeline_collapses"]["stop"] >= 1
        assert LONG[0] + [first] + list(stream) \
            == long_reference(params, *LONG)
    finally:
        server.close()


# ---- the first token stays on the device (ISSUE 47) ----------------------


class Gate:
    """The loop's wait for a window (``cache.await_window``, made with
    the work lock released) held back by an event: while the gate is
    shut and ``entered`` is set the loop stands in
    ``loop/harvest_wait`` and holds nothing, for as long as the test
    needs to admit, cancel or close beside it."""

    def __init__(self, server):
        self._real = server._cache.await_window
        self.open = threading.Event()
        self.open.set()
        self.entered = threading.Event()
        server._cache.await_window = self

    def __call__(self, handle):
        self.entered.set()
        assert self.open.wait(120), "the test never opened the gate"
        return self._real(handle)

    def shut(self):
        """Returns once the loop waits at the shut gate."""
        self.entered.clear()
        self.open.clear()
        assert self.entered.wait(60), "the loop harvested nothing"


def _host_picks(server):
    """The parent's path: the handler reads its pick back, lock held,
    and the loop reads its windows lock held."""
    server._unlocked_reads = False
    return server


def _until(cond, what, seconds=60.0):
    deadline = time.monotonic() + seconds
    while not cond():
        assert time.monotonic() < deadline, f"never: {what}"
        time.sleep(0.005)


FIRST_TOKEN_CASES = {
    "greedy": ([5, 9, 2, 7, 1, 1, 4], 21, None),
    "sampled": ([1, 2, 3, 4], 24, SAMPLING),
    "one-token": ([5, 9, 2], 1, None),
    "one-token-sampled": ([1, 2, 3, 4], 1, SAMPLING),
    "stop-is-first": ([5, 9, 2, 7, 1, 1, 4], 21, None),
    "stop-is-later": ([5, 9, 2, 7, 1, 1, 4], 21, None),
}


def _first_token_server(params, cfg):
    return PagedGenerationServer(params, cfg, slots=3, pages=80, window=4,
                                 prefill_chunk=3, prefix_cache=False)


def _block_reference(block):
    """A patterned block's reference (``decode.generate`` refuses a
    pattern): the same server on the host's pick, the request alone."""
    cfg, params = block

    @functools.cache
    def served(prompt, n_new, sampled):
        server = _host_picks(_first_token_server(params, cfg))
        try:
            return server.submit(list(prompt), n_new,
                                 sampling=SAMPLING if sampled else None)
        finally:
            server.close()

    return lambda _, prompt, n_new, sampling=None: served(
        tuple(prompt), n_new, sampling is not None)


def _first_token_case(params, kind, on_device, cfg=LONG_CFG,
                      reference=long_reference):
    """One request, ``kind``'s, served alone (its first window is a
    boundary's) and then as a newcomer to the long request's pipeline
    (it joins an overlapped window beside a row on the carry, or,
    asked for one token or stopped on its first, finishes beside it).
    Returns both streams, the long request's, and the stats."""
    prompt, n_new, sampling = FIRST_TOKEN_CASES[kind]
    kw = {"sampling": sampling}
    if kind.startswith("stop"):
        whole = reference(params, prompt, n_new)[len(prompt):]
        kw["stop_token"] = whole[0 if kind == "stop-is-first" else 6]
    server = _first_token_server(params, cfg)
    if not on_device:
        _host_picks(server)
    try:
        alone = server.submit(prompt, n_new, **kw)
        _slowed(server, 0.02)
        stream = server.submit_stream(*LONG)
        first = next(stream)
        joined = server.submit(prompt, n_new, timeout=60.0, **kw)
        long = LONG[0] + [first] + list(stream)
        return alone, joined, long, server.stats()
    finally:
        server.close()


@pytest.mark.parametrize("block", ["plain", "recurrent", "window-block"])
@pytest.mark.parametrize("kind", sorted(FIRST_TOKEN_CASES))
def test_a_first_token_kept_on_the_device_is_the_hosts_pick(
        params, probe_blocks, kind, block):
    """Token for token what the handler's own read of the pick serves
    (the parent's path, kept where a server checkpoints), alone and
    joining a running pipeline; every pick stayed on the device and
    none was read with the lock in hand. On a recurrent block the
    newcomer's state, on a window block its second table, enter the
    overlapped window beside the carry's rows the same way."""
    cfg, reference = LONG_CFG, long_reference
    if block != "plain":
        cfg, params = probe_blocks[block]
        reference = _block_reference(probe_blocks[block])
    want = _first_token_case(params, kind, False, cfg, reference)
    got = _first_token_case(params, kind, True, cfg, reference)
    assert got[:3] == want[:3]
    assert got[0] == got[1]
    assert got[2] == reference(params, *LONG)
    prompt, n_new, sampling = FIRST_TOKEN_CASES[kind]
    if not kind.startswith("stop"):
        assert got[0] == reference(params, prompt, n_new, sampling)
    elif kind == "stop-is-first":
        assert len(got[0]) == len(prompt) + 1
    picks = got[3]["phase_ms"]["admit/first_pick"][0]
    assert picks == 3
    assert got[3]["first_tokens_on_device_total"] == picks
    assert want[3]["first_tokens_on_device_total"] == 0
    assert got[3]["stop_finishes_total"] == want[3]["stop_finishes_total"]
    # A row that can still step joins on the carry; one that ends on
    # its first token without a step takes the boundary it always took.
    if "one" in kind:
        assert got[3]["pipeline_joins_total"] == 0
        assert got[3]["pipeline_collapses"]["stop"] >= 1
    else:
        assert got[3]["pipeline_joins_total"] == 1
    text = render_metrics({"serving": got[3]})
    assert f"kvedge_serve_first_tokens_on_device_total {picks}" in text


def test_a_cancel_between_the_pick_and_the_join_ends_the_request(params):
    """The newcomer's pick is dispatched and the request active while
    the loop waits for a window (lock released), and it is cancelled
    before any window carried its row: the host never reads the token,
    the request ends cancelled, and the long request's stream is
    generate's."""
    server = PagedGenerationServer(params, LONG_CFG, slots=2, pages=80,
                                   window=4, prefix_cache=False)
    try:
        gate = Gate(server)
        stream = server.submit_stream(*LONG)
        first = next(stream)
        gate.shut()
        src = server.submit_stream([1, 2, 3], 150)
        _until(lambda: server.stats()["in_flight"] == 2,
               "the newcomer active beside a loop that waits")
        req = src._req
        assert req.first_dev is not None and req.state == "join_wait"
        src.cancel()
        gate.open.set()
        with pytest.raises(RequestCancelled):
            list(src)
        assert req.first_dev is not None and not req.generated
        assert not req.t_first
        stats = server.stats()
        assert stats["pipeline_collapses"]["cancel"] >= 1
        assert stats["first_tokens_on_device_total"] == 2
        assert LONG[0] + [first] + list(stream) \
            == long_reference(params, *LONG)
    finally:
        server.close()


def test_time_to_first_token_is_stamped_when_the_host_has_it(params):
    """Admitted while the loop waits at a shut gate, a request is
    picked, active and in ``join_wait``, and has no time to first
    token: that comes with the harvest of the first window that
    carried its row, just before the token's put on its stream."""
    server = PagedGenerationServer(params, LONG_CFG, slots=2, pages=80,
                                   window=4, prefix_cache=False)
    try:
        gate = Gate(server)
        stream = server.submit_stream(*LONG)
        next(stream)
        gate.shut()
        src = server.submit_stream([5, 9, 2, 7], 9)
        req = src._req
        _until(lambda: req.first_dev is not None, "the pick dispatched")
        ttft = server.stats()["ttft_ms"]["count"]
        time.sleep(0.2)
        assert not req.t_first and not req.generated
        assert server.stats()["ttft_ms"]["count"] == ttft
        gate.open.set()
        got = list(src)
        assert [5, 9, 2, 7] + got == long_reference(params,
                                                    [5, 9, 2, 7], 9)
        assert req.first_dev is None
        # the pick was dispatched 0.2 s and more before the host read
        # the token, and the token was on the stream at once
        assert req.t_first - req.t_admit >= 0.2
        assert 0 <= req.t_emit - req.t_first < 0.1
        assert req.state_ms["join_wait"] >= 150
        assert server.stats()["ttft_ms"]["count"] == ttft + 1
        list(stream)
    finally:
        server.close()


def test_a_preempted_row_takes_its_first_token_to_the_host(params):
    """A batch request preempted before any window carried its row:
    the row of first tokens is by slot, which the resume changes, so
    the swap reads the token; the resumed stream is generate's."""
    server = PagedGenerationServer(
        params, LONG_CFG, slots=1, pages=80, window=4,
        prefix_cache=False, sched_policy="strict",
        sched_swap_budget_mb=64)
    try:
        with server._hold("control"):
            # Admitted and picked while the loop cannot run: the
            # preemption finds a row no window has carried.
            batch = threading.Thread(target=lambda: got.append(
                server.submit([5, 9, 2, 7], 30, priority="batch",
                              timeout=120.0)))
            got: list = []
            batch.start()
        _until(lambda: server.stats()["first_tokens_on_device_total"] == 1,
               "the batch request picked")
        fast = server.submit([1, 2, 3], 5, timeout=120.0)
        batch.join(timeout=120)
        assert fast == long_reference(params, [1, 2, 3], 5)
        assert got == [long_reference(params, [5, 9, 2, 7], 30)]
    finally:
        server.close()


def test_the_pick_and_the_joins_are_lowered_once(params):
    """The pick program depends on the vocabulary's width and on the
    sampling mode (and the pool's slots), the join on the window
    length and the bucket: a second round of the same admissions into
    a running pipeline lowers nothing, whichever slot a row takes."""
    lowered: list = []

    def on_duration(event, duration, **kw):
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            lowered.append(str(kw.get("fun_name")))

    import jax.monitoring as mon
    from jax._src import monitoring as mon_src

    server = PagedGenerationServer(params, LONG_CFG, slots=5, pages=120,
                                   window=4, prefill_chunk=3,
                                   prefix_cache=False)
    mon.register_event_duration_secs_listener(on_duration)
    try:
        _slowed(server, 0.02)

        def round_trip():
            stream = server.submit_stream(*LONG)
            first = next(stream)
            got = [server.submit(prompt, n_new, sampling=sampling)
                   for prompt, n_new, sampling in NEWCOMERS]
            return [first] + list(stream), got

        want = round_trip()
        picks = [name for name in lowered if "_pick_first" in name]
        assert sorted(picks) == ["jit(_pick_first_greedy)",
                                 "jit(_pick_first_sampled)"]
        # one join a window length, over the one bucket: the windows
        # of 4 steps and what the budgets' tails cut them to
        joins = [name for name in lowered if "_join_carry" in name]
        assert 1 <= len(joins) <= 3
        del lowered[:]
        assert round_trip() == want
        assert lowered == []
        stats = server.stats()
        assert stats["first_tokens_on_device_total"] == 8
        assert stats["pipeline_joins_total"] == 6
    finally:
        mon_src.unregister_event_duration_listener(on_duration)
        server.close()


# ---- the loop reads a window back with the lock released (ISSUE 47) ------


def _gated_server(params, **kw):
    """A long request streams, and the loop stands at a shut gate in
    its wait for a window: ``(server, gate, stream, first token)``."""
    server = PagedGenerationServer(params, LONG_CFG, slots=2, pages=80,
                                   window=4, prefill_chunk=3,
                                   prefix_cache=False, **kw)
    try:
        gate = Gate(server)
        stream = server.submit_stream(*LONG)
        first = next(stream)
        gate.shut()
    except BaseException:
        server.close()
        raise
    return server, gate, stream, first


def _lock_gain(a: dict, b: dict) -> tuple:
    """(the lock's own total, what the holders' names account for)
    gained between two snapshots, ms."""
    total = b["lock_held_ms_total"] - a["lock_held_ms_total"]
    named = sum(b["lock_held_ms"][n][1] - a["lock_held_ms"][n][1]
                for n in LOCK_HOLDERS)
    return total, named


@pytest.mark.parametrize("debug_locks", [False, True],
                         ids=["lock", "debuglock"])
def test_prefill_chunks_get_the_lock_while_the_loop_reads_a_window(
        params, debug_locks):
    """The loop is inside ``loop/harvest_wait`` and holds nothing: a
    newcomer's admission, its four chunks and its pick all take the
    lock and finish beside it; the wait is the phase's and nobody's
    hold, and every millisecond the lock was held has a name."""
    server, gate, stream, first = _gated_server(params,
                                                debug_locks=debug_locks)
    try:
        a = server.stats()
        assert server._harvesting is not None
        prompt = [100, 50, 7, 7, 7, 2, 9, 9, 4, 1, 6]
        src = server.submit_stream(prompt, 12)
        _until(lambda: server.stats()["in_flight"] == 2,
               "the newcomer active beside a loop that waits")
        time.sleep(0.25)
        b = server.stats()
        assert b["phase_ms"]["admit/prefill_chunk"][0] \
            - a["phase_ms"]["admit/prefill_chunk"][0] == 4
        assert b["lock_held_ms"]["admit/first_pick"][0] \
            - a["lock_held_ms"]["admit/first_pick"][0] == 1
        # the loop dispatched and harvested nothing meanwhile
        assert b["lock_held_ms"]["loop"] == a["lock_held_ms"]["loop"]
        assert b["overlap_windows_total"] == a["overlap_windows_total"]
        wall = (b["clock_s"] - a["clock_s"]) * 1e3
        waited = (b["phase_ms"]["loop/harvest_wait"][1]
                  - a["phase_ms"]["loop/harvest_wait"][1])
        assert wall >= 250.0 and waited == pytest.approx(wall, abs=5.0)
        total, named = _lock_gain(a, b)
        assert total < wall - 200.0      # the wait is in no hold
        assert total == pytest.approx(named, abs=1e-6)
        gate.open.set()
        assert prompt + list(src) == long_reference(params, prompt, 12)
        assert LONG[0] + [first] + list(stream) \
            == long_reference(params, *LONG)
        c = server.stats()
        # (a loop parked for work, at the end, stamps its pause and
        # its release apart: microseconds, as before this issue)
        total, named = _lock_gain(a, c)
        assert total == pytest.approx(named, abs=0.5)
        assert c["loop_ms_total"] == pytest.approx(
            sum(c["phase_ms"][name][1] for name in c["phase_ms"]
                if name.startswith("loop/")
                and name != "loop/window_release"), rel=1e-6)
    finally:
        gate.open.set()
        server.close()


@pytest.mark.parametrize("what", ["close", "poisoning", "cancel"])
def test_what_lands_during_the_wait_is_seen_when_the_lock_is_back(
        params, what):
    """A hard close, a poisoning (a newcomer's prefill chunk fails
    terminally) and a cancel each take the lock while the loop waits
    for its window without it; the loop finds them when it has the
    lock again: the first two end it without a token more, the third
    it honours at the boundary it always took."""
    server, gate, stream, first = _gated_server(params)
    try:
        loop = server._thread
        emitted = len(stream._req.generated)
        if what == "close":
            closer = threading.Thread(target=server.close)
            closer.start()
            _until(lambda: server._closed, "the close under the lock")
            gate.open.set()
            closer.join(timeout=60)
            with pytest.raises(ServerClosed):
                list(stream)
        elif what == "poisoning":
            def failing(*a, **kw):
                raise DeviceOpTimeout("injected: the chunk's op hung")

            server._cache.prefill_chunk = failing
            with pytest.raises(DeviceOpTimeout):
                server.submit([5, 9, 2], 4)
            assert server._poison is not None and server._closed
            gate.open.set()
            with pytest.raises(DeviceOpTimeout):
                list(stream)
        else:
            stream.cancel()
            gate.open.set()
            with pytest.raises(RequestCancelled):
                list(stream)
            assert server.stats()["pipeline_collapses"]["cancel"] == 1
            # the server lives on
            assert server.submit([5, 9, 2, 7], 9) == long_reference(
                params, [5, 9, 2, 7], 9)
        if what != "cancel":
            loop.join(timeout=60)
            assert not loop.is_alive()
            # not a token of the window it waited for was emitted
            assert len(stream._req.generated) == emitted
            assert stream._req.inflight == 0
        assert server._harvesting is None
        stats = server.stats()
        total = stats["lock_held_ms_total"]
        named = sum(stats["lock_held_ms"][n][1] for n in LOCK_HOLDERS)
        assert total == pytest.approx(named, abs=0.5)
    finally:
        gate.open.set()
        server.close()
