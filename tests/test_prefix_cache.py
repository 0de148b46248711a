"""Copy-on-write radix prefix cache (SERVING.md rung 24).

The contract under test: cross-request prefix reuse may change WHERE
prompt K/V comes from — an HBM registry pin, a COW-copied partial
page, a host-tier swapin, or a journal-shadow restore — but never
WHAT any request emits. Every leg here pins bit-identity against the
contiguous reference (or a prefix_cache=off server), and the
bookkeeping legs pin the books: leases, refcounts, host-budget
billing, and the journal's shadow store must all settle to zero.

Committed-length arithmetic used throughout: the final emitted token
is never fed back, so a finished request's committed device state is
``len(prompt) + n_new - 1`` tokens, and registration pins one entry
per FULL page of that stream.
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
from kvedge_tpu.models import (
    TransformerConfig,
    generate,
    init_params,
)
from kvedge_tpu.models import kvcache as kvcache_mod
from kvedge_tpu.models.serving import PagedGenerationServer
from kvedge_tpu.testing.servingfaults import FaultyCache

pytestmark = pytest.mark.prefix

CFG = TransformerConfig(
    vocab=128, d_model=32, n_heads=4, n_kv_heads=2, n_layers=2, d_ff=64,
    max_seq=64,
)

STEM = [3, 1, 4, 1, 5, 9, 2, 6]  # two full pages at page_size=4


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


def reference(params, prompt, n_new):
    out = generate(params, jnp.asarray([prompt], jnp.int32), CFG,
                   n_new=n_new)
    return [int(t) for t in np.asarray(out)[0]]


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


def _wait_stats(server, pred, timeout_s=30.0, what="condition"):
    deadline = time.monotonic() + timeout_s
    while True:
        st = server.stats()
        if pred(st):
            return st
        assert time.monotonic() < deadline, f"timed out waiting: {what}"
        time.sleep(0.002)


# ---- COW divergence: bit-identity under the full device-resident stack ---


@pytest.mark.parametrize("sampled", [False, True],
                         ids=["greedy", "sampled"])
def test_cow_divergence_bit_identical(params, sampled):
    """A probe whose prompt diverges INSIDE a cached entry's last page
    admits via cow_page and must emit exactly what a prefix_cache=off
    server emits — with the overlapped pipeline on, greedy and
    sampled (the acceptance pin)."""
    kw = dict(slots=3, pages=48, page_size=4, window=4)
    warm = STEM + [5, 3]
    probe = STEM + [5, 8, 9]  # shares 1 token of warm's third page

    def sampling(k):
        if not sampled:
            return None
        return (jax.random.PRNGKey(k), jnp.float32(0.8),
                jnp.float32(0.9))

    on = PagedGenerationServer(params, CFG, prefix_cache=True, **kw)
    try:
        got_warm = on.submit(warm, n_new=6, sampling=sampling(1))
        got = on.submit(probe, n_new=6, sampling=sampling(2))
        st = on.stats()
        # warm commits 10+6-1=15 tokens -> 3 full pages; the probe's
        # walk matches 2 full blocks then LCPs 1 token into the third.
        assert st["prefix_cow_copies"] == 1
        assert st["prefix_hits"] == 1
        assert st["prefix_tokens_saved"] == 9
    finally:
        on.close()

    off = PagedGenerationServer(params, CFG, prefix_cache=False, **kw)
    try:
        assert off.submit(warm, n_new=6, sampling=sampling(1)) \
            == got_warm
        assert off.submit(probe, n_new=6, sampling=sampling(2)) == got
        assert off.stats()["prefix_cow_copies"] == 0
    finally:
        off.close()


def test_multi_turn_followup_reuses_generated_pages(params):
    """Finish-time registration covers prompt AND generated pages, so
    a multi-turn follow-up embedding turn 1's full transcript hits on
    every committed full page — prefill work on the second turn is
    priced at the suffix only."""
    kw = dict(slots=2, pages=48, page_size=4, window=4)
    server = PagedGenerationServer(params, CFG, prefix_cache=True, **kw)
    try:
        g1 = server.submit(STEM, n_new=8)  # prompt + generated
        # 8 + 8 - 1 = 15 committed tokens -> 3 full pages registered.
        assert server.stats()["prefix_entries"] == 3
        p2 = g1 + [7, 7]  # the multi-turn transcript
        before = server.stats()["prefix_tokens_saved"]
        g2 = server.submit(p2, n_new=4)
        st = server.stats()
        assert st["prefix_tokens_saved"] - before == 12  # all 3 pages
        with server._lock:
            per_token = (server._page_bytes_locked()
                         // server._cache.page_size)
        assert st["prefix_bytes_saved"] == \
            st["prefix_tokens_saved"] * per_token
    finally:
        server.close()
    assert g1 == reference(params, STEM, 8)
    assert g2 == reference(params, p2, 4)


# ---- tiered host residency ----------------------------------------------


def test_host_tier_demote_then_promote(params):
    """Pool pressure demotes evicted prefix entries to the host tier
    (verbatim swapout bytes) instead of dropping them; a later arrival
    whose best match is host-resident promotes it back at admission
    and decodes bit-identically."""
    server = PagedGenerationServer(
        params, CFG, prefix_cache=True, prefix_host_mb=64,
        slots=1, pages=6, page_size=4, window=4)
    try:
        ga = server.submit(STEM, n_new=4)          # registers 2 pages
        pb = [7, 7, 2, 9, 1, 1, 8, 4, 6, 2, 5, 5]  # unrelated, 3 pages
        gb = server.submit(pb, n_new=8)            # needs 5 -> evicts A
        st = server.stats()
        assert st["prefix_demotions"] >= 2
        assert st["prefix_host_entries"] >= 1
        assert st["prefix_evictions"]["admission"] >= 2
        pc = STEM + [0, 0]
        gc = server.submit(pc, n_new=4)
        st = server.stats()
        assert st["prefix_promotions"] == 1
        assert st["prefix_hits"] == 1
        assert st["prefix_tokens_saved"] == 8  # the promoted 2 pages
    finally:
        server.close()
    assert ga == reference(params, STEM, 4)
    assert gb == reference(params, pb, 8)
    assert gc == reference(params, pc, 4)


def test_host_budget_bills_drops_and_lru(params):
    """The host tier is budgeted: oversize records drop ("host_over"),
    and admitting a new record over budget evicts host-LRU entries
    ("host_lru") until the bytes fit — the budget is never exceeded."""
    server = PagedGenerationServer(
        params, CFG, prefix_cache=True, prefix_host_mb=64,
        slots=2, pages=32, page_size=4, window=4)
    try:
        s2 = [2, 7, 1, 8, 2, 8, 1, 8]
        server.submit(STEM + [5], n_new=4)  # 3 entries (12 committed)
        server.submit(s2 + [6], n_new=4)    # 3 more under another stem
        with server._lock:
            pb = server._page_bytes_locked()
            # Shrink the budget to exactly one page of host room, then
            # evict deepest-first: multi-page records overflow outright,
            # and the second one-page root displaces the first.
            server._prefix_host_budget = pb
            order = sorted(server._prefix_entry_nodes,
                           key=lambda n: len(server._node_tokens(n)),
                           reverse=True)
            for node in order:
                server._evict_prefix_node(node, "pressure")
        st = server.stats()
        assert st["prefix_evictions"]["host_over"] == 4
        assert st["prefix_evictions"]["host_lru"] == 1
        assert st["prefix_evictions"]["pressure"] == 6
        assert st["prefix_host_entries"] == 1
        assert st["prefix_host_bytes"] == pb
        assert st["prefix_entries"] == 0
    finally:
        server.close()


# ---- journal refcounts: shared pages checkpoint by reference -------------


def test_journal_refcount_checkpoint_and_restore(params):
    """Two in-flight sharers checkpoint their common prefix as ONE
    shadow snapshot (refs=2) — the journal bills those bytes once, not
    per request — and revive() restores both: the first restorer
    resurrects the shadow as a live registry entry, the second rides
    its pages. Both streams complete bit-identical."""
    cache = FaultyCache(CFG, slots=3, pages=32, page_size=4)
    server = PagedGenerationServer(
        params, CFG, cache=cache, window=2,
        checkpoint_every=1, prefix_cache=True)
    try:
        server.submit(STEM + [5], n_new=4)  # register the stem
        pa, pb = STEM + [7, 2], STEM + [8, 3]
        ga, da, ea = _stream_in_background(server, pa, 24)
        gb, db, eb = _stream_in_background(server, pb, 24)
        _wait_stats(
            server,
            lambda st: (st["journal_entries"] == 2
                        and st["journal_shadow_nodes"] == 1),
            what="both sharers checkpointed against one shadow")
        with server._lock:
            pb_bytes = server._page_bytes_locked()
            shadow = list(server._prefix_shadow.values())
            assert len(shadow) == 1
            assert shadow[0]["refs"] == 2
            assert shadow[0]["npages"] == 2
        real = cache.harvest_window

        def dying(handle):
            raise RuntimeError("injected: device lost mid-window")

        cache.harvest_window = dying
        _wait_degraded(server)
        st = server.stats()
        # The shared pages are billed ONCE: the shadow's bytes, not
        # one copy per citing checkpoint.
        assert st["journal_shadow_bytes"] == 2 * pb_bytes
        cache.harvest_window = real
        assert server.revive() == 2
        assert da.wait(60) and db.wait(60)
        assert not ea and not eb
        st = server.stats()
        assert st["journal_restores_total"] >= 2
        # Streams emit only NEW tokens: compare past the prompt.
        assert ga == reference(params, pa, 24)[len(pa):]
        assert gb == reference(params, pb, 24)[len(pb):]
        # Books settle: no journal residue once both finished.
        done = _wait_stats(
            server,
            lambda s: s["journal_entries"] == 0,
            what="journal drains after completion")
        assert done["journal_shadow_nodes"] == 0
        assert done["journal_shadow_bytes"] == 0
        assert done["reserved_pages"] == 0
    finally:
        server.close()


# ---- zero-retrace pins (acceptance: no compiles off the hot path) --------


def test_cow_hit_zero_retrace_within_bucket(params):
    """A COW admission compiles nothing new once its shapes are warm:
    round two (fresh stem, same lengths) must leave trace_count flat."""
    server = PagedGenerationServer(
        params, CFG, prefix_cache=True, slots=4, pages=64,
        page_size=4, window=4, min_bucket=4)
    try:
        def round_trip(b):
            stem = [b, 1, 4, 1, 5, 9, 2, 6]
            server.submit(stem + [5, 3], n_new=6)
            probe = stem + [5, 8, 9]
            assert server.submit(probe, n_new=6) \
                == reference(params, probe, 6)

        round_trip(3)
        pinned = kvcache_mod.trace_count()
        round_trip(7)
        assert kvcache_mod.trace_count() == pinned
        assert server.stats()["prefix_cow_copies"] == 2
    finally:
        server.close()


def test_refcount_restore_zero_retrace(params):
    """Poison/revive with a journal-refcount checkpoint in play: the
    second crash-restore cycle (same shapes, fresh suffix) re-runs the
    shadow swapin + shared re-admission entirely on warm programs."""
    cache = FaultyCache(CFG, slots=2, pages=32, page_size=4)
    server = PagedGenerationServer(
        params, CFG, cache=cache, window=2,
        checkpoint_every=1, min_bucket=2, prefix_cache=True)
    real = cache.harvest_window
    try:
        server.submit(STEM + [5], n_new=4)  # register the stem

        def round_trip(k):
            calls = [0]

            def dying(handle):
                calls[0] += 1
                if calls[0] == 3:
                    calls[0] = -10**9  # fire exactly once
                    raise RuntimeError("injected: harvest died")
                return real(handle)

            cache.harvest_window = dying
            p = STEM + [k, k + 1]
            got, done, errs = _stream_in_background(server, p, 8)
            _wait_degraded(server)
            cache.harvest_window = real
            assert server.revive() == 1
            assert done.wait(60)
            assert not errs
            assert got == reference(params, p, 8)[len(p):]

        round_trip(7)
        pinned = kvcache_mod.trace_count()
        round_trip(9)
        assert kvcache_mod.trace_count() == pinned
    finally:
        cache.harvest_window = real
        server.close()


# ---- leases: live sharers outlive the registry entry ---------------------


def test_lease_outlives_registry_eviction(params):
    """Evicting every registry entry while two sharers are mid-decode
    must not free their pages out from under them: the lease (slot
    refcounts) keeps the shared pages alive, both streams finish
    bit-identical, and the books settle to an all-free pool."""
    server = PagedGenerationServer(
        params, CFG, prefix_cache=True, slots=3, pages=48,
        page_size=4, window=2)
    try:
        server.submit(STEM + [5], n_new=4)  # register the stem
        pa, pb = STEM + [7, 2], STEM + [8, 3]
        ga, da, ea = _stream_in_background(server, pa, 24)
        gb, db, eb = _stream_in_background(server, pb, 24)
        _wait_stats(
            server,
            lambda st: st["in_flight"] == 2 and st["prefix_hits"] >= 2,
            what="both sharers admitted on the cached stem")
        with server._lock:
            assert server._lease  # live sharers hold leases
            for node in list(server._prefix_entry_nodes):
                server._evict_prefix_node(node, "pressure")
            assert not server._prefix_entry_nodes
        assert da.wait(60) and db.wait(60)
        assert not ea and not eb
        assert ga == reference(params, pa, 24)[len(pa):]
        assert gb == reference(params, pb, 24)[len(pb):]
        st = server.stats()
        assert st["reserved_pages"] == 0
        with server._lock:
            assert not server._lease
            # Force-evict whatever finish-time registration re-pinned:
            # the pool must return to every-page-free.
            for node in list(server._prefix_entry_nodes):
                server._evict_prefix_node(node, "pressure")
            for node in list(server._prefix_host_nodes):
                server._drop_host_record_locked(node)
            assert server._cache.free_pages() == st["pages_total"]
    finally:
        server.close()


# ---- low-watermark shed prices shared pages as resident ------------------


def test_shed_prices_shared_pages_as_resident(params):
    """The page-watermark shed gates on the arrival's MARGINAL cost:
    full shared pages another live request already leases are free;
    the COW page and true privates still bill. The same arrival that
    sheds at raw pages_needed parks at its discounted price."""
    server = PagedGenerationServer(
        params, CFG, prefix_cache=True, slots=2, pages=8,
        page_size=4, window=4, page_low_watermark=0.5)
    try:
        server.submit(STEM + [5], n_new=4)  # 3 entries (12 committed)
        probe = STEM + [5, 9]  # 2 full shared pages + 1 COW page
        with server._lock:
            _, shared, stok, _ = server._prefix_lookup(probe)
            assert stok == 9 and len(shared) == 3
            # Solo arrival: nobody leases yet, so the first sharer
            # books every lease unit — marginal cost is the full 4.
            assert server._admission_price_locked(4, shared, stok) == 4
            full = tuple(shared[:2])
            server._lease_take_locked(full)  # a live sharer rides
            try:
                price = server._admission_price_locked(4, shared, stok)
                assert price == 2  # 1 private + 1 COW, leases free
                assert server._page_shed_locked("batch", 4) is not None
                assert server._page_shed_locked("batch", price) is None
            finally:
                server._lease_drop_locked(full)
    finally:
        server.close()


# ---- cache off: today's exact behavior ----------------------------------


def test_cache_off_keeps_baseline_semantics(params):
    """prefix_cache=False is the seed's serving path: no registry, no
    leases, no shadow store — identical resubmits re-prefill in full
    and emit the reference stream."""
    server = PagedGenerationServer(
        params, CFG, prefix_cache=False, slots=2, pages=16,
        page_size=4, window=4)
    try:
        a = server.submit(STEM + [5], n_new=6)
        b = server.submit(STEM + [5], n_new=6)
        st = server.stats()
        assert a == b == reference(params, STEM + [5], 6)
        assert st["prefix_entries"] == 0
        assert st["prefix_hits"] == 0
        assert st["prefix_tokens_saved"] == 0
        assert st["prefix_cow_copies"] == 0
        assert st["prefix_host_entries"] == 0
        assert st["journal_shadow_nodes"] == 0
        with server._lock:
            assert not server._lease
    finally:
        server.close()


# ---- config knobs --------------------------------------------------------


def test_config_prefix_knobs_round_trip_and_validate():
    """Rung 24 knobs: serving_prefix_cache (off restores the seed's
    behavior) and the host-tier budget in MB (0 = no host tier)."""
    cfg = RuntimeConfig.parse(
        "[payload]\nserving = 'paged'\n"
        "serving_prefix_cache = false\n"
        "serving_prefix_host_mb = 256\n"
    )
    assert cfg.serving_prefix_cache is False
    assert cfg.serving_prefix_host_mb == 256
    assert RuntimeConfig.parse(cfg.to_toml()) == cfg
    default = RuntimeConfig.parse("")
    assert default.serving_prefix_cache is True
    assert default.serving_prefix_host_mb == 0
    with pytest.raises(RuntimeConfigError):
        RuntimeConfig.parse("[payload]\nserving_prefix_host_mb = -1\n")


# ---- the pool's layout stops at the device's edge ------------------------
#
# On the device the pool is [L, P, page, K*Dh] (kvcache.PagedState); what
# leaves it — snapshots, swaps, the prefix cache's files — keeps the
# per-head shape [L, n, page, K, Dh] it had before the pool was merged.


def _per_head_pages(n: int, kv_dtype: str):
    """K and V for ``n`` pages, [L, n, page, K, Dh], every element its
    own bf16-exact value (a multiple of 1/8 under 32: a head or an
    offset that lands elsewhere changes the numbers)."""
    shape = (CFG.n_layers, n, 4, CFG.kv_heads, CFG.d_head)
    k = (np.arange(np.prod(shape)).reshape(shape) % 251) / 8.0
    if kv_dtype == "int8":
        # One amax of 127/8 a row: x / scale is then a whole number and
        # the quantisation round trip is exact.
        k = k % 16.0
        k[..., 0] = 127 / 8.0
    return k.astype(np.float32), (k[..., ::-1] / 2).astype(np.float32)


@pytest.mark.parametrize("kv_dtype", ["", "int8"], ids=["bf16", "int8"])
@pytest.mark.parametrize("path", ["snapshot_write", "swap", "cow"])
def test_pages_cross_the_devices_edge_per_head(path, kv_dtype):
    """write_pages -> snapshot_pages, swap out -> swap in and a
    copy-on-write page give back the same [L, n, page, K, Dh] arrays,
    whatever the layout on the device, where column k * Dh + d of a row
    is head k's element d."""
    cache = kvcache_mod.PagedKVCache(CFG, slots=2, pages=12, page_size=4,
                                     kv_dtype=kv_dtype)
    L, K, Dh = CFG.n_layers, CFG.kv_heads, CFG.d_head
    assert cache.state.pool_k.shape == (L, 12, 4, K * Dh)
    k, v = _per_head_pages(3, kv_dtype)
    ids = [5, 2, 9]
    cache.write_pages(ids, k, v)
    got_k, got_v = cache.read_pages(ids)
    assert got_k.shape == got_v.shape == (L, 3, 4, K, Dh)
    np.testing.assert_array_equal(got_k, k)
    np.testing.assert_array_equal(got_v, v)
    if not kv_dtype:
        on_device = np.asarray(cache.state.pool_k[:, 2], np.float32)
        np.testing.assert_array_equal(
            on_device.reshape(L, 4, K, Dh), k[:, 1])
    if path == "snapshot_write":
        snapshot = cache.snapshot_pages(ids)
        assert [a.shape for a in snapshot] == (
            [(L, 3, 4, K, Dh)] * 2
            + ([(L, 3, 4, K)] * 2 if kv_dtype else []))
        return
    stored = cache.swapout_pages(ids)
    assert stored[0].shape == stored[1].shape == (L, 3, 4, K, Dh)
    if path == "swap":
        cache.swapin_pages([0, 1, 3], stored)
        again = cache.swapout_pages([0, 1, 3])
    else:
        # Slot 0 takes pages 11, 10, 8 off the free list; a second holder
        # of its first page makes the next write there diverge.
        cache.admit(0, 12)
        src = cache.slot_pages(0)[0]
        cache.swapin_pages(cache.slot_pages(0), stored)
        cache.retain_pages([src])
        dst = cache.cow_page(0, 0)
        assert dst is not None and dst != src
        again = cache.swapout_pages([dst] + cache.slot_pages(0)[1:])
    for a, b in zip(again, stored):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(a, b)


def test_prefix_file_keeps_its_shapes_and_pr24s_file_loads(params,
                                                           tmp_path):
    """The persisted prefix cache holds [L, n, page, K, Dh] float32
    arrays, as it did before the pool's heads were merged on the device:
    a file written by PR 24's tree (tests/fixtures/prefix/pr24_bf16.npz:
    this CFG, PRNGKey(0), STEM + [7, 7] and 6 new tokens, page size 4)
    loads, its three entries serve the stem warm and the stream is the
    one that tree emitted; and a dump from this tree has the same
    arrays in it."""
    import pathlib

    old = pathlib.Path(__file__).parent / "fixtures/prefix/pr24_bf16.npz"
    prompt, emitted = STEM + [7, 7], [90, 104, 104, 69, 36, 106]
    server = PagedGenerationServer(params, CFG, slots=2, pages=24,
                                   page_size=4)
    try:
        assert server.load_prefix_cache(str(old), "pr24") == 3
        assert server.submit(prompt, n_new=6) == prompt + emitted
        assert server.stats()["prefix_hits"] == 1
        mine = str(tmp_path / "pc.npz")
        assert server.dump_prefix_cache(mine, "pr25") >= 3
    finally:
        server.close()
    with np.load(old) as was, np.load(mine) as now:
        for name in ("pool_k", "pool_v"):
            assert now[name].dtype == was[name].dtype == np.float32
            assert now[name].shape[2:] == was[name].shape[2:] == (
                4, CFG.kv_heads, CFG.d_head)
            assert now[name].shape[0] == CFG.n_layers
        np.testing.assert_array_equal(now["pool_k"][:, :3], was["pool_k"])
        np.testing.assert_array_equal(now["pool_v"][:, :3], was["pool_v"])
