"""Cross-host paged serving (runtime/sliceserve.py), single-process leg.

The slice protocol's leader side runs the UNMODIFIED serving stack over
a ``SlicePagedKVCache`` whose device seams broadcast before executing.
On a single-process mesh the broadcast degenerates to a copy, so the
whole leader path — global-array state, re-jitted kernels with pinned
replicated out-shardings, host-mask derivation — is testable in-process
against the plain cache/server, with exactness pinned the same way every
other serving backend is. The 2-process proof (real op-stream replay by
a follower) lives in tests/test_distributed.py.
"""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from kvedge_tpu.models import TransformerConfig, generate, init_params
from kvedge_tpu.models.kvcache import PagedKVCache
from kvedge_tpu.models.serving import PagedGenerationServer
from kvedge_tpu.runtime.sliceserve import SlicePagedKVCache

CFG = TransformerConfig(
    vocab=128, d_model=32, n_heads=4, n_kv_heads=2, n_layers=2, d_ff=64,
    max_seq=64,
)


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


@pytest.fixture(scope="module")
def mesh():
    devs = np.array(jax.devices()).reshape(2, 4)
    return Mesh(devs, ("data", "model"))


def _slice_server(params, mesh, **kw):
    cache = SlicePagedKVCache(
        CFG, slots=kw.pop("slots", 3), pages=kw.pop("pages", 24),
        page_size=kw.pop("page_size", 16), mesh=mesh,
    )
    return PagedGenerationServer(params, CFG, cache=cache, **kw)


def reference(params, prompt, n_new):
    out = generate(params, jnp.asarray([prompt], jnp.int32), CFG,
                   n_new=n_new)
    return [int(t) for t in np.asarray(out)[0]]


def test_every_slice_op_is_sent_and_handled():
    """The op table has no dead entry: every ``OP_*`` code is sent by
    a leader method and replayed by the follower, none is replayed
    that nothing sends, and each has its span name."""
    import ast
    import inspect

    from kvedge_tpu.runtime import sliceserve

    ops = {name for name in vars(sliceserve) if name.startswith("OP_")}
    codes = {name: getattr(sliceserve, name) for name in ops}
    assert sorted(codes.values()) == list(range(len(ops)))
    assert set(sliceserve._OP_NAMES) == set(codes.values())

    mentions: dict = {}
    tree = ast.parse(inspect.getsource(SlicePagedKVCache))
    for fn in tree.body[0].body:
        if isinstance(fn, ast.FunctionDef):
            mentions[fn.name] = {
                node.id for node in ast.walk(fn)
                if isinstance(node, ast.Name) and node.id in ops}
    follower = ("_follow_op", "_replay_packed", "_multi_templates")
    sent = set().union(*(names for fn, names in mentions.items()
                         if fn not in follower))
    coalesced = {name for name in ops
                 if codes[name] in sliceserve._COALESCABLE}
    # A coalescable op needs its payload's shapes and its replay; the
    # others are branches of _follow_op itself.
    assert mentions["_multi_templates"] == coalesced
    assert mentions["_replay_packed"] == coalesced
    handled = mentions["_follow_op"] | coalesced
    assert sent == handled == ops, (sent ^ handled, ops - sent)


# The ten ops left since the paged server stopped speculating (PR 48),
# in the order they are numbered.
OPS = ("OP_STOP", "OP_SYNC", "OP_PREFILL", "OP_STEP", "OP_WINDOWP",
       "OP_WSAMPLEP", "OP_SWAPOUT", "OP_SWAPIN", "OP_MULTI", "OP_COWP")


@pytest.mark.parametrize("code, name", list(enumerate(OPS)))
def test_the_op_table_is_these_ten_codes(code, name):
    """Ten codes from ``range(10)``, each with its span name, and none
    besides (the sender and the follower's branch of each:
    ``test_every_slice_op_is_sent_and_handled``)."""
    from kvedge_tpu.runtime import sliceserve

    assert getattr(sliceserve, name) == code
    assert sliceserve._OP_NAMES[code] == name[3:].lower()
    assert {n for n in vars(sliceserve) if n.startswith("OP_")} == set(OPS)


def test_slice_cache_matches_plain_cache_step_and_window(params, mesh):
    """Direct cache equality: chunked prefill + per-token steps + a
    device window produce identical tokens through both caches."""
    plain = PagedKVCache(CFG, slots=2, pages=16, page_size=4)
    sliced = SlicePagedKVCache(
        CFG, slots=2, pages=16, page_size=4, mesh=mesh
    )
    prompt = [3, 1, 4, 1, 5, 9, 2]
    seqs = []
    for cache in (plain, sliced):
        cache.admit(0, len(prompt))
        logits = None
        for off in range(0, len(prompt), 3):  # chunked prefill
            piece = jnp.asarray(prompt[off:off + 3], jnp.int32)
            logits = cache.prefill_chunk(params, 0, piece, off)
        tok = int(np.argmax(np.asarray(logits)))
        toks = [tok]
        active = np.array([True, False])
        for _ in range(3):
            step_logits = cache.step(
                params, jnp.asarray([tok, 0], jnp.int32), active=active
            )
            tok = int(np.argmax(np.asarray(step_logits)[0]))
            toks.append(tok)
        window = cache.harvest_window(cache.dispatch_window(
            params, np.asarray([tok, 0], np.int32), 4, active=active
        ))[:4]
        toks.extend(int(t) for t in window[:, 0])
        seqs.append(toks)
    assert seqs[0] == seqs[1]


def test_slice_server_greedy_matches_generate(params, mesh):
    server = _slice_server(params, mesh)
    try:
        prompt = [5, 9, 2, 7, 1]
        assert server.submit(prompt, n_new=6) == reference(
            params, prompt, 6
        )
    finally:
        server.close()


def test_slice_server_concurrent_requests_each_match(params, mesh):
    """Concurrent ragged requests through the slice cache ride one
    batched step (windows included) and each still equals its own
    contiguous decode — continuous batching is preserved across the
    broadcast seams."""
    server = _slice_server(params, mesh)
    requests = [([5, 9, 2], 8), ([1, 1, 4, 3, 7, 7], 4), ([100, 50], 12)]
    results: dict[int, list[int]] = {}
    errors: list[Exception] = []

    def worker(i, prompt, n_new):
        try:
            results[i] = server.submit(prompt, n_new)
        except Exception as e:
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
        for i, (prompt, n_new) in enumerate(requests):
            assert results[i] == reference(params, prompt, n_new), i
    finally:
        server.close()


def test_slice_server_sampled_and_streamed_match_plain_server(
        params, mesh):
    """Sampling is leader-local (only chosen tokens enter the op
    stream): a sampled and a streamed request through the slice server
    must match the plain single-host paged server exactly."""
    sampling = (jax.random.fold_in(jax.random.PRNGKey(7), 0),
                jnp.float32(0.8), jnp.float32(0.9))
    prompt = [9, 8, 7, 6]

    plain = PagedGenerationServer(params, CFG, slots=2, pages=16)
    try:
        want_sampled = plain.submit(prompt, 5, sampling=sampling)
        want_streamed = list(plain.submit_stream(prompt, 5))
    finally:
        plain.close()

    server = _slice_server(params, mesh, slots=2, pages=16)
    try:
        assert server.submit(prompt, 5, sampling=sampling) == want_sampled
        assert list(server.submit_stream(prompt, 5)) == want_streamed
    finally:
        server.close()


def test_sharded_pool_matches_reference(params):
    """When kv_heads divides the model axis size the K/V pools shard
    over it (a model-sharded layer's K/V scatters stay local); tokens
    must still equal the contiguous decode exactly."""
    from jax.sharding import PartitionSpec as P

    devs = np.array(jax.devices()).reshape(4, 2)
    mesh = Mesh(devs, ("data", "model"))
    cache = SlicePagedKVCache(CFG, slots=2, pages=16, page_size=8,
                              mesh=mesh)
    assert cache.state.pool_k.sharding.spec == P(None, None, None, "model")
    server = PagedGenerationServer(params, CFG, cache=cache)
    try:
        prompt = [5, 9, 2, 7, 1]
        assert server.submit(prompt, n_new=6) == reference(
            params, prompt, 6
        )
    finally:
        server.close()


def test_hard_close_mid_request_and_double_close_do_not_hang(
        params, mesh):
    """The follower-release (OP_STOP) rides the server's close under
    the server lock: a hard close racing an in-flight request must not
    let the request's teardown broadcast after STOP (its table sync
    becomes a local no-op), and a second close() must not broadcast a
    second STOP (idempotent flag). Either bug hangs the leader in a
    collective — this test completing IS the assertion."""
    server = _slice_server(params, mesh, slots=2, pages=16)
    errors: list = []

    def worker():
        try:
            server.submit([1, 2, 3], n_new=40)
        except Exception as e:
            errors.append(e)

    t = threading.Thread(target=worker)
    t.start()
    while server.stats()["in_flight"] == 0 and t.is_alive():
        time.sleep(0.001)  # request admitted (or already failed)
    server.close()           # hard close mid-decode
    server.close()           # idempotent second close
    t.join(timeout=60)
    assert not t.is_alive()
    assert server._cache._stopped


def test_slice_server_prefix_sharing_stays_exact(params, mesh):
    """The prefix registry (host-only leader state) composes with the
    slice cache: a repeated prompt reuses pinned pages and still decodes
    the same tokens."""
    server = _slice_server(params, mesh, page_size=4)
    try:
        prompt = [11, 12, 13, 14, 15, 16, 17, 18, 19]
        first = server.submit(prompt, n_new=4)
        again = server.submit(prompt, n_new=4)
        assert first == again == reference(params, prompt, 4)
        assert server.stats()["prefix_hits"] >= 1
    finally:
        server.close()


def test_slice_cache_refuses_prefix_persistence(params, mesh):
    """Prefix-cache dump/load would run leader-only computations on
    global arrays — a collective the followers never join. The refusal
    lives with the API (read_pages/write_pages raise), not just at the
    workload call-site guard."""
    import pytest

    from kvedge_tpu.models.kvcache import PagedCacheError
    from kvedge_tpu.runtime.sliceserve import SlicePagedKVCache

    cache = SlicePagedKVCache(
        CFG, slots=2, pages=16, page_size=4, mesh=mesh
    )
    with pytest.raises(PagedCacheError, match="single-host|not supported"):
        cache.read_pages([0])
    with pytest.raises(PagedCacheError, match="single-host|not supported"):
        cache.write_pages([0], None, None)


def test_slice_cache_pins_gather_attention(mesh):
    """A slice cache downgrades even an explicit 'kernel' to the gather
    path: the Pallas kernel has no partitioning rule, so a sharded
    trace would poison the first decode step on a real slice. The pin
    is part of the construction protocol (every process replaces cfg
    identically), so it must hold before any device op runs."""
    import dataclasses

    forced = dataclasses.replace(CFG, paged_attention="kernel")
    cache = SlicePagedKVCache(
        forced, slots=2, pages=8, page_size=4, mesh=mesh
    )
    assert cache.cfg.paged_attention == "gather"
    auto = SlicePagedKVCache(
        CFG, slots=2, pages=8, page_size=4, mesh=mesh
    )
    assert auto.cfg.paged_attention == "gather"


def test_slice_stop_after_dead_stream_is_bounded(params, mesh):
    """stop() must not broadcast into a dead op stream: once the
    watchdog latched an op timeout, close() returns without queuing the
    STOP collective the departed followers would never join."""
    import time as _time

    from kvedge_tpu.runtime.failures import OpBudgets, SliceFollowerLost

    cache = SlicePagedKVCache(
        CFG, slots=2, pages=16, page_size=4, mesh=mesh,
        op_budgets=OpBudgets(steady_s=0.5, compile_s=0.5),
    )
    release = threading.Event()
    orig = cache._bcast

    def wedged(tree):
        release.wait(30)
        raise RuntimeError("wedged bcast released")

    cache._bcast = wedged
    cache.admit(0, 4)  # admit QUEUES the table sync (deferred, rung 23)
    with pytest.raises(SliceFollowerLost):
        cache._flush_ops()  # the flush is the first broadcast — wedges
    assert cache._ops.dead is not None
    cache._bcast = orig
    start = _time.monotonic()
    cache.stop()
    assert _time.monotonic() - start < 5.0
    release.set()


@pytest.mark.parametrize("joined", [False, True],
                         ids=["carry", "newcomer-joins"])
def test_slice_pipelined_windows_replay_matches_plain(params, mesh,
                                                      joined):
    """OP_WINDOWP protocol replay (degenerate single-process broadcast):
    two pipelined windows — the second dispatched on the device carry
    BEFORE the first is harvested, header + payload riding the ordered
    op stream, the harvest deliberately NOT a broadcast — produce the
    plain cache's pipelined tokens exactly. ``joined``: a second row
    sat the first window out and enters the second from the host's
    row (its entry of the broadcast row states its token, the other is
    below 0 and takes the carry's), joined on every process alike."""
    prompts = {0: [3, 1, 4, 1, 5, 9, 2]}
    if joined:
        prompts[1] = [2, 7, 1]
    seqs = []
    for cache in (
        PagedKVCache(CFG, slots=2, pages=16, page_size=4),
        SlicePagedKVCache(CFG, slots=2, pages=16, page_size=4,
                          mesh=mesh),
    ):
        pend = np.zeros((2,), np.int32)
        for slot, prompt in prompts.items():
            cache.admit(slot, len(prompt))
            logits = cache.prefill(params, slot,
                                   jnp.asarray(prompt, jnp.int32))
            pend[slot] = int(np.argmax(np.asarray(logits)))
        active = np.array([True, False])
        h1 = cache.dispatch_window(params, jnp.asarray(pend), 4,
                                   active=active)
        if joined:
            h2 = cache.dispatch_window(
                params, np.array([-1, pend[1]], np.int32), 4)
        else:
            h2 = cache.dispatch_window(params, None, 4, active=active)
        toks = np.concatenate([np.asarray(cache.harvest_window(h1)),
                               np.asarray(cache.harvest_window(h2))])
        cache.drop_carry()
        seqs.append(toks[:, :len(prompts)].tolist())
        assert cache._carry is None
    assert seqs[0] == seqs[1]


def test_slice_overlap_server_greedy_and_sampled_match_plain(params,
                                                             mesh):
    """The pipelined serving loop over the slice cache (OP_WINDOWP /
    OP_WSAMPLEP in steady state) serves the same tokens as the plain
    pipelined server — greedy against contiguous generate, sampled
    bit-identical across backends under one seed."""
    key = jax.random.fold_in(jax.random.PRNGKey(3), 0)
    prompt_g, prompt_s = [5, 9, 2, 7, 1], [1, 2, 3, 4]
    plain = PagedGenerationServer(params, CFG, slots=3, pages=24)
    sliced = _slice_server(params, mesh)
    try:
        results = []
        for server in (plain, sliced):
            sampling = (key, jnp.float32(0.8), jnp.float32(0.9))
            greedy = server.submit(prompt_g, n_new=12)
            sampled = server.submit(prompt_s, n_new=18,
                                    sampling=sampling)
            results.append((greedy, sampled))
        assert results[0] == results[1]
        assert results[0][0] == reference(params, prompt_g, 12)
    finally:
        plain.close()
        sliced.close()

@pytest.mark.parametrize("tree", ["masters", "cast"])
def test_slice_multi_frame_follower_replay_matches_leader(params, mesh,
                                                          tree):
    """Leader and follower read the same tree, float32 masters or, as
    the serve payload hands both (workload._restore_serving_params), the
    one cast at load: the replay is exact over either.

    Coalesced broadcasts (SERVING.md rung 23), end to end: a page
    boundary queues the table sync, and the window dispatch a moment
    later flushes sync + dispatch as ONE framed OP_MULTI broadcast.
    The leader's recorded op stream — frames included — replayed
    through the REAL follower loop on a second cache reproduces the
    leader's device tokens bit-exactly, which pins both the frame
    carving (_multi_templates offsets) and the shared exec path."""
    from kvedge_tpu.models import serving_params
    from kvedge_tpu.runtime.sliceserve import OP_MULTI, follow_paged

    if tree == "cast":
        params = serving_params(params, CFG)
        assert params["w_qkv"].dtype == jnp.bfloat16
    leader = SlicePagedKVCache(CFG, slots=2, pages=16, page_size=4,
                               mesh=mesh)
    log = []
    orig = leader._bcast

    def recording(tree):
        out = orig(tree)
        log.append(out)
        return out

    leader._bcast = recording
    prompt = [3, 1, 4, 1, 5, 9, 2]
    leader.admit(0, len(prompt))
    logits = leader.prefill(params, 0, jnp.asarray(prompt, jnp.int32))
    pend = np.zeros((2,), np.int32)
    pend[0] = int(np.argmax(np.asarray(logits)))
    active = np.array([True, False])
    h1 = leader.dispatch_window(params, jnp.asarray(pend), 4,
                                active=active)
    h2 = leader.dispatch_window(params, None, 4, active=active)
    want = np.asarray(leader.harvest_window(h2))
    leader.drop_carry()
    leader.stop()  # OP_STOP ends the recorded stream
    # Page growth put a sync in front of each dispatch: both flushes
    # actually coalesced (2 ops per frame), and the frames are on the
    # wire as OP_MULTI headers.
    assert leader.coalesced_flushes >= 1
    assert leader.coalesced_ops >= 2 * leader.coalesced_flushes
    headers = [t for t in log
               if isinstance(t, np.ndarray) and t.shape == (4,)
               and t.dtype == np.int64]
    assert any(int(h[0]) == OP_MULTI for h in headers)

    follower = SlicePagedKVCache(CFG, slots=2, pages=16, page_size=4,
                                 mesh=mesh)
    replay = iter(log)
    follower._bcast = lambda tree: next(replay)
    follow_paged(follower, params)
    toks, n_steps = follower._carry
    assert n_steps == 4
    np.testing.assert_array_equal(np.asarray(toks), want)


