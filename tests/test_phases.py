"""The clock inside the decode loop and the submit path (ISSUE 24).

Phases (runtime/tracing.py) are named stretches of one thread's time
with three sinks: an always-on accumulator that ``stats()`` exports, a
``jax.profiler.TraceAnnotation`` while a capture is live, a ring span
with the tracer on. Here: the loop's phases add up to the loop thread's
own time, the counts taken at the loop's boundaries obey their
identities after a fixed set of requests, a chunk made to wait for the
lock shows in its histogram, and a CPU capture holds every phase's
annotation on a host plane. No test asserts a wall-clock duration.
"""

import glob
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from kvedge_tpu.models import TransformerConfig, init_params
from kvedge_tpu.models.serving import PagedGenerationServer
from kvedge_tpu.runtime.tracing import (
    ADMIT_PHASES,
    LOOP_PHASES,
    Phase,
    PhaseClock,
    PhaseSum,
    Tracer,
)

pytestmark = pytest.mark.trace

CFG = TransformerConfig(
    vocab=128, d_model=32, n_heads=4, n_kv_heads=2, n_layers=2, d_ff=64,
    max_seq=64,
)
PAGE = 4
CHUNK = 4
# (prompt, n_new): distinct first tokens, so no prompt shares a prefix.
REQUESTS = [([5, 9, 2, 7, 1, 3, 4, 6, 8], 9), ([11, 12, 13, 14, 15], 12),
            ([21, 22, 23, 24, 25, 26, 27], 1), ([31, 32, 33], 10)]
# The shapes a trip of the one decode loop takes: greedy windows; a
# batch with a sampled row (``dispatch_window_sampled``); one-step
# windows (a program of its own, a harvest a token); the same loop over
# a recurrent block and over a window block (``probe_blocks``: state a
# row, a second page pool given back mid-request).
LOOPS = {
    "overlap": {},
    "sampled": {},
    "one-step": {"window": 1},
    "recurrent": {"block": "recurrent"},
    "window-block": {"block": "window-block"},
}


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


def _server(params, block=None, **kw):
    """``block``: a ``(cfg, params)`` of ``probe_blocks`` in place of
    the plain block's."""
    kw.setdefault("window", 4)
    cfg = CFG
    if block is not None:
        cfg, params = block
        kw["prefix_cache"] = False
    return PagedGenerationServer(params, cfg, slots=4, pages=48,
                                 page_size=PAGE, prefill_chunk=CHUNK, **kw)


def _loop_server(params, probe_blocks, loop):
    kw = dict(LOOPS[loop])
    return _server(params, probe_blocks.get(kw.pop("block", None)), **kw)


def _serve(server, requests=REQUESTS, loop=""):
    """The fixed set, all at once; returns the generated tokens. Under
    the ``sampled`` shape the second request (the longest) samples."""
    out = [None] * len(requests)
    sampling = (jax.random.fold_in(jax.random.PRNGKey(3), 0),
                jnp.float32(0.8), jnp.float32(0.9))

    def one(i, prompt, n_new):
        out[i] = server.submit(
            prompt, n_new,
            sampling=sampling if loop == "sampled" and i == 1 else None,
        )[len(prompt):]

    threads = [threading.Thread(target=one, args=(i, p, n))
               for i, (p, n) in enumerate(requests)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    assert all(o is not None for o in out)
    return out


# ---- the primitive -------------------------------------------------------


def test_a_phase_feeds_its_accumulator_the_ring_and_stops_once():
    tr = Tracer(sample=1.0)
    acc = PhaseSum()
    clock = PhaseClock({"a/b": acc, "quiet": PhaseSum()}, tr)
    with clock("a/b", rid="req-1", args={"n": 3}) as ph:
        ph.stop()
        first = ph.t1
    assert isinstance(ph, Phase) and ph.t1 == first  # the exit added nothing
    assert acc.n == 1 and acc.total == ph.ms == (ph.t1 - ph.t0) * 1e3
    with clock("quiet", ring=False):
        pass
    spans = [rec for rec in tr._snapshot()]
    assert [(r[3], r[5], r[6]) for r in spans] == [("a/b", "req-1", {"n": 3})]
    assert clock.snapshot(time.perf_counter()) == {
        "a/b": [1, acc.total], "quiet": [1, clock.sinks["quiet"].total]}
    with pytest.raises(KeyError):
        clock("no/such/phase")


def test_chained_phases_leave_nothing_between_them():
    clock = PhaseClock({"x": PhaseSum(), "y": PhaseSum(), "z": PhaseSum()},
                       chained=("x", "y"))
    start = clock.mark()
    with clock("x") as x:
        pass
    with clock("z") as z:  # not of the chain: its own start
        pass
    with clock("y") as y:
        pass
    assert x.t0 == start and y.t0 == x.t1 and z.t0 >= x.t1
    assert x.ms + y.ms == pytest.approx((y.t1 - start) * 1e3)
    # a snapshot counts the chain's open phase as far as it has got
    with clock("x") as again:
        assert clock.open == "x"
        now = again.t0 + 0.25
        assert clock.snapshot(now)["x"] == [1, pytest.approx(x.ms + 250.0)]
        assert clock.snapshot(now)["y"] == [1, y.ms]
    assert clock.open is None
    assert clock.snapshot(now)["x"] == [2, x.ms + again.ms]


def test_between_two_snapshots_the_loops_phases_gain_the_time_between(
        params):
    """What a reader of a live server does (the benchmark, at the
    window's edges): the loop is parked in ``loop/wait_work`` at the
    first snapshot and wherever it is at the others, and the six gain
    what ``clock_s`` gains."""
    server = _server(params)
    try:
        shots = [server.stats()]
        for prompt, n_new in REQUESTS[:2]:
            server.submit(prompt, n_new)
            shots.append(server.stats())
    finally:
        server.close()
    for a, b in zip(shots, shots[1:]):
        gained = sum(b["phase_ms"][n][1] - a["phase_ms"][n][1]
                     for n in LOOP_PHASES)
        between = (b["clock_s"] - a["clock_s"]) * 1e3
        assert 0.98 * between <= gained <= between * (1 + 1e-6) + 1e-3
        assert b["loop_ms_total"] - a["loop_ms_total"] \
            == pytest.approx(between)


# ---- the loop thread's time ----------------------------------------------


@pytest.mark.parametrize("loop", sorted(LOOPS))
def test_the_loops_phases_add_up_to_the_loop_threads_time(
        params, probe_blocks, loop):
    server = _loop_server(params, probe_blocks, loop)
    try:
        _serve(server, loop=loop)
    finally:
        server.close(drain=True)
    stats = server.stats()
    assert not server._thread.is_alive()
    phases = stats["phase_ms"]
    covered = sum(phases[name][1] for name in LOOP_PHASES)
    assert phases["loop/lock_wait"][0] >= 2   # once an iteration
    assert 0.98 * stats["loop_ms_total"] <= covered \
        <= stats["loop_ms_total"] * (1 + 1e-9)
    # held = the iteration less its waits, for the lock, for work and
    # (the lock released for it since PR 47) for a window: never more
    # than the time outside them
    busy = covered - phases["loop/lock_wait"][1] \
        - phases["loop/wait_work"][1] - phases["loop/harvest_wait"][1]
    assert 0 < stats["loop_lock_held_ms_total"] <= stats["loop_ms_total"]
    assert stats["loop_lock_held_ms_total"] >= 0.98 * busy
    # the two histograms ARE two of the phases: one record, two names
    assert phases["loop/harvest_wait"] == [
        stats["window_device_ms"]["count"], stats["window_device_ms"]["sum"]]
    assert phases["loop/emit"] == [
        stats["window_host_ms"]["count"], stats["window_host_ms"]["sum"]]
    assert phases["loop/harvest_wait"][0] == phases["loop/emit"][0] >= 1


# ---- counts at the loop's boundaries -------------------------------------


def _count_steps_at_the_cache(server):
    """Decode steps as the cache itself is asked for them, whatever the
    server books: window lengths at dispatch, single steps."""
    cache, seen = server._cache, {"steps": 0}

    def wrap(name, steps_of):
        inner = getattr(cache, name)

        def counted(*a, **kw):
            seen["steps"] += steps_of(a, kw)
            return inner(*a, **kw)

        setattr(cache, name, counted)

    wrap("dispatch_window", lambda a, kw: a[2])
    wrap("dispatch_window_sampled", lambda a, kw: a[2])
    wrap("step", lambda a, kw: 1)
    return seen


@pytest.mark.parametrize("loop", sorted(LOOPS))
def test_count_identities_after_a_fixed_set_of_requests(
        params, probe_blocks, loop):
    server = _loop_server(params, probe_blocks, loop)
    seen = _count_steps_at_the_cache(server)
    clocks = [server.stats()["clock_s"]]
    try:
        generated = _serve(server, loop=loop)
        clocks.append(server.stats()["clock_s"])
    finally:
        server.close(drain=True)
    stats = server.stats()
    clocks.append(stats["clock_s"])
    assert clocks == sorted(clocks) and clocks[0] < clocks[-1]
    assert [len(g) for g in generated] == [n for _, n in REQUESTS]
    chunks = sum(-(-len(p) // CHUNK) for p, _ in REQUESTS)
    assert stats["prefix_tokens_saved"] == 0
    assert stats["prefill_lock_wait_ms"]["count"] \
        == stats["prefill_chunk_ms"]["count"] == chunks
    assert stats["phase_ms"]["admit/first_pick"][0] == len(REQUESTS)
    assert stats["first_emit_ms"]["count"] == len(REQUESTS)
    assert stats["tokens_emitted_total"] == sum(n for _, n in REQUESTS)
    assert stats["decode_steps_total"] == seen["steps"] >= 1
    assert 0 < stats["decode_row_steps_total"] \
        <= stats["decode_bucket_steps_total"]
    assert stats["decode_bucket_steps_total"] \
        <= stats["decode_steps_total"] * stats["slots_total"]
    assert 0 < stats["pages_live_steps_total"] \
        <= stats["decode_steps_total"] * stats["pages_total"]


def test_a_shared_prefix_takes_its_chunks_off_the_count(params):
    """⌈(prompt − shared) / chunk⌉: the second request starts on the
    first one's cached pages and prefills only what is left, and a
    shared page is counted once among the pages in use."""
    server = _server(params)
    prompt = list(range(40, 53))          # 13 tokens: 3 full pages and 1
    try:
        server.submit(prompt, 3)
        before = server.stats()
        server.submit(prompt[:12] + [99], 3)
        after = server.stats()
    finally:
        server.close(drain=True)
    shared = after["prefix_tokens_saved"] - before["prefix_tokens_saved"]
    assert shared >= PAGE
    assert after["prefill_chunk_ms"]["count"] \
        - before["prefill_chunk_ms"]["count"] == -(-(13 - shared) // CHUNK)
    steps = after["decode_steps_total"] - before["decode_steps_total"]
    live = after["pages_live_steps_total"] - before["pages_live_steps_total"]
    assert 0 < live <= steps * -(-(13 + 3) // PAGE)


# ---- a chunk behind the lock ---------------------------------------------


def test_a_chunk_that_waits_for_the_lock_shows_in_its_histogram(params):
    """The second chunk of a prompt is made to ask for the work lock
    while this test holds it; what the histogram gained is no less than
    the stretch between two of the test's own stamps inside the hold."""
    server = _server(params)
    asking, held, release = (threading.Event() for _ in range(3))

    class Pausing(list):
        """The prompt, stopping its reader where the submit path cuts
        the second chunk: after chunk 1's release (its wait for the
        lock already started) and before it asks for the lock again."""

        def __getitem__(self, key):
            if isinstance(key, slice) and key.start == CHUNK:
                asking.set()
                assert held.wait(timeout=120)   # the test holds it now
            return list.__getitem__(self, key)

    make_wait = server._admit_wait

    def pausing(req, off):
        if not isinstance(req.prompt, Pausing):
            req.prompt = Pausing(req.prompt)
        return make_wait(req, off)

    def hold():
        with server._lock:
            held.set()
            assert release.wait(timeout=120)

    server._admit_wait = pausing
    result = {}
    submit = threading.Thread(
        target=lambda: result.update(out=server.submit(
            [61, 62, 63, 64, 65, 66], 2)))
    holder = threading.Thread(target=hold)
    try:
        submit.start()
        assert asking.wait(timeout=120)   # t0 of the wait is behind us
        holder.start()
        assert held.wait(timeout=120)
        t_held = time.perf_counter()
        time.sleep(0.05)
        t_release = time.perf_counter()   # the lock is still held here
        release.set()
        submit.join(timeout=120)
        holder.join(timeout=120)
        assert not submit.is_alive() and not holder.is_alive()
        stats = server.stats()
    finally:
        release.set()
        server.close()
    assert len(result["out"]) == 8
    waits = stats["prefill_lock_wait_ms"]
    assert waits["count"] == 2
    assert waits["sum"] >= (t_release - t_held) * 1e3
    assert waits["count"] == sum(waits["counts"])


# ---- the profiler's sink -------------------------------------------------


@pytest.fixture(scope="module")
def capture(params, tmp_path_factory):
    """One CPU capture around two requests with an idle stretch between
    them (``loop/wait_work`` is entered and left inside the session)."""
    from jax.profiler import ProfileData

    path = str(tmp_path_factory.mktemp("capture"))
    server = _server(params)
    jax.profiler.start_trace(path)
    try:
        server.submit([5, 9, 2, 7, 1, 3], 6)
        # the next request finds the loop parked inside Condition.wait
        # (its wait_work entered inside this session), not still busy
        limit = time.monotonic() + 120
        while not server._work._waiters and time.monotonic() < limit:
            time.sleep(0.01)
        assert server._work._waiters
        server.submit([8, 9, 2, 7, 1, 3], 6)
    finally:
        jax.profiler.stop_trace()
        server.close()
    (found,) = glob.glob(path + "/plugins/profile/*/*.xplane.pb")
    names: dict = {}
    for plane in ProfileData.from_file(found).planes:
        for index, line in enumerate(plane.lines):
            for event in line.events:
                if event.name.startswith("kvedge/"):
                    names.setdefault(event.name, set()).add(
                        (plane.name, index))
    return names


@pytest.mark.parametrize("name", LOOP_PHASES + ADMIT_PHASES)
def test_a_capture_holds_every_phase_on_a_host_plane(capture, name):
    where = capture["kvedge/" + name]
    assert where and all(plane.startswith("/host:") for plane, _ in where)
    loop_lines = {w for n in LOOP_PHASES for w in capture["kvedge/" + n]}
    # one thread, one line: the loop's phases share theirs, and no
    # handler's phase is on it
    assert len(loop_lines) == 1
    if name in ADMIT_PHASES:
        assert not where & loop_lines


@pytest.mark.parametrize("holder", ["loop", "admit/start",
                                    "admit/prefill_chunk",
                                    "admit/first_pick"])
def test_a_capture_holds_every_hold_of_the_lock_on_its_threads_line(
        capture, holder):
    """The lock's ledger (ISSUE 38) on the profiler's clock: a hold is
    ``kvedge/lock/<holder>`` on the line of the thread that holds, the
    loop's on the loop's line and a handler's on none of the loop's."""
    where = capture["kvedge/lock/" + holder]
    assert where and all(plane.startswith("/host:") for plane, _ in where)
    loop_lines = {w for n in LOOP_PHASES for w in capture["kvedge/" + n]}
    if holder == "loop":
        assert where == loop_lines
    else:
        assert not where & loop_lines


def test_tokens_do_not_depend_on_a_live_capture(params, tmp_path):
    def tokens():
        server = _server(params)
        try:
            key = jax.random.fold_in(jax.random.PRNGKey(3), 0)
            return (server.submit([5, 9, 2, 7], 9),
                    server.submit([1, 2, 3, 4], 8, sampling=(
                        key, jnp.float32(0.8), jnp.float32(0.9))))
        finally:
            server.close()

    plain = tokens()
    jax.profiler.start_trace(str(tmp_path))
    try:
        traced = tokens()
    finally:
        jax.profiler.stop_trace()
    assert plain == traced
