"""Two ledgers that close (ISSUE 38).

The work lock keeps its own account of the time it is held
(``tracing.TimedLock``) and every site that takes it says who it is
(``tracing.Hold``, one of ``LOCK_HOLDERS``): the names add up to the
lock's own count. A request passes through states from submit to its
last token (``REQUEST_STATES``), each boundary one stamp, so its states
add up to its life; finished requests' states are summed in
``stats()["request_ms"]``. And ``stats()`` does not wait for the lock
it measures. No test asserts a duration of the server's own work: only
sums that must close, and the test's own sleeps.
"""

import ast
import json
import os
import threading
import time

import jax
import pytest

from kvedge_tpu.models import TransformerConfig, init_params
from kvedge_tpu.models.serving import (
    PagedGenerationServer,
    RequestCancelled,
)
from kvedge_tpu.runtime.debuglock import DebugLock
from kvedge_tpu.runtime.tracing import (
    LOCK_HOLDERS,
    REQUEST_STATES,
    LockLedger,
    TimedLock,
    Tracer,
)

pytestmark = pytest.mark.trace

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = TransformerConfig(
    vocab=128, d_model=32, n_heads=4, n_kv_heads=2, n_layers=2, d_ff=64,
    max_seq=64,
)
PAGE = 4
CHUNK = 4


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


# The plain block, and the two ledgers over a patterned one: a recurrent
# block (no pages for the state) and a window block (two pools, pages
# given back mid-request: a request of 48 positions passes the window
# of 24).
BLOCKS = pytest.mark.parametrize(
    "block", ["plain", "recurrent", "window-block"])


def _unnamed_pct(a: dict, b: dict) -> float:
    total = b["lock_held_ms_total"] - a["lock_held_ms_total"]
    named = sum(b["lock_held_ms"][n][1] - a["lock_held_ms"][n][1]
                for n in LOCK_HOLDERS)
    assert total > 0
    return 100.0 * (total - named) / total


# ---- the primitives ------------------------------------------------------


@pytest.mark.parametrize("inner", [threading.Lock, DebugLock])
def test_the_lock_counts_its_own_holds_and_a_parked_waiter_holds_nothing(
        inner):
    lock = TimedLock(inner())
    cond = threading.Condition(lock)
    woke = threading.Event()

    def waiter():
        with cond:
            cond.wait(timeout=60)
            woke.set()

    thread = threading.Thread(target=waiter)
    thread.start()
    limit = time.monotonic() + 60
    while not cond._waiters and time.monotonic() < limit:
        time.sleep(0.005)
    assert cond._waiters and not lock.locked()
    before = lock.held_ms_total
    time.sleep(0.1)                 # parked: the lock is free, and says so
    assert lock.held_ms_total == before
    with lock:
        t0 = time.perf_counter()
        time.sleep(0.05)
        held = (time.perf_counter() - t0) * 1e3
        cond.notify_all()
    thread.join(timeout=60)
    assert woke.is_set() and not lock.locked()
    gained = lock.held_ms_total - before
    # this hold and the waiter's two short ones, and no 100 ms of parking
    assert held <= gained < held + 40.0
    assert lock.acquire(timeout=1) and lock.locked()
    lock.release()
    assert not lock.locked()


def test_a_hold_has_a_name_a_wait_and_a_total_that_agree():
    tr = Tracer(sample=1.0)
    ledger = LockLedger(TimedLock(threading.Lock()), tr)
    with pytest.raises(KeyError):
        ledger.hold("somebody")
    with ledger.hold("control") as hold:
        with pytest.raises(KeyError):
            hold.switch("nobody")
        time.sleep(0.01)
        open_shot = ledger.snapshot(time.perf_counter())
        boundary = hold.switch("cancel")
        assert boundary == hold.last > hold.t0
    shot = ledger.snapshot(time.perf_counter())
    held = shot["lock_held_ms"]
    assert set(held) == set(shot["lock_wait_ms"]) == set(LOCK_HOLDERS)
    assert held["control"][0] == held["cancel"][0] == 1
    assert held["control"][1] == pytest.approx((boundary - hold.t0) * 1e3)
    named = sum(ms for _, ms in held.values())
    assert 0.0 <= shot["lock_held_ms_total"] - named < 0.5
    # a hold under way is counted as far as it has got, name and total
    assert open_shot["lock_held_ms"]["control"] == [
        0, pytest.approx(open_shot["lock_held_ms_total"])]
    assert open_shot["lock_held_ms_total"] >= 10.0
    assert shot["lock_wait_ms"]["control"][0] == 1
    # unsampled by default: nothing of it in the ring, and with a
    # request's id its holds are spans
    assert not len(tr)
    with ledger.hold("cancel", rid="req-1", ring=True):
        pass
    assert [(r[3], r[5]) for r in tr._snapshot()] == [
        ("lock/cancel", "req-1")]


def test_a_hold_that_runs_out_of_time_records_nothing():
    ledger = LockLedger(TimedLock(threading.Lock()))
    with ledger.hold("control"):
        late = ledger.hold("stats")
        t0 = time.perf_counter()
        assert late.acquire(timeout=0.01) is False
        assert time.perf_counter() - t0 < 1.0
    shot = ledger.snapshot(time.perf_counter())
    assert shot["lock_wait_ms"]["stats"] == [0, 0.0]
    assert shot["lock_held_ms"]["stats"] == [0, 0.0]


# ---- (a) the lock's ledger on a live server ------------------------------


def test_every_lock_site_of_the_server_says_who_it_is():
    """No ``with self._work:`` / ``with self._lock:`` and no bare
    ``acquire`` in models/serving.py: the lock is taken through
    ``self._hold("<name>")`` with a literal name of ``LOCK_HOLDERS``
    (or through a hold made ahead by ``_admit_wait``, which names it
    the same way), and ``switch`` names its target likewise."""
    path = os.path.join(REPO, "kvedge_tpu", "models", "serving.py")
    with open(path) as fh:
        tree = ast.parse(fh.read())
    named, bare = [], []
    for node in ast.walk(tree):
        if isinstance(node, (ast.With, ast.AsyncWith)):
            for item in node.items:
                expr = item.context_expr
                if (isinstance(expr, ast.Attribute)
                        and expr.attr in ("_work", "_lock")):
                    bare.append(node.lineno)
        if not isinstance(node, ast.Call) \
                or not isinstance(node.func, ast.Attribute):
            continue
        if node.func.attr in ("_hold", "switch"):
            arg = node.args[0]
            if isinstance(arg, ast.IfExp):
                named += [arg.body, arg.orelse]
            else:
                named.append(arg)
        elif node.func.attr == "acquire":
            owner = node.func.value
            # a Hold's own acquire (stats(): one, with a time limit)
            if not (isinstance(owner, ast.Name) and owner.id == "hold"):
                bare.append(node.lineno)
    assert not bare, f"lock taken without a name at lines {bare}"
    assert len(named) >= 15
    for arg in named:
        assert isinstance(arg, ast.Constant) and arg.value in LOCK_HOLDERS, \
            f"line {arg.lineno}: not a literal holder name"
    assert {arg.value for arg in named} == set(LOCK_HOLDERS)


@BLOCKS
def test_the_lock_ledger_closes_over_admissions_cancels_stats_and_a_close(
        params, probe_blocks, block):
    server = _server(params, probe_blocks.get(block))
    stop = threading.Event()
    seen = []

    def storm():
        while not stop.is_set():
            seen.append(server.stats()["clock_s"])

    reader = threading.Thread(target=storm)
    first = server.stats()
    try:
        reader.start()
        streams = [server.submit_stream([i + 1, 9, 2, 7, 1, 3, 4, 6], 40)
                   for i in range(3)]
        for stream in streams:
            next(stream)
        streams[0].cancel()
        with pytest.raises(RequestCancelled):
            list(streams[0])
        for stream in streams[1:]:
            assert len(list(stream)) == 39
        server.submit([5, 5, 5], 6)
        server.set_params(server._params)
    finally:
        stop.set()
        reader.join(timeout=60)
        server.close()
    last = server.stats()
    assert len(seen) > 10 and seen == sorted(seen)
    assert _unnamed_pct(first, last) < 1.0
    if block == "window-block":
        assert last["window_pages_released_total"] > 0
    held, waits = last["lock_held_ms"], last["lock_wait_ms"]
    for name in LOCK_HOLDERS:
        assert held[name][0] >= 1 and held[name][1] > 0.0, name
        assert waits[name][0] >= 1, name
    # one record, two names: the loop's share under its old name, the
    # loop's and a chunk's waits in the phases they were before
    assert last["loop_lock_held_ms_total"] == held["loop"][1]
    assert waits["loop"] == last["phase_ms"]["loop/lock_wait"]
    assert waits["admit/prefill_chunk"] == last["phase_ms"]["admit/lock_wait"]
    assert held["admit/prefill_chunk"][0] \
        == last["prefill_chunk_ms"]["count"] == 3 * 2 + 1
    assert held["admit/first_pick"][0] == 4
    # a hold cannot be shorter than the phases inside it
    assert held["admit/prefill_chunk"][1] >= last["prefill_chunk_ms"]["sum"]
    assert last["lock_held_ms_total"] <= (last["clock_s"]
                                          - first["clock_s"]) * 1e3 + 1.0


def test_an_unnamed_hold_shows_as_unnamed(params):
    """What the remainder is for: a site that takes the lock without a
    name (here the test, as ``with server._lock``) is in the lock's own
    total and in no holder's."""
    server = _server(params)
    try:
        server.submit([5, 9, 2, 7], 4)
        before = server.stats()
        with server._lock:
            time.sleep(0.05)
        after = server.stats()
    finally:
        server.close()
    total = after["lock_held_ms_total"] - before["lock_held_ms_total"]
    named = sum(after["lock_held_ms"][n][1] - before["lock_held_ms"][n][1]
                for n in LOCK_HOLDERS)
    assert total - named >= 50.0


def test_the_parked_loop_and_a_parked_waiter_hold_nothing(params):
    """An idle server's loop is inside ``loop/wait_work``; a request
    that cannot have a slot is parked in the scheduler's queue. Neither
    is a hold: while both sleep the lock's total stands still."""
    server = PagedGenerationServer(params, CFG, slots=1, pages=48,
                                   page_size=PAGE, prefill_chunk=CHUNK,
                                   window=4)
    gate = threading.Event()
    wait = server._cache.await_window

    def gated_wait(handle):
        # where the loop stands with the lock free since PR 47: in its
        # wait for a window (it leaves its hold for nothing else while
        # windows are in flight)
        while gate.is_set():
            time.sleep(0.001)
        return wait(handle)

    try:
        limit = time.monotonic() + 60
        while not server._work._waiters and time.monotonic() < limit:
            time.sleep(0.005)
        assert server._work._waiters        # parked for work
        idle = [server.stats()]
        time.sleep(0.1)
        idle.append(server.stats())
        server._cache.await_window = gated_wait
        occupier = server.submit_stream([7, 7, 7], 30)
        next(occupier)
        gate.set()                          # the loop stands still, lock free
        parked = threading.Thread(
            target=lambda: server.submit([1, 2, 3], 2))
        parked.start()
        while (server.stats()["sched_queue_depth_interactive"] < 1
               and time.monotonic() < limit):
            time.sleep(0.005)
        queued = [server.stats()]
        time.sleep(0.1)
        queued.append(server.stats())
        gate.clear()
        parked.join(timeout=120)
        assert not parked.is_alive()
        assert len(list(occupier)) == 29
    finally:
        gate.clear()
        server.close()
    for a, b in (idle, queued):
        assert (b["clock_s"] - a["clock_s"]) * 1e3 >= 100.0
        # all that held the lock in a tenth of a second is this
        # test's own stats()
        assert b["lock_held_ms_total"] - a["lock_held_ms_total"] < 20.0
        assert _unnamed_pct(a, b) < 1.0 or \
            b["lock_held_ms_total"] - a["lock_held_ms_total"] < 1.0
    # the parked request's wait is its queue time, not the admission's hold
    last = queued[-1]
    assert last["lock_held_ms"]["admit/start"][1] < 100.0


def test_first_picks_wait_plus_its_hold_is_the_phase(params):
    server = _server(params)
    try:
        for prompt in ([5, 9, 2, 7, 1], [8, 8, 1], [3, 1, 4, 1, 5, 9]):
            server.submit(prompt, 5)
        stats = server.stats()
    finally:
        server.close()
    count, phase = stats["phase_ms"]["admit/first_pick"]
    waited = stats["lock_wait_ms"]["admit/first_pick"]
    held = stats["lock_held_ms"]["admit/first_pick"]
    assert count == waited[0] == held[0] == 3
    assert waited[1] + held[1] == pytest.approx(phase, rel=1e-9)
    assert waited[1] >= 0.0 and held[1] > 0.0


# ---- (b) the request's ledger --------------------------------------------


def _spy_on_requests(server) -> list:
    """Every request the server admits, as its own object."""
    seen, make = [], server._admit_wait

    def spy(req, off):
        if req not in seen:
            seen.append(req)
        return make(req, off)

    server._admit_wait = spy
    return seen


def _life(req) -> float:
    return (req.t_done - req.t_submit) * 1e3


def _run_streamed(server, seen):
    handle = server.submit_stream([5, 9, 2, 7, 1, 3], 7,
                                  request_id="req-mine")
    assert len(list(handle)) == 7
    handle.first_written()
    return handle._req


def _run_buffered(server, seen):
    assert len(server.submit([5, 9, 2, 7, 1, 3], 7,
                             request_id="req-mine")) == 13
    return seen[-1]


def _run_cancelled_in_prefill(server, seen):
    chunk = server._cache.prefill_chunk

    def cancelling(*a, **kw):
        seen[-1].cancelled = True     # honored before the next chunk
        return chunk(*a, **kw)

    server._cache.prefill_chunk = cancelling
    with pytest.raises(RequestCancelled):
        server.submit([5, 9, 2, 7, 1, 3, 4, 6, 8], 7,
                      request_id="req-mine")
    server._cache.prefill_chunk = chunk
    return seen[-1]


def _run_prefix_hit(server, seen):
    prompt = list(range(40, 53))
    server.submit(prompt, 3)
    before = server.stats()["prefix_tokens_saved"]
    server.submit(prompt[:12] + [99], 3, request_id="req-mine")
    assert server.stats()["prefix_tokens_saved"] - before >= PAGE
    return seen[-1]


RUNS = {"streamed": _run_streamed, "buffered": _run_buffered,
        "cancelled-in-prefill": _run_cancelled_in_prefill,
        "prefix-hit": _run_prefix_hit}


@pytest.mark.parametrize("kind, block", [
    *((kind, "plain") for kind in sorted(RUNS)),
    ("streamed", "recurrent"), ("streamed", "window-block")])
def test_a_requests_states_add_up_to_its_life(params, probe_blocks, kind,
                                              block):
    tr = Tracer(sample=1.0)
    server = _server(params, probe_blocks.get(block), tracer=tr)
    seen = _spy_on_requests(server)
    try:
        req = RUNS[kind](server, seen)
        stats = server.stats()
    finally:
        server.close()
    assert req.t_done > req.t_submit and req.state == "done"
    assert req.rid == "req-mine"
    assert set(req.state_ms) <= set(REQUEST_STATES)
    assert all(ms >= 0.0 for ms in req.state_ms.values())
    assert sum(req.state_ms.values()) == pytest.approx(_life(req), rel=0.01)
    finished = kind != "cancelled-in-prefill"
    if finished:
        assert {"queued", "admit", "prefill_wait", "prefill", "pick",
                "join_wait", "decode"} == set(req.state_ms)
    else:
        assert "decode" not in req.state_ms and "pick" not in req.state_ms
    # only finished requests are summed, every state over the same ones
    done = stats["requests_done_total"]
    summed = stats["request_ms"]
    assert set(summed) == set(REQUEST_STATES) | {"first_write"}
    assert {summed[s][0] for s in req.state_ms if finished} <= {done}
    assert summed["queued"][0] == done == (
        0 if not finished else 2 if kind == "prefix-hit" else 1)
    assert summed["swapped"] == [0, 0.0]
    assert summed["first_write"][0] == (1 if kind == "streamed" else 0)
    assert summed["first_write"][1] >= 0.0
    # the ring: a root span the request's states lie in
    mine = [rec for rec in tr._snapshot()
            if rec[5] == req.rid and rec[0] == "X"]
    (root,) = [rec for rec in mine if rec[3] == "request"]
    assert root[1] == req.t_submit
    assert root[2] == pytest.approx(req.t_done - req.t_submit)
    states = [rec for rec in mine if rec[3] in (
        "queue", "admit", "admit/lock_wait", "admit/prefill_chunk",
        "admit/first_pick", "join_wait", "decode")]
    assert all(root[1] - 1e-6 <= rec[1]
               and rec[1] + rec[2] <= root[1] + root[2] + 1e-6
               for rec in states)
    if finished:
        assert {"admit", "join_wait", "decode"} <= {r[3] for r in states}
        # they do not overlap and leave little of the life out (the
        # request's own sum above is the exact one)
        assert 0.8 * root[2] <= sum(rec[2] for rec in states) \
            <= root[2] * (1 + 1e-6)


def test_a_request_cancelled_mid_decode_closes_its_ledger_too(params):
    server = _server(params)
    try:
        handle = server.submit_stream([5, 9, 2, 7], 50)
        next(handle)
        handle.cancel()
        with pytest.raises(RequestCancelled):
            list(handle)
        stats = server.stats()
    finally:
        server.close()
    req = handle._req
    assert sum(req.state_ms.values()) == pytest.approx(_life(req), rel=0.01)
    assert req.state_ms["decode"] > 0.0
    assert stats["request_ms"]["decode"] == [0, 0.0]   # it did not finish


def test_a_preempted_request_is_swapped_while_it_is_out(params):
    """``swapped``: out of the pool between a preemption and its
    resume. A batch request gives its only slot to an interactive one
    and decodes on after it."""
    server = PagedGenerationServer(
        params, CFG, slots=1, pages=48, page_size=PAGE, window=2,
        sched_policy="strict", sched_swap_budget_mb=64)
    try:
        batch = server.submit_stream([7, 7, 7], 40, priority="batch")
        next(batch)
        assert len(server.submit([1, 2, 3], 6)) == 9
        assert len(list(batch)) == 39
        stats = server.stats()
    finally:
        server.close()
    assert stats["sched_preemptions_total"] >= 1
    req = batch._req
    assert req.state_ms["swapped"] > 0.0
    assert sum(req.state_ms.values()) == pytest.approx(_life(req), rel=0.01)
    assert stats["request_ms"]["swapped"][0] == 1


# ---- (c) stats() does not wait for the lock it measures ------------------


def test_stats_returns_at_once_while_the_lock_is_held_for_a_second(params):
    server = _server(params)
    held, release = threading.Event(), threading.Event()

    def holder():
        with server._hold("control"):
            held.set()
            release.wait(timeout=1.0)

    thread = threading.Thread(target=holder)
    try:
        server.submit([5, 9, 2, 7], 4)
        fresh = server.stats()
        thread.start()
        assert held.wait(timeout=60)
        took = []
        for _ in range(5):
            t0 = time.perf_counter()
            shot = server.stats()
            took.append(time.perf_counter() - t0)
        assert thread.is_alive()
        release.set()
        thread.join(timeout=60)
        later = server.stats()
    finally:
        release.set()
        server.close()
    assert min(took) < 0.05, took
    # what it got is the loop's last published snapshot: one hold's
    # counters with that hold's clock, not newer than a fresh one
    assert shot["clock_s"] <= fresh["clock_s"] < later["clock_s"]
    assert shot["requests_done_total"] == 1
    assert shot["lock_held_ms"].keys() == fresh["lock_held_ms"].keys()


def test_the_published_snapshot_is_the_fresh_one_of_the_same_instant(
        params):
    """The two paths of ``stats()`` at one instant: with the lock held
    (as a named holder) the test publishes and takes the core snapshot
    itself at the same stamp, and a reader on another thread, who
    cannot have the lock, gets the published one. Same counters."""
    server = _server(params)
    got = {}
    try:
        server.submit([5, 9, 2, 7, 1, 3], 5)
        with server._hold("control") as hold:
            now = hold.t0
            server._publish_locked(now)
            direct = server._stats_core_locked(now)
            reader = threading.Thread(
                target=lambda: got.update(stats=server.stats()))
            reader.start()
            reader.join(timeout=60)
            assert not reader.is_alive()
    finally:
        server.close()
    published = got["stats"]
    assert published["clock_s"] == direct["clock_s"] == now
    assert published == direct
    assert published["requests_done_total"] == 1


# ---- /metrics -------------------------------------------------------------


def test_metrics_render_the_ledger_as_two_labelled_counters(params):
    from test_tracing import check_prometheus_text

    from kvedge_tpu.runtime.status import render_metrics

    server = _server(params)
    try:
        server.submit([5, 9, 2, 7], 4)
        stats = server.stats()
    finally:
        server.close()
    text = render_metrics({"serving": stats})
    families = check_prometheus_text(text)
    for what in ("held", "wait"):
        name = f"kvedge_serve_lock_{what}_ms_total"
        assert families[name] == "counter"
        for holder in LOCK_HOLDERS:
            assert f'{name}{{holder="{holder}"}} ' in text


# ---- tools/idle_by_phase.py ------------------------------------------------


def _ops(start, dur):
    return {"device": "/device:TPU:0", "line": "XLA Ops", "name": "fusion.1",
            "start": start, "dur": dur}


def _host(line, name, start, dur):
    return {"line": line, "name": "kvedge/" + name, "start": start,
            "dur": dur}


# Three gaps, by hand: 0.15 to 0.20 starts inside the loop's hold and
# its harvest_wait; 0.30 to 0.31 while a handler holds the lock for its
# pick and the loop waits for it; 0.40 to 0.46 with nobody on the lock
# and the loop between two annotations.
BY_HAND = {
    "events": [_ops(0.0, 0.10), _ops(0.10, 0.05), _ops(0.20, 0.10),
               _ops(0.31, 0.09), _ops(0.46, 0.04),
               {"device": "/device:TPU:0", "line": "XLA Modules",
                "name": "jit_x(1)", "start": 0.0, "dur": 0.15}],
    "host": [_host("loop", "loop/harvest_wait", 0.0, 0.16),
             _host("loop", "loop/emit", 0.16, 0.02),
             _host("loop", "loop/lock_wait", 0.18, 0.14),
             _host("loop", "lock/loop", 0.0, 0.18),
             _host("h1", "lock/admit/first_pick", 0.181, 0.13),
             _host("h1", "admit/first_pick", 0.17, 0.141)],
    "expected_rows": [["(free)", "loop/(none)", 1],
                      ["loop", "loop/harvest_wait", 1],
                      ["admit/first_pick", "loop/lock_wait", 1]],
    "expected_seconds": [0.06, 0.05, 0.01],
}


@pytest.mark.parametrize("which", ["by-hand", "recorded"])
def test_idle_gaps_are_put_down_to_the_holder_and_the_loops_phase(which):
    """On three gaps made by hand, and on a piece of a capture of
    ``starcoder2-3b.batchgen`` recorded on the chip (``fixtures/
    idle_by_phase_recorded.json``: 0.15 s around the capture's longest
    gap, operations closer than 2 us merged; its rows were read off
    the tool once and are pinned)."""
    import sys

    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import idle_by_phase
    finally:
        sys.path.pop(0)
    recorded = BY_HAND
    if which == "recorded":
        with open(os.path.join(os.path.dirname(__file__), "fixtures",
                               "idle_by_phase_recorded.json")) as fh:
            recorded = json.load(fh)
    rows = idle_by_phase.idle_by_phase(recorded["events"], recorded["host"])
    assert [row[:2] + [row[3]] for row in rows] == recorded["expected_rows"]
    assert [row[2] for row in rows] == pytest.approx(
        recorded["expected_seconds"])
    from benchmark import trace

    idle = sum(hi - lo for lo, hi in trace.idle_gaps(recorded["events"]))
    assert sum(row[2] for row in rows) == pytest.approx(idle)
    assert idle_by_phase.idle_by_phase(
        recorded["events"], recorded["host"], top=1) == rows[:1]
    # a capture with no server in it: every gap is nobody's
    assert [row[:2] for row in idle_by_phase.idle_by_phase(
        recorded["events"], [])] == [["(free)", "loop/(none)"]]


def test_the_tool_reads_a_captures_host_plane_and_says_so_without_a_device(
        params, tmp_path, capsys):
    """A CPU capture has the holds and phases on its host plane and no
    device plane: the reader finds the first, and the command line
    says there is nothing to put them against."""
    import sys

    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import idle_by_phase
    finally:
        sys.path.pop(0)
    server = _server(params)
    jax.profiler.start_trace(str(tmp_path))
    try:
        server.submit([5, 9, 2, 7, 1, 3], 6)
    finally:
        jax.profiler.stop_trace()
        server.close()
    from benchmark import trace

    events, host = idle_by_phase.read(trace.find_xplane(str(tmp_path)))
    assert events == []
    names = {e["name"] for e in host}
    assert {"kvedge/lock/loop", "kvedge/lock/admit/start",
            "kvedge/lock/admit/prefill_chunk",
            "kvedge/lock/admit/first_pick", "kvedge/loop/dispatch",
            "kvedge/loop/harvest_wait"} <= names
    assert all(e["dur"] >= 0.0 and e["line"] for e in host)
    # the loop's holds and its phases share a line; a handler's do not
    loop_line = {e["line"] for e in host if e["name"].startswith(
        "kvedge/loop/")}
    assert len(loop_line) == 1
    assert {e["line"] for e in host
            if e["name"] == "kvedge/lock/loop"} == loop_line
    assert idle_by_phase.main([str(tmp_path)]) == 1
    assert "no device operations" in capsys.readouterr().err

