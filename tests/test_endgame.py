"""Device-resident endgame composition tests (SERVING.md rung 23).

Rung 23 moves the last per-token host costs into the dispatched scans:
stop-token/budget finishes are detected in the scan carry and
harvested as packed finish rows (the boundary sweep does
O(active-finishes) work, not O(bucket)). These tests pin the new
machinery COMPOSED with everything beneath it:

* stop tokens — device-side detection, host-side truncation contract
  (first produced occurrence emitted last, rest of budget unused), the
  deferred finish when a stop lands mid-pipeline, and the
  ``stop_finishes_total`` counter;
* rung 17 — scheduler preemption/resume of a sampled stream with a
  stop token, bit-identical to the never-preempted run;
* rung 22 — poison with a journaled sampled+stop request in flight,
  revive restores it from the checkpoint and it completes exactly;
* rung 21 — within a warm bucket, the new program shapes (sampled
  windows, capped windows with stop rows) retrace zero times.

All fixed-seed and fast: these run in the tier-1 gate under the
``endgame`` marker.
"""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kvedge_tpu.models import TransformerConfig, generate, init_params
from kvedge_tpu.models import kvcache as kvcache_mod
from kvedge_tpu.models.serving import PagedGenerationServer

pytestmark = pytest.mark.endgame

CFG = TransformerConfig(
    vocab=128, d_model=32, n_heads=4, n_kv_heads=2, n_layers=2, d_ff=64,
    max_seq=64,
)

SAMPLING = (jax.random.fold_in(jax.random.PRNGKey(23), 0),
            jnp.float32(0.8), jnp.float32(0.9))


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


def reference(params, prompt, n_new):
    out = generate(params, jnp.asarray([prompt], jnp.int32), CFG,
                   n_new=n_new)
    return [int(t) for t in np.asarray(out)[0]]


def truncate_at(full, prompt_len, stop):
    """The submit() stop contract applied host-side: the first
    generated occurrence of ``stop`` is the final token."""
    gen = full[prompt_len:]
    if stop in gen:
        gen = gen[:gen.index(stop) + 1]
    return full[:prompt_len] + gen


def pick_stop(full, prompt_len):
    """A stop token the greedy/sampled stream actually produces,
    mid-stream (never the last token, so truncation is observable)."""
    gen = full[prompt_len:]
    return gen[len(gen) // 2]


def pick_late_stop(full, prompt_len):
    """The stop token whose FIRST occurrence lands latest in the
    generated stream — maximizes decode runway before truncation (the
    preempt test needs the victim alive long enough to be preempted)."""
    gen = full[prompt_len:]
    firsts = {}
    for i, t in enumerate(gen):
        firsts.setdefault(t, i)
    return max(firsts, key=firsts.get)


def sampled_reference(params, prompt, n_new, sampling=SAMPLING):
    """Fault-free sampled stream from a plain (non-speculative,
    serial-default) server — the established oracle for the positional
    key schedule."""
    plain = PagedGenerationServer(params, CFG, slots=2, pages=32,
                                  page_size=4)
    try:
        return plain.submit(prompt, n_new, sampling=sampling)
    finally:
        plain.close()


# ---- stop tokens: device detection, truncation, deferred finish ----------


def test_stop_token_truncates_and_counts(params):
    """A produced stop token ends the request with the stop emitted
    last and the rest of the budget unused; a stop token the stream
    never produces changes nothing. Detection rides the capped window
    scan (overlap pipeline), so the finish may be deferred — the
    counter and the empty deferred set prove the sweep ran."""
    prompt = [5, 9, 2]
    want_full = reference(params, prompt, 16)
    stop = pick_stop(want_full, len(prompt))
    want_cut = truncate_at(want_full, len(prompt), stop)
    assert len(want_cut) < len(want_full)  # the stop really fires

    server = PagedGenerationServer(params, CFG, slots=2, pages=32,
                                   page_size=4, window=4)
    try:
        got = server.submit(prompt, 16, stop_token=stop)
        assert got == want_cut
        # vocab=128, so token 127 is legal but (checked) never drawn.
        assert 127 not in want_full[len(prompt):]
        assert server.submit(prompt, 16, stop_token=127) == want_full
        stats = server.stats()
        assert stats["stop_finishes_total"] == 1
        assert server._stops_pending == 0
    finally:
        server.close()


def test_stop_mid_pipeline_defers_without_perturbing_cotenant(params):
    """One request stops mid-window while its co-tenant keeps
    decoding: the stopped row's finish defers to the boundary the
    pipeline is forced to, and the survivor's stream is untouched."""
    p_stop, p_go = [5, 9, 2], [7, 7, 7, 7, 7, 1, 4]
    full = reference(params, p_stop, 20)
    stop = pick_stop(full, len(p_stop))
    want_stop = truncate_at(full, len(p_stop), stop)
    want_go = reference(params, p_go, 20)

    server = PagedGenerationServer(params, CFG, slots=2, pages=32,
                                   page_size=4, window=4)
    try:
        results: dict[str, list[int]] = {}

        def sub(key, prompt, **kw):
            results[key] = server.submit(prompt, 20, **kw)

        ts = [threading.Thread(target=sub, args=("s", p_stop),
                               kwargs={"stop_token": stop}),
              threading.Thread(target=sub, args=("g", p_go))]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=300)
        assert results["s"] == want_stop
        assert results["g"] == want_go
        assert server.stats()["stop_finishes_total"] == 1
        assert server._stops_pending == 0
    finally:
        server.close()


@pytest.mark.parametrize("block", ["recurrent", "window-block"])
@pytest.mark.parametrize("where", ["first-token", "mid-window"])
def test_a_stop_on_a_patterned_block_ends_its_row_alone(probe_blocks, block,
                                                        where):
    """A stop token that is the request's first token (no step: the
    boundary's sweep ends it) and one mid-window, beside a co-tenant
    that keeps decoding, on a recurrent block and on a window block.
    The reference is the same server, each request alone and unstopped
    (``decode.generate`` refuses a pattern). The row is then used
    again: what the next request is served there is what it is served
    alone (a recurrent state zeroed before reuse, a window table
    started anew), and the co-tenant's stream is untouched."""
    cfg, params = probe_blocks[block]
    p_stop, p_go, p_next = [5, 9, 2], [7, 7, 7, 7, 7, 1, 4], [3, 1, 4, 1]
    server = PagedGenerationServer(params, cfg, slots=2, pages=64,
                                   page_size=4, window=4,
                                   prefix_cache=False)
    try:
        full = server.submit(p_stop, 20)
        want_go = server.submit(p_go, 60)
        want_next = server.submit(p_next, 12)
        gen = full[len(p_stop):]
        stop = gen[0] if where == "first-token" else pick_stop(
            full, len(p_stop))
        want_stop = truncate_at(full, len(p_stop), stop)
        assert len(want_stop) < len(full)  # the stop really fires
        if where == "first-token":
            assert len(want_stop) == len(p_stop) + 1

        cotenant = server.submit_stream(p_go, 60)
        first = next(cotenant)
        assert server.submit(p_stop, 20, stop_token=stop) == want_stop
        assert server.submit(p_next, 12) == want_next
        assert p_go + [first] + list(cotenant) == want_go
        stats = server.stats()
        assert stats["stop_finishes_total"] == 1
        assert server._stops_pending == 0
        assert stats["free_pages"] == stats["pages_total"]
    finally:
        server.close()


def test_stop_composes_with_a_sampled_cotenant(params):
    """A greedy row and a sampled co-tenant, each with its own stop
    token, served by the sampled window — both truncate exactly where
    the fault-free references do."""
    p_g, p_s = [5, 9, 2, 7], [1, 2, 3, 4]
    full_g = reference(params, p_g, 14)
    full_s = sampled_reference(params, p_s, 14)
    stop_g = pick_stop(full_g, len(p_g))
    stop_s = pick_stop(full_s, len(p_s))
    want_g = truncate_at(full_g, len(p_g), stop_g)
    want_s = truncate_at(full_s, len(p_s), stop_s)

    server = PagedGenerationServer(params, CFG, slots=2, pages=32,
                                   page_size=4, window=4)
    try:
        stream = server.submit_stream(p_s, n_new=14, sampling=SAMPLING,
                                      stop_token=stop_s)
        first = next(stream)
        got_g = server.submit(p_g, 14, stop_token=stop_g)
        got_s = p_s + [first] + list(stream)
        stats = server.stats()
        assert got_g == want_g
        assert got_s == want_s
        assert stats["stop_finishes_total"] == 2
    finally:
        server.close()


# ---- rung 17: preempt/resume a sampled stream with a stop token ----------


def test_preempt_resume_sampled_stream_with_stop(params):
    """A sampled batch victim carrying a stop token is preempted by an
    interactive arrival and resumed: the positional key schedule makes
    resume bit-identical, and the stop still truncates exactly where
    the never-preempted run stops."""
    victim_prompt, inter_prompt = [9, 8, 7], [40, 41, 42]
    full_v = sampled_reference(params, victim_prompt, 40)
    stop_v = pick_late_stop(full_v, len(victim_prompt))
    want_v = truncate_at(full_v, len(victim_prompt), stop_v)

    server = PagedGenerationServer(
        params, CFG, slots=1, pages=16, page_size=4, window=4,
        sched_policy="strict", sched_swap_budget_mb=64,
    )
    try:
        victim = server.submit_stream(victim_prompt, n_new=40,
                                      priority="batch",
                                      sampling=SAMPLING,
                                      stop_token=stop_v)
        first = next(victim)
        got_i = server.submit(inter_prompt, n_new=6)
        got_v = victim_prompt + [first] + list(victim)
        stats = server.stats()
        assert stats["sched_preemptions_total"] >= 1
        assert stats["sched_resumes_total"] >= 1
        assert got_i == reference(params, inter_prompt, 6)
        assert got_v == want_v
        assert stats["stop_finishes_total"] >= 1
        assert server.stats()["sched_swap_bytes_host"] == 0
    finally:
        server.close()


# ---- rung 22: poison/revive restores a sampled+stop request --------------


def _wait_degraded(server, timeout_s=30.0):
    deadline = time.monotonic() + timeout_s
    while server.degraded is None:
        assert time.monotonic() < deadline, "pool never poisoned"
        time.sleep(0.01)


def test_poison_revive_restores_sampled_stop_request(params):
    """Boundary checkpoints journal the live _Request itself, so
    sampling state and the stop token survive poison/revive: a sampled
    stream killed mid-decode (after its first checkpoint) resumes from
    the journal and completes bit-identical, stop truncation
    included."""
    prompt = [3, 1, 4, 1, 5]
    full = sampled_reference(params, prompt, 20)
    stop = pick_stop(full, len(prompt))
    want = truncate_at(full, len(prompt), stop)

    server = PagedGenerationServer(
        params, CFG, slots=2, pages=24, page_size=4, window=2,
        checkpoint_every=1, prefix_cache=False,
    )
    cache = server._cache
    real_h = cache.harvest_window
    state = {"arm": True}

    def dying_harvest(handle):
        if state["arm"] and len(server._journal) >= 1:
            state["arm"] = False
            raise RuntimeError("injected: died mid-sampled-stream")
        return real_h(handle)

    cache.harvest_window = dying_harvest
    dying_thread = server._thread
    got: list[int] = []
    errs: list[Exception] = []
    done = threading.Event()

    def consume():
        try:
            for tok in server.submit_stream(prompt, n_new=20,
                                            sampling=SAMPLING,
                                            stop_token=stop):
                got.append(tok)
        except Exception as e:
            errs.append(e)
        finally:
            done.set()

    threading.Thread(target=consume, daemon=True).start()
    try:
        _wait_degraded(server)
        dying_thread.join(timeout=30)
        assert not dying_thread.is_alive()
        assert server.revive() == 1
        assert done.wait(timeout=120)
        assert not errs, errs
        assert prompt + got == want
        stats = server.stats()
        assert stats["journal_restores_total"] == 1
        assert stats["stop_finishes_total"] >= 1
    finally:
        server.close()


# ---- rung 21: the new shapes retrace zero times within a bucket ----------


def test_endgame_shapes_zero_retraces_within_bucket(params):
    """The rung-23 programs (sampled windows, capped windows with
    stop rows) key on the same bucketed shapes as everything else:
    after one warm pass per request shape, repeating the identical
    requests — sampled, stopped, and mixed — triggers zero new
    traces."""
    server = PagedGenerationServer(params, CFG, slots=2, pages=32,
                                   page_size=4, min_bucket=1,
                                   prefix_cache=False)
    p_g, p_s = [5, 9, 2, 7], [1, 2, 3, 4]
    full_g = reference(params, p_g, 8)
    stop_g = pick_stop(full_g, len(p_g))

    def round_trip():
        """One solo greedy+stop, one solo sampled, one mixed pair —
        the same shapes every time."""
        outs = [server.submit(p_g, 8, stop_token=stop_g),
                server.submit(p_s, 8, sampling=SAMPLING)]
        stream = server.submit_stream(p_s, n_new=8, sampling=SAMPLING)
        first = next(stream)
        outs.append(server.submit(p_g, 8))
        outs.append(p_s + [first] + list(stream))
        return outs

    try:
        warm = round_trip()
        round_trip()
        pinned = kvcache_mod.trace_count()
        again = round_trip()
        assert kvcache_mod.trace_count() == pinned, (
            "a warm-bucket endgame request recompiled"
        )
        assert again == warm
        assert again[0] == truncate_at(full_g, len(p_g), stop_g)
        assert again[2] == full_g
    finally:
        server.close()
