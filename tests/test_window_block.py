"""A block with layers bound to a window beside full layers
(models/hybrid.py's ``"window"`` kind, the window layers' page pool of
models/kvcache.py, the bound in ops/paged_attention.py), on the paged
serving path, held to the benchmark's plain reference
(benchmark/references/smallthinker.py), never to decode.generate.

One preset at a size the CPU runs in seconds: pattern f w w w over two
periods (a full layer without positional encoding, three rotary layers
bound to a window of 24 positions, base 1,500,000), 14 query heads over
2 key heads of 16 (a query group of 7), all 8 ReLU-gated experts held,
3 a token, the router read before the mixer, no shared expert, a head of
its own. The program computes in float32 here, so that what separates it
from the float32 reference is the order of its sums and nothing else.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import cellspec
from kvedge_tpu.config.runtime_config import RuntimeConfig, RuntimeConfigError
from kvedge_tpu.models import hybrid, kvcache
from kvedge_tpu.models.serving import PagedGenerationServer
from kvedge_tpu.models.transformer import TransformerConfig
from kvedge_tpu.ops import paged_attention

REFERENCE = cellspec.load_module(
    "smallthinker_for_tests",
    os.path.join(cellspec.REPO, "benchmark", "references", "smallthinker.py"))

SEQ, PAGE, WINDOW = 256, 16, 24
PUBLISHED = {
    "head_dim": 16, "hidden_size": 32, "max_position_embeddings": 512,
    "model_name": "preset", "moe_ffn_hidden_size": 16,
    "moe_num_active_primary_experts": 3, "moe_num_primary_experts": 8,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "num_attention_heads": 14, "num_hidden_layers": 8,
    "num_key_value_heads": 2, "rms_norm_eps": 1e-6,
    "rope_layout": [0, 1, 1, 1] * 2, "rope_scaling": None,
    "rope_theta": 1500000, "sliding_window_layout": [0, 1, 1, 1] * 2,
    "sliding_window_size": WINDOW, "tie_word_embeddings": False,
    "vocab_size": 256, "payload": {"seq": SEQ},
}
MODEL = REFERENCE.model_of(PUBLISHED)
# The float32 program against the float32 reference: sums in another
# order, over 8 layers, on logits of size 0.5 (the largest gap seen is
# 1.2e-6). bf16 in the program's place reads 3e-3, the reference's int8
# control 3e-2: both are told from float32 a hundred times over.
TOLERANCE = 2e-5


def document(payload: dict | None = None, model: dict | None = None) -> dict:
    section = {k: v for k, v in MODEL.items() if k != "seq"}
    return {
        "runtime": {"name": "window-test", "state_dir": "/tmp/unused"},
        "tpu": {"platform": "cpu", "expected_chips": 1},
        "mesh": {"axes": {"data": 1}},
        "model": {**section, **(model or {})},
        "payload": {"kind": "serve", "serving": "paged", "seq": SEQ,
                    "serving_prefix_cache": False, **(payload or {})},
    }


def config_of(model: dict | None = None, dtype="float32",
              **replaced) -> TransformerConfig:
    """The program's config through the product's own path ([model] ->
    ModelSpec -> derive_model_config)."""
    from kvedge_tpu.runtime.workload import derive_model_config

    cfg = RuntimeConfig.from_mapping(document(model=model))
    one = jax.devices()[:1]  # of the tests' eight virtual devices
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax, "devices", lambda *a, **k: one)
        tcfg, _ = derive_model_config(cfg, seq=SEQ)
    return dataclasses.replace(tcfg, dtype=dtype, **replaced)


@pytest.fixture(scope="module")
def cfg():
    return config_of()


@pytest.fixture(scope="module")
def params(cfg):
    return hybrid.init_params(jax.random.PRNGKey(0), cfg)


@pytest.fixture(scope="module")
def weights():
    return REFERENCE.make_weights(MODEL)


def prompt_of(seed: int, n: int) -> list:
    return [int(t) for t in np.random.default_rng(seed).integers(0, 256, n)]


def server_of(params, cfg, **kw):
    kw = {"slots": 4, "pages": 64, "page_size": PAGE, "prefill_chunk": 16,
          "prefix_cache": False, "window": 4, **kw}
    return PagedGenerationServer(params, cfg, **kw)


def teacher_forced(cfg, params, sequence: list, n_prompt: int,
                   chunk: int = 16, window: int = 8):
    """Logits [len(sequence) - n_prompt + 1, V] of the positions from
    the prompt's last on, through the cache's own programs: the prompt
    prefilled in chunks of ``chunk``, then one decode step a token, the
    window layers' pages given back every ``window`` steps as a
    harvested decode window does. Also the most window pages the row
    ever held."""
    cache = kvcache.PagedKVCache(cfg, slots=2, pages=32, page_size=PAGE,
                                 window_advance=max(chunk, window))
    cache.admit(1, n_prompt)
    most = 0
    for lo in range(0, n_prompt, chunk):
        out = cache.prefill_chunk(
            params, 1, jnp.asarray(sequence[lo:min(n_prompt, lo + chunk)],
                                   jnp.int32), lo)
        most = max(most, cache.window_pages_held(1))
    rows = [np.asarray(out)]
    for i, token in enumerate(sequence[n_prompt:]):
        logits = cache.step(params, jnp.asarray([0, token], jnp.int32),
                            active=[False, True])
        rows.append(np.asarray(logits[1]))
        most = max(most, cache.window_pages_held(1))
        if i % window == window - 1:
            cache.release_window_pages([1])
    return np.stack(rows), cache, most


# ---- (a) the served path against the reference's full forward pass -------


def test_served_tokens_and_logits_are_the_reference_s(cfg, params, weights):
    """A context of 118 positions, five times the window: the prompt of
    70 in chunks of 16 (the second crosses the window's edge at 24, and
    every later one starts past it), then 48 tokens in decode windows
    of 4, each of which moves the window on."""
    prompt, n_new = prompt_of(1, 70), 48
    server = server_of(params, cfg)
    try:
        served = server.submit(prompt, n_new)
        stats = server.stats()
    finally:
        server.close()
    sequence, generated = served, served[len(prompt):]
    assert sequence[:len(prompt)] == prompt and len(generated) == n_new
    (want,) = REFERENCE.logits(MODEL, weights, [sequence],
                               [len(prompt) - 1])
    gaps = want[:n_new].max(axis=-1) - want[np.arange(n_new), generated]
    assert gaps.max() <= TOLERANCE
    # the pages behind the window went back while the row was live, and
    # all of them when it ended
    assert stats["window_pages_released_total"] >= 4
    assert stats["window_free_pages"] == stats["window_pages_total"]
    assert stats["free_pages"] == stats["pages_total"]
    # and the logits the cache's programs give at those positions are
    # the reference's
    got, cache, most = teacher_forced(cfg, params, sequence[:-1],
                                      len(prompt))
    np.testing.assert_allclose(got, want[:n_new], rtol=0, atol=TOLERANCE)
    # a window of 24 and advances of 16 over pages of 16: never more
    # than ceil(40 / 16) + 1 = 4 pages, where the context spans 8
    assert most <= cache.window_cap == 4
    assert cache.slot_length(1) == len(sequence) - 1 > 4 * WINDOW
    # the tolerance tells the precisions apart: the program in bf16 ...
    rough, _, _ = teacher_forced(
        dataclasses.replace(cfg, dtype="bfloat16"), params, sequence[:-1],
        len(prompt))
    assert np.abs(rough - want[:n_new]).max() > 10 * TOLERANCE
    # ... the reference's own int8 control ...
    (control,) = REFERENCE.logits(MODEL, weights, [sequence],
                                  [len(prompt) - 1], quant="int8")
    assert np.abs(control[:n_new] - want[:n_new]).max() > 10 * TOLERANCE
    # ... and tells a block without the bound from this one
    unbound = dict(MODEL, attention_window=SEQ)
    (other,) = REFERENCE.logits(unbound, weights, [sequence],
                                [len(prompt) - 1])
    assert np.abs(other[:n_new] - want[:n_new]).max() > 10 * TOLERANCE


@pytest.mark.parametrize("chunk, window", [(8, 4), (32, 16), (64, 32)])
def test_chunks_and_windows_of_any_size_read_the_same_logits(
        cfg, params, weights, chunk, window):
    """Whatever the chunk and the decode window (an advance smaller
    than the attention window, equal to a page, larger than the
    attention window), a row holds no more than its cap and reads the
    reference's logits."""
    sequence = prompt_of(3, 100)
    n_prompt = 64
    (want,) = REFERENCE.logits(MODEL, weights, [sequence], [n_prompt - 1])
    got, cache, most = teacher_forced(cfg, params, sequence[:-1], n_prompt,
                                      chunk=chunk, window=window)
    np.testing.assert_allclose(got, want[:len(got)], rtol=0, atol=TOLERANCE)
    assert most <= cache.window_cap == -(
        -(WINDOW + max(chunk, window)) // PAGE) + 1


def test_a_decode_window_equals_its_steps(cfg, params):
    """The windowed program (a scan of steps on the device, the pages
    for the whole window allocated up front, what lies behind given
    back at the harvest) produces the tokens of step-by-step greedy
    decoding, across the window's edge and far past it."""
    prompt = prompt_of(5, 20)
    by_step, by_window = [], []
    for produced, n_steps in ((by_step, 1), (by_window, 8)):
        cache = kvcache.PagedKVCache(cfg, slots=2, pages=32, page_size=PAGE,
                                     window_advance=16)
        cache.admit(0, len(prompt))
        logits = cache.prefill(params, 0, jnp.asarray(prompt, jnp.int32))
        token = int(np.argmax(np.asarray(logits)))
        for _ in range(48 // n_steps):
            handle = cache.dispatch_window(
                params, np.asarray([token, 0], np.int32), n_steps,
                active=[True, False])
            block = cache.harvest_window(handle)
            cache.release_window_pages([0])
            produced += block[:n_steps, 0].tolist()
            token = produced[-1]
            assert cache.window_pages_held(0) <= cache.window_cap
    assert by_window == by_step


# ---- (b) the kernel's bound is the gather's, bit for bit ------------------


def _attended(cfg, state, normed, w_qkv, w_out, positions, window):
    pools = ((state.win_pool_k, state.win_pool_v, None, None) if window
             else (state.pool_k, state.pool_v, None, None))
    out, _ = kvcache._paged_attention(
        cfg, state, normed, w_qkv, w_out, 1, pools, positions, window=window)
    return np.asarray(out)


def test_the_kernel_equals_the_gather_bit_for_bit_under_a_bound():
    """A query group of 7, five rows: dead, a row whose table starts at
    position 32 and whose window's lower edge falls inside its oldest
    page, dead, a row whose context is shorter than the window (its
    table starts at 0 and the bound masks nothing), a row whose window
    starts exactly on a page. Under the interpreter the kernel's live
    rows are the gather's in every bit, and a dead row's are zeros."""
    cfg = config_of(dtype="bfloat16", paged_attention="gather")
    kernel_cfg = dataclasses.replace(cfg, paged_attention="kernel")
    rows, cap, pages, width = 5, 4, 24, 2 * 16
    keys = jax.random.split(jax.random.PRNGKey(7), 5)
    pool_k = jax.random.normal(keys[0], (6, pages, PAGE, width), jnp.bfloat16)
    pool_v = jax.random.normal(keys[1], (6, pages, PAGE, width), jnp.bfloat16)
    lengths = np.array([0, 70, 0, 20, 71], np.int32)  # the next positions
    first = np.array([0, 32, 0, 0, 48], np.int32)
    tables = np.zeros((rows, cap), np.int32)
    for b, page in zip((1, 3, 4), (1, 9, 13)):
        held = (lengths[b] - first[b]) // PAGE + 1
        tables[b, :held] = np.arange(page, page + held)
    state = kvcache.PagedState(
        pool_k=jnp.zeros((2, 4, PAGE, width), jnp.bfloat16),
        pool_v=jnp.zeros((2, 4, PAGE, width), jnp.bfloat16),
        tables=jnp.zeros((rows, SEQ // PAGE), jnp.int32),
        lengths=jnp.asarray(lengths),
        win_pool_k=pool_k, win_pool_v=pool_v,
        win_tables=jnp.asarray(tables), win_first=jnp.asarray(first))
    normed = jax.random.normal(keys[2], (rows, 1, 32), jnp.bfloat16)
    w_qkv = jax.random.normal(keys[3], (32, (14 + 4) * 16),
                              jnp.bfloat16) * 32 ** -0.5
    w_out = jax.random.normal(keys[4], (14 * 16, 32),
                              jnp.bfloat16) * 224 ** -0.5
    positions = jnp.asarray(lengths)[:, None]
    want = _attended(cfg, state, normed, w_qkv, w_out, positions, WINDOW)
    got = _attended(kernel_cfg, state, normed, w_qkv, w_out, positions,
                    WINDOW)
    live = lengths > 0
    np.testing.assert_array_equal(got[live].view(np.uint16),
                                  want[live].view(np.uint16))
    assert not got[~live].any()
    # the bound bites: under a window as long as the context row 1,
    # whose oldest page starts 15 positions before its window, reads
    # otherwise; row 3, inside its window, and row 4, whose table starts
    # where its window does, read the same
    loose = _attended(kernel_cfg, state, normed, w_qkv, w_out, positions,
                      SEQ)
    assert (loose[1] != got[1]).any()
    for row in (3, 4):
        np.testing.assert_array_equal(loose[row].view(np.uint16),
                                      got[row].view(np.uint16))


def _kernel_body_operations(window: int) -> int:
    """Operations in the kernel's body as traced, nested bodies
    included."""
    q = jnp.zeros((3, 14, 16), jnp.bfloat16)
    pool = jnp.zeros((1, 12, PAGE, 32), jnp.bfloat16)
    tables, rows = jnp.zeros((3, 5), jnp.int32), jnp.zeros((3,), jnp.int32)
    bound = dict(first=rows, window=window) if window else {}
    traced = jax.make_jaxpr(
        lambda *a: paged_attention.paged_decode_attention(
            *a, 0, interpret=True, **bound))(q, pool, pool, tables, rows)

    def inner(eqn):
        for value in eqn.params.values():
            for item in value if isinstance(value, (list, tuple)) else [value]:
                item = getattr(item, "jaxpr", item)
                if hasattr(item, "eqns"):
                    yield item

    def count(jaxpr):
        return sum(1 + sum(map(count, inner(e))) for e in jaxpr.eqns)

    def find(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                return eqn
            for sub in inner(eqn):
                if (hit := find(sub)) is not None:
                    return hit

    return count(find(traced.jaxpr).params["jaxpr"])


def test_a_call_without_a_bound_traces_none_of_the_bound_s_code():
    """The window is a static argument: with none the kernel's body is
    the 164 operations it was before the bound existed (counted on the
    parent of ISSUE 40), and the cells without a window layer run the
    programs they ran; with one it is the body and the bound's 21."""
    assert _kernel_body_operations(0) == 164
    assert _kernel_body_operations(WINDOW) == 164 + 21
    with pytest.raises(ValueError, match="go together"):
        paged_attention.paged_decode_attention(
            jnp.zeros((1, 2, 16)), jnp.zeros((1, 2, PAGE, 16)),
            jnp.zeros((1, 2, PAGE, 16)), jnp.zeros((1, 2), jnp.int32),
            jnp.zeros((1,), jnp.int32), 0, interpret=True, window=8)


def test_the_mask_has_one_statement():
    keys, queries = np.arange(40)[None, :], np.arange(40)[:, None]
    seen = np.asarray(paged_attention.visible(keys, queries, 8))
    for q in range(40):
        assert [k for k in range(40) if seen[q, k]] == list(
            range(max(0, q - 7), q + 1))
    causal = np.asarray(paged_attention.visible(keys, queries))
    assert (causal == (keys <= queries)).all()


# ---- (c) the allocator of the two pools ------------------------------------


def test_the_two_pools_books_close_under_a_random_order_of_events(params,
                                                                 cfg):
    """Admissions, prefill chunks, decode windows, preemptions
    (swapped out and back into another slot) and releases in a seeded
    random order over four slots: after every event no page is free
    and in a table at once, in either pool, the census closes, no row
    holds more than its cap, and at the end every page is back."""
    rng = np.random.default_rng(40)
    cache = kvcache.PagedKVCache(cfg, slots=4, pages=64, page_size=PAGE,
                                 window_advance=16)
    assert cache.window_cap == 4 and cache.num_window_pages == 16
    rows: dict = {}     # slot -> [prompt, prefilled, tokens left]
    parked: list = []   # (saved length, tokens left, arrays)

    def check():
        acct = cache.page_accounting()
        assert acct["free"] + acct["live"] == acct["pages_total"]
        assert acct["window_free"] + acct["window_live"] \
            == acct["window_pages_total"]
        for key in ("free_dup", "neg_refs", "free_live", "window_free_dup",
                    "window_held_dup", "window_free_live",
                    "window_over_cap"):
            assert not acct[key], key
        for slot in rows:
            assert cache.window_pages_held(slot) <= cache.window_cap

    events = 0
    while events < 70:
        free = [s for s in range(4) if s not in rows]
        kind = rng.choice(["admit", "advance", "advance", "preempt",
                           "resume", "release"])
        if kind == "admit" and free:
            prompt = int(rng.integers(1, 5)) * 16
            cache.admit(free[0], prompt)
            rows[free[0]] = [prompt, 0, int(rng.integers(2, 9)) * 8]
        elif kind == "advance" and rows:
            slot = int(rng.choice(list(rows)))
            prompt, done, left = rows[slot]
            if done < prompt:
                cache.prefill_chunk(
                    params, slot, jnp.zeros((16,), jnp.int32), done)
                rows[slot][1] += 16
            elif left and cache.slot_length(slot) + 8 <= SEQ:
                active = [s == slot for s in range(4)]
                cache.harvest_window(cache.dispatch_window(
                    params, np.zeros(4, np.int32), 8, active=active))
                cache.release_window_pages([slot])
                rows[slot][2] -= 8
            else:
                continue
        elif kind == "preempt" and rows:
            slot = int(rng.choice(list(rows)))
            prompt, done, left = rows[slot]
            if done < prompt:
                continue
            length = cache.slot_length(slot)
            arrays = (cache.swapout_pages(
                cache.slot_pages(slot)[:-(-length // PAGE)])
                + cache.swapout_row(slot))
            cache.release(slot)
            del rows[slot]
            parked.append((length, left, arrays))
        elif kind == "resume" and parked and free:
            length, left, arrays = parked.pop()
            cache.admit(free[-1], length)
            cache.swapin_slot(free[-1], arrays)
            rows[free[-1]] = [length, length, left]
        elif kind == "release" and rows:
            slot = int(rng.choice(list(rows)))
            cache.release(slot)
            del rows[slot]
        else:
            continue
        events += 1
        check()
    for slot in list(rows):
        cache.release(slot)
    acct = cache.page_accounting()
    assert acct["free"] == acct["pages_total"] == 64
    assert acct["window_free"] == acct["window_pages_total"] == 16
    assert cache.window_pages_released > 0


def test_a_row_past_its_cap_is_refused_not_overrun(cfg, params):
    """The cap is the table's width: a row asked to advance by more than
    the pool was sized for is an error before any page is touched."""
    cache = kvcache.PagedKVCache(cfg, slots=1, pages=16, page_size=PAGE,
                                 window_advance=8)
    assert cache.window_cap == 3
    cache.admit(0, 128)
    with pytest.raises(kvcache.PagedCacheError, match="cap of 3"):
        cache.prefill_chunk(params, 0, jnp.zeros((64,), jnp.int32), 0)


def test_a_swap_snapshot_carries_both_tables_and_the_first_position(
        cfg, params):
    """Preemption's pair: what leaves with a row is its pages of the
    full pool, its window layers' pages and where their table starts,
    and it comes back bit for bit into another slot; a snapshot
    without the window's part is refused."""
    cache = kvcache.PagedKVCache(cfg, slots=2, pages=32, page_size=PAGE,
                                 window_advance=16)
    cache.admit(0, 64)
    for lo in range(0, 64, 16):
        cache.prefill_chunk(params, 0, jnp.asarray(
            prompt_of(13, 64)[lo:lo + 16], jnp.int32), lo)
    pages = cache.swapout_pages(cache.slot_pages(0))
    state = cache.swapout_row(0)
    first, keys, values = state
    assert int(first) == 32 and keys.shape == (6, 2, PAGE, 2, 16)
    assert cache.row_state_bytes() >= sum(a.nbytes for a in state[1:])
    logits = np.asarray(cache.step(
        params, jnp.asarray([7, 0], jnp.int32), active=[True, False]))[0]
    cache.release(0)
    cache.admit(1, 64)
    with pytest.raises(kvcache.PagedCacheError, match="window"):
        cache.swapin_slot(1, pages)
    cache.swapin_slot(1, pages + state)
    assert cache._wfirst[1] == 32 and cache.window_pages_held(1) == 2
    again = np.asarray(cache.step(
        params, jnp.asarray([0, 7], jnp.int32), active=[False, True]))[1]
    np.testing.assert_array_equal(again, logits)


# ---- (d) the layer: router before the mixer, a ReLU gate -------------------


def test_the_layer_routes_on_the_mixer_s_input_and_gates_by_relu(cfg,
                                                                 params):
    """With every expert held, the block's feed-forward is the
    reference's whole routed sum when, and only when, the picks are
    read off the mixer's normed input; and its gate is ReLU's."""
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(1, 12, 32)), jnp.float32)
    a = jnp.asarray(rng.normal(size=(1, 12, 32)), jnp.float32)
    w = jax.tree_util.tree_map(lambda leaf: leaf[0, 1], params["ffn"])
    got, picks = hybrid.feed_forward(cfg, x, w, None, routed_on=a)
    ref = REFERENCE.layer_weights(MODEL, 1)
    np.testing.assert_array_equal(np.asarray(w["router"]),
                                  np.asarray(ref["router"]))
    h = REFERENCE._rmsnorm(x[0], MODEL["norm_eps"])
    with jax.default_matmul_precision("highest"):
        idx, gates = REFERENCE.route(a[0], ref["router"], 3)
        want = x[0] + REFERENCE.routed(h, idx, gates, ref)
        idx_after, gates_after = REFERENCE.route(h, ref["router"], 3)
        after = x[0] + REFERENCE.routed(h, idx_after, gates_after, ref)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               rtol=0, atol=TOLERANCE)
    assert np.abs(np.asarray(got[0]) - np.asarray(after)).max() \
        > 100 * TOLERANCE
    assert int(picks[0]) == int(picks[1]) == 12 * 3  # every pick is held
    # read off the feed-forward's own input, the program gives the other
    other, _ = hybrid.feed_forward(cfg, x, w, None)
    np.testing.assert_allclose(np.asarray(other[0]), np.asarray(after),
                               rtol=0, atol=TOLERANCE)
    # and a SiLU gate is another function
    silu, _ = hybrid.feed_forward(
        dataclasses.replace(cfg, ffn_activation="silu"), x, w, None,
        routed_on=a)
    assert np.abs(np.asarray(silu[0]) - np.asarray(want)).max() \
        > 100 * TOLERANCE


def test_the_whole_reference_layer_reads_the_router_before_the_mixer(
        weights):
    """The reference's own switch: a layer whose router reads the
    feed-forward's input is another layer."""
    x = weights["embedding"][jnp.asarray(prompt_of(2, 40), jnp.int32)]
    w = REFERENCE.layer_weights(MODEL, 1)
    with jax.default_matmul_precision("highest"):
        before, picks = REFERENCE.layer(MODEL, x, w)
        after, picks_after = REFERENCE.layer(MODEL, x, w, router_after=True)
    assert (np.asarray(picks) != np.asarray(picks_after)).any()
    assert np.abs(np.asarray(before) - np.asarray(after)).max() \
        > 100 * TOLERANCE


# ---- (e) the reference's band ----------------------------------------------


def test_the_reference_s_band_is_a_position_by_position_loop():
    for lo, hi, t, window in ((0, 7, 7, 0), (0, 40, 40, 8), (16, 40, 40, 8),
                              (5, 9, 30, 1), (0, 12, 12, 40)):
        got = np.asarray(REFERENCE.band(lo, hi, t, window))
        for i in range(lo, hi):
            for j in range(t):
                want = j <= i and (not window or j >= i - window + 1)
                assert got[i - lo, j] == want, (lo, hi, t, window, i, j)


def test_the_reference_rotates_as_the_program_does():
    """One convention of RoPE on both sides (halves paired, the base
    from the configuration), far out in the context."""
    from kvedge_tpu.models.transformer import _rotary

    x = jnp.asarray(np.random.default_rng(0).normal(size=(200, 3, 16)),
                    jnp.float32)
    want = REFERENCE.rope(x, 1.5e6)
    got = _rotary(x[None], jnp.arange(200), 1.5e6)[0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)
    other = _rotary(x[None], jnp.arange(200))[0]  # the base of 10,000
    assert np.abs(np.asarray(other) - np.asarray(want)).max() > 0.1


# ---- what cannot run it, and what it counts --------------------------------


def test_the_server_refuses_the_prefix_cache(cfg, params):
    with pytest.raises(ValueError,
                       match="shared page that a 'window' layer"):
        server_of(params, cfg, prefix_cache=True)


@pytest.mark.parametrize("payload, named", [
    ({"serving_prefix_cache": True}, "serving_prefix_cache"),
    ({"serving_speculative": 3}, "serving_speculative"),
    ({"serving_kv_dtype": "int8"}, "serving_kv_dtype"),
])
def test_the_runtime_config_refuses_what_cannot_run_the_block(payload,
                                                             named):
    with pytest.raises(RuntimeConfigError, match=named):
        RuntimeConfig.from_mapping(document(payload=payload))


@pytest.mark.parametrize("change, named", [
    ({"attention_window": 0}, "attention_window"),
    ({"layer_pattern": ("attention",) * 4}, "attention_window"),
    ({"ffn_activation": "tanh"}, "ffn_activation"),
    ({"ffn_activation": "relu", "ffn_gated": False}, "ffn_gated"),
    ({"layer_pattern": ("window", "local", "window", "window")}, "local"),
])
def test_a_pattern_the_block_cannot_run_is_refused_by_name(cfg, change,
                                                           named):
    with pytest.raises(ValueError, match=named):
        dataclasses.replace(cfg, **change).validate()


def test_keys_of_a_patterned_block_are_refused_without_a_pattern():
    with pytest.raises(ValueError, match="attention_window"):
        TransformerConfig(attention_window=8).validate()
    with pytest.raises(RuntimeConfigError, match="attention_window"):
        RuntimeConfig.from_mapping(document(
            model={"layer_pattern": [], "expert_top_k": 1}))
    # the rotary base is the plain block's too
    plain = TransformerConfig(rope_theta=5e5)
    plain.validate()
    assert TransformerConfig().rope_theta == 10000.0


def test_the_other_paths_refuse_the_block_by_the_key_s_name(cfg):
    from kvedge_tpu.models import transformer

    with pytest.raises(ValueError, match="layer_pattern"):
        transformer.init_params(jax.random.PRNGKey(0), cfg)


def test_the_model_section_round_trips_through_toml():
    parsed = RuntimeConfig.from_mapping(document())
    assert parsed.model.layer_pattern == ("attention", "window", "window",
                                          "window")
    again = RuntimeConfig.parse(parsed.to_toml())
    assert again == parsed
    assert again.model.attention_window == WINDOW
    assert again.model.rope_theta == 1.5e6
    assert again.model.ffn_activation == "relu"
    assert again.model.router_before_mixer
    # a document without the new keys is the document it was
    plain = RuntimeConfig.from_mapping({"model": {"preset": "probe"}})
    assert "rope_theta" not in plain.to_toml()


def test_a_block_without_a_window_layer_builds_no_second_pool():
    """The two patterned blocks the benchmark has, and the plain one:
    no window pool, no table, no first position, and the pytree the
    programs were compiled for."""
    plain = TransformerConfig(vocab=64, d_model=32, n_heads=2, n_layers=2,
                              d_ff=64, max_seq=64)
    cache = kvcache.PagedKVCache(plain, slots=2, pages=8, page_size=PAGE)
    assert cache.window == 0 and cache.num_window_pages == 0
    assert cache.state.win_pool_k is None and cache.state.win_first is None
    assert len(jax.tree_util.tree_leaves(cache.state)) == 4
    assert "window_free" not in cache.page_accounting()
    assert cache.release_window_pages([0]) == 0


def test_a_preempted_row_resumes_past_the_window(cfg, params):
    """A batch-class row 90 positions long, well past its window, is
    swapped out for an interactive one and back: its pages of both
    pools and its table's first position travel together, and its
    tokens are an uninterrupted run's."""
    long_prompt, n_new = prompt_of(11, 64), 56
    alone = server_of(params, cfg, slots=1)
    try:
        want = alone.submit(long_prompt, n_new)
    finally:
        alone.close()
    server = server_of(params, cfg, slots=1, window=2,
                       sched_swap_budget_mb=64, debug_pages=True)
    try:
        victim = server.submit_stream(long_prompt, n_new, priority="batch")
        first = next(victim)
        server.submit(prompt_of(12, 32), 8)
        got = long_prompt + [first] + list(victim)
        stats = server.stats()
    finally:
        server.close()
    assert stats["sched_preemptions_total"] >= 1
    assert stats["sched_resumes_total"] >= 1
    assert got == want
    assert stats["window_free_pages"] == stats["window_pages_total"]
    assert stats["reserved_pages"] == 0


def test_the_server_counts_the_window_pool(cfg, params):
    """Three requests through two slots: the gauges and counters of the
    second pool beside the first's, the two phases in ``phase_ms``, the
    pick counters of a block with no recurrent state, and a pool that
    holds every slot's cap."""
    server = server_of(params, cfg, slots=2)
    try:
        # a row's cap is 4 pages (24 + 16 over 16, and one), the pool
        # two slots' caps: both rows run together, each past its window
        first = server.submit_stream(prompt_of(1, 64), 40)
        next(first)
        second = server.submit(prompt_of(2, 48), 24)
        list(first)
        stats = server.stats()
    finally:
        server.close()
    assert len(second) == 72
    assert stats["window_pages_total"] == 8
    assert stats["window_free_pages"] == 8
    assert stats["window_pages_released_total"] >= 6
    assert 0 < stats["window_pages_live_steps_total"] \
        < stats["pages_live_steps_total"]
    assert stats["window_pages_live_steps_total"] \
        <= 8 * stats["decode_steps_total"]
    for phase in ("loop/window_release", "admit/window_release"):
        assert stats["phase_ms"][phase][0] > 0
    assert stats["expert_picks_total"] == stats["expert_picks_held_total"] > 0
    assert "state_gb" not in stats
    assert stats["lock_held_ms_total"] == pytest.approx(
        sum(ms for _, ms in stats["lock_held_ms"].values()), rel=0.02)
