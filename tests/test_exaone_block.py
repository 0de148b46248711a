"""A block with a leading dense layer before its periods, a norm on each
sublayer's output and on each head's q and k, and a sigmoid router with
a choice bias and a scale (models/hybrid.py, models/moe.py,
models/kvcache.py), and the paged-attention kernel's blocked form
(ops/paged_attention.py), on the paged serving path, held to the
benchmark's plain reference (benchmark/references/exaone_moe.py), never
to decode.generate.

One preset at a size the CPU runs in seconds: a dense layer and two
periods of w w f w (three rotary layers bound to a window of 24
positions, base 1,000,000, to one full layer without positional
encoding), 8 query heads over 2 key heads of 16, 16 gated experts of 16,
4 a token by sigmoid scores plus a bias, gates over the picks times 2.5,
a shared expert, a head of its own. The program computes in float32
here, so that what separates it from the float32 reference is the order
of its sums and nothing else.
"""

import contextlib
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import cellspec
from kvedge_tpu.config.runtime_config import RuntimeConfig, RuntimeConfigError
from kvedge_tpu.models import hybrid, kvcache, moe
from kvedge_tpu.models.serving import PagedGenerationServer
from kvedge_tpu.models.transformer import TransformerConfig
from kvedge_tpu.ops import paged_attention

REFERENCE = cellspec.load_module(
    "exaone_moe_for_tests",
    os.path.join(cellspec.REPO, "benchmark", "references", "exaone_moe.py"))

SEQ, PAGE, WINDOW = 256, 16, 24
TYPES = ["sliding_attention"] * 3 + ["full_attention"]
PUBLISHED = {
    "first_k_dense_replace": 1, "head_dim": 16, "hidden_act": "silu",
    "hidden_size": 32, "intermediate_size": 64,
    "layer_types": (TYPES * 3)[:9], "max_position_embeddings": 512,
    "mlp_layer_types": ["dense"] + ["sparse"] * 8,
    "model_type": "exaone_moe", "moe_intermediate_size": 16, "n_group": 1,
    "norm_topk_prob": True, "num_attention_heads": 8, "num_experts": 16,
    "num_experts_per_tok": 4, "num_hidden_layers": 9,
    "num_key_value_heads": 2, "num_nextn_predict_layers": 1,
    "num_shared_experts": 1, "rms_norm_eps": 1e-5,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "sliding_window": WINDOW,
    "sliding_windows": (([WINDOW] * 3 + [0]) * 3)[:9],
    "tie_word_embeddings": False, "topk_group": 1, "vocab_size": 256,
    "payload": {"seq": SEQ},
}
MODEL = REFERENCE.model_of(PUBLISHED)
# The float32 program against the float32 reference: sums in another
# order, over 9 layers, on logits of size 0.5 (the largest gap seen is
# 8e-7). bf16 in the program's place reads 5e-3 and more, the
# reference's int8 control 2e-2: both are told from float32 a hundred
# times over.
TOLERANCE = 2e-5


def document(payload: dict | None = None, model: dict | None = None) -> dict:
    section = {k: v for k, v in MODEL.items() if k != "seq"}
    return {
        "runtime": {"name": "exaone-test", "state_dir": "/tmp/unused"},
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


def teacher_forced(cfg, params, sequence: list, n_prompt: int,
                   chunk: int = 16, window: int = 8, late_chunk=None):
    """Logits [len(sequence) - n_prompt + 1, V] of the positions from
    the prompt's last on, through the cache's own programs: the prompt
    prefilled in chunks of ``chunk``, then one decode step a token, the
    window layers' pages given back every ``window`` steps as a
    harvested decode window does; ``late_chunk`` = (slot, tokens): a
    second row's prefill chunk dispatched between two of the first
    row's steps. Also the cache and the most window pages the row
    held."""
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
        if late_chunk is not None and i == 20:
            slot, tokens = late_chunk
            cache.admit(slot, len(tokens))
            cache.prefill_chunk(params, slot,
                                jnp.asarray(tokens, jnp.int32), 0)
        logits = cache.step(params, jnp.asarray([0, token], jnp.int32),
                            active=[False, True])
        rows.append(np.asarray(logits[1]))
        most = max(most, cache.window_pages_held(1))
        if i % window == window - 1:
            cache.release_window_pages([1])
    return np.stack(rows), cache, most


# ---- (a) the served path against the reference's full forward pass -------


@pytest.fixture
def blocked_scratch(monkeypatch):
    """A scratch budget under which this preset's full-layer table (16
    pages, 8 heads, a 32-wide pool) takes the blocked form, in blocks of
    two pages: scores, weights and pads fit, the V image does not."""
    monkeypatch.setattr(paged_attention, "_SCRATCH_VMEM_BUDGET", 20 * 1024)
    monkeypatch.setattr(paged_attention, "_PAD_VMEM_BUDGET", 4 * 1024)
    assert paged_attention.decode_scratch_form(16, PAGE, 32, 8) == "blocked"
    assert paged_attention.blocked_block_pages(16, PAGE, 32) == 2
    # the window layers' table of a row's cap keeps the whole form
    assert paged_attention.decode_scratch_form(4, PAGE, 32, 8) == "whole"


def test_served_tokens_and_logits_are_the_reference_s(cfg, params, weights):
    """A context of 134 positions, five times the window: the prompt of
    70 in chunks of 16 (the second crosses the window's edge at 24),
    then 64 tokens in decode windows of 4: the row passes four page
    boundaries while it decodes, and its window layers give a page back
    at each."""
    prompt, n_new = prompt_of(1, 70), 64
    server = PagedGenerationServer(
        params, cfg, slots=4, pages=64, page_size=PAGE, prefill_chunk=16,
        prefix_cache=False, window=4)
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
    assert len(set(generated)) > n_new // 4  # no collapse onto one token
    # the pages behind the window went back while the row was live, and
    # all of them when it ended; the sums are the shapes'
    assert stats["window_pages_total"] == 4 * 4  # slots x a row's cap
    assert stats["window_pages_released_total"] >= 4
    assert stats["window_free_pages"] == stats["window_pages_total"]
    assert stats["free_pages"] == stats["pages_total"]
    steps = stats["decode_steps_total"]
    assert 2 * steps <= stats["window_pages_live_steps_total"] <= 4 * steps
    assert stats["window_pages_live_steps_total"] \
        < stats["pages_live_steps_total"] <= 9 * steps
    # the picks are the eight sparse layers': the dense layer has none
    assert stats["expert_picks_total"] == 8 * 4 * steps
    assert stats["expert_picks_held_total"] == stats["expert_picks_total"]
    assert stats["expert_reads_per_step"] == 8 * 16
    got, cache, most = teacher_forced(cfg, params, sequence[:-1],
                                      len(prompt))
    np.testing.assert_allclose(got, want[:n_new], rtol=0, atol=TOLERANCE)
    assert most <= cache.window_cap == 4
    assert cache.slot_length(1) == len(sequence) - 1 > 5 * WINDOW


def test_a_chunk_between_steps_and_the_blocked_form_read_the_same_logits(
        cfg, params, weights, blocked_scratch):
    """The same row with the kernel forced under the interpreter, its
    full layers in the blocked form (a context of 134 positions is nine
    pages: past four block boundaries of two pages) and its window
    layers in the whole form, and with another row's prefill chunk
    dispatched between two of its steps: the reference's logits."""
    sequence, n_prompt = prompt_of(1, 70) + prompt_of(2, 64), 70
    (want,) = REFERENCE.logits(MODEL, weights, [sequence], [n_prompt - 1])
    kernel = dataclasses.replace(cfg, paged_attention="kernel")
    got, cache, _ = teacher_forced(kernel, params, sequence[:-1], n_prompt,
                                   late_chunk=(0, prompt_of(3, 16)))
    np.testing.assert_allclose(got, want[:len(got)], rtol=0, atol=TOLERANCE)
    assert cache.slot_length(1) == 133


def _without_bias(p: dict) -> dict:
    return {**p, "ffn": {k: v for k, v in p["ffn"].items()
                         if k != "router_bias"}}


LEFT_OUT = {
    "bf16": lambda cfg, p: (dataclasses.replace(cfg, dtype="bfloat16"), p),
    "the choice bias": lambda cfg, p: (cfg, _without_bias(p)),
    "the scale": lambda cfg, p: (
        dataclasses.replace(cfg, router_scale=1.0), p),
    "the norm on q and k": lambda cfg, p: (
        dataclasses.replace(cfg, qk_norm=False), p),
    "the norm's place": lambda cfg, p: (
        dataclasses.replace(cfg, norm_after=False), p),
    "the dense layer": lambda cfg, p: (cfg, {**p, "dense": {
        **p["dense"], "w_out": jnp.zeros_like(p["dense"]["w_out"])}}),
    "the sigmoid": lambda cfg, p: (dataclasses.replace(
        cfg, router_score="softmax", router_bias=False, router_scale=1.0),
        _without_bias(p)),
}


@pytest.mark.parametrize("what", sorted(LEFT_OUT))
def test_the_tolerance_tells_a_block_with_a_part_left_out(
        cfg, params, weights, what):
    """The program in a lower precision, or with one of the block's
    parts left out, is told from the reference ten tolerances over; so
    is the reference's own int8 control."""
    sequence, n_prompt = prompt_of(1, 70) + prompt_of(2, 20), 70
    (want,) = REFERENCE.logits(MODEL, weights, [sequence], [n_prompt - 1])
    other_cfg, other_params = LEFT_OUT[what](cfg, params)
    got, _, _ = teacher_forced(other_cfg, other_params, sequence[:-1],
                               n_prompt)
    assert np.abs(got - want[:len(got)]).max() > 10 * TOLERANCE
    if what == "bf16":
        (control,) = REFERENCE.logits(MODEL, weights, [sequence],
                                      [n_prompt - 1], quant="int8")
        assert np.abs(control - want).max() > 10 * TOLERANCE


# ---- (b) the shares add up to the uncut layer ------------------------------


def test_the_shares_of_a_layer_add_up_to_the_uncut_reference_s(cfg):
    """Eight chips share a layer by its experts: the parts of the routed
    sum that the eight shares give (two experts each), with the shared
    expert, which every chip computes alike, counted once, are the uncut
    reference's feed-forward for the whole layer; the dense layer is
    computed whole by each and counted once; and the vocabulary's slices
    tile the uncut logits."""
    layer = 3  # a sparse layer (the full one of the first period)
    whole = REFERENCE.layer_weights(MODEL, layer)
    x = jax.random.normal(jax.random.PRNGKey(5), (40, 32), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want, picks = REFERENCE.feed_forward(MODEL, x, whole)
    shared = hybrid._shared_expert(
        cfg, x, whole["shared_in"], whole["shared_out"])
    total = jnp.zeros_like(x)
    seen = 0
    for chip in range(8):
        w = REFERENCE.layer_weights(MODEL, layer, held=(2 * chip, 2))
        part, counted = moe.held_experts_ffn(
            x, w["router"], w["experts_in"], w["experts_out"], top_k=4,
            first=2 * chip, gated=True, renormalize=True, score="sigmoid",
            bias=w["router_bias"], scale=2.5)
        total = total + part
        seen += int(counted[1])
        # and the reference, given the same share, gives the same part
        with jax.default_matmul_precision("highest"):
            ref_part, _ = REFERENCE.routed(x, w, top_k=4, scale=2.5)
        np.testing.assert_allclose(part, ref_part, rtol=0, atol=2e-6)
    assert seen == 40 * 4 == picks.size  # every pick fell on one share
    np.testing.assert_allclose(total + shared, want, rtol=0, atol=2e-6)
    dense = REFERENCE.layer_weights(MODEL, 0)
    with jax.default_matmul_precision("highest"):
        f, none = REFERENCE.feed_forward(MODEL, x, dense)
    assert none is None
    np.testing.assert_allclose(
        hybrid._shared_expert(cfg, x, dense["dense_in"], dense["dense_out"]),
        f, rtol=0, atol=2e-6)
    # the vocabulary: 8 slices of 32 rows of the head tile its logits
    head = REFERENCE.table(MODEL, "head")
    with jax.default_matmul_precision("highest"):
        uncut = REFERENCE.readout(x, head, eps=1e-5)
        tiled = jnp.concatenate(
            [REFERENCE.readout(x, head[32 * i:32 * (i + 1)], eps=1e-5)
             for i in range(8)], axis=-1)
    np.testing.assert_allclose(tiled, uncut, rtol=0, atol=1e-6)


# ---- (c) the router alone --------------------------------------------------


def test_the_router_scores_chooses_by_bias_and_gates_by_score():
    """Scores are sigmoids of the logits, each expert alone; the bias
    moves the choice and never a gate: with the recipe's bias a counted
    share of the picks changes, and a pick both choices share is gated
    by its score over the sum of ITS choice's scores; the gates of a
    token sum to the scale."""
    d, n, k, scale = 32, 16, 4, 2.5
    keys = jax.random.split(jax.random.PRNGKey(9), 3)
    x = jax.random.normal(keys[0], (512, d), jnp.float32) * 1.4
    router = jax.random.normal(keys[1], (d, n), jnp.float32) * d ** -0.5
    bias = jax.random.normal(keys[2], (n,), jnp.float32) * 0.01
    scores, with_bias, gates = moe._route(
        x, router, k, True, "sigmoid", bias, scale)
    _, without, plain_gates = moe._route(
        x, router, k, True, "sigmoid", None, scale)
    logits = np.asarray(x, np.float64) @ np.asarray(router, np.float64)
    np.testing.assert_allclose(scores, 1 / (1 + np.exp(-logits)), atol=1e-6)
    scores, with_bias, without = map(np.asarray, (scores, with_bias, without))
    # the choice: the k largest of score + bias, and of score
    for idx, ranked in ((with_bias, scores + np.asarray(bias)),
                        (without, scores)):
        assert (np.sort(idx, axis=-1)
                == np.sort(np.argsort(-ranked, axis=-1)[:, :k], axis=-1)).all()
    changed = sum(set(a) != set(b) for a, b in zip(with_bias, without))
    assert 10 <= changed <= 256  # it changes picks, and not most of them
    # the gates: the picked scores over their sum, times the scale
    picked = np.take_along_axis(scores, with_bias, axis=-1)
    np.testing.assert_allclose(
        gates, scale * picked / picked.sum(-1, keepdims=True), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(gates).sum(-1), scale, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(plain_gates).sum(-1), scale,
                               rtol=1e-6)
    # a token whose picks the bias did not change keeps every gate's value
    same = [i for i, (a, b) in enumerate(zip(with_bias, without))
            if list(a) == list(b)]
    assert len(same) > 100
    np.testing.assert_array_equal(np.asarray(gates)[same],
                                  np.asarray(plain_gates)[same])
    # the reference's router is the same function
    ref_idx, ref_gates = REFERENCE.route(x, router, bias, k, scale)
    assert (np.asarray(ref_idx) == with_bias).all()
    np.testing.assert_allclose(ref_gates, gates, rtol=1e-6)
    # and the softmax router is what it was: no bias, no scale
    _, soft_idx, soft_gates = moe._route(x, router, k, True)
    np.testing.assert_allclose(np.asarray(soft_gates).sum(-1), 1.0,
                               rtol=1e-6)
    assert (np.asarray(soft_idx) == without).all()  # sigmoid is monotone


def test_the_recipe_s_bias_changes_picks_in_the_block(cfg, params, weights):
    """In the block itself, over a sequence's tokens in every sparse
    layer: the reference's picks with the recipe's bias (normal times
    0.01) differ from the picks without it in a counted share."""
    sequence = prompt_of(4, 256)  # a whole block of the reference's queries
    picks: list = []
    list(REFERENCE.logits(MODEL, weights, [sequence], [0], picks=picks))
    assert len(picks) == 8  # the sparse layers
    changed = total = 0
    with jax.default_matmul_precision("highest"):
        x = weights["embedding"][jnp.asarray(sequence, jnp.int32)]
        for i in range(MODEL["n_layers"]):
            w = REFERENCE.layer_weights(MODEL, i)
            if not w["dense"]:
                # the router's input: the stream after the layer's mixer
                mixed = x + REFERENCE._rmsnorm(REFERENCE.attention(
                    x, {"w_qkv": w["w_qkv"], "w_out": w["w_out"]},
                    h=8, kv=2, dh=16, eps=1e-5,
                    window=WINDOW if w["kind"] == "window" else 0,
                    theta=1e6 if w["kind"] == "window" else 0.0), 1e-5)
                biased, _ = REFERENCE.route(mixed, w["router"],
                                            w["router_bias"], 4, 2.5)
                plain, _ = REFERENCE.route(mixed, w["router"],
                                           w["router_bias"], 4, 2.5,
                                           biased=False)
                assert (np.asarray(biased) == picks[i - 1][0]).all()
                changed += sum(set(a) != set(b) for a, b in
                               zip(np.asarray(biased), np.asarray(plain)))
                total += len(sequence)
            x, _ = REFERENCE.layer(MODEL, x, w)
    assert 0.02 * total < changed < 0.5 * total, (changed, total)


# ---- (d) the blocked form against the gather -------------------------------


def _attended(cfg, state, x, w_qkv, w_out, positions, window, qk_norm=None):
    pools = ((state.win_pool_k, state.win_pool_v, None, None) if window
             else (state.pool_k, state.pool_v, None, None))
    out, _ = kvcache._paged_attention(
        cfg, state, x, w_qkv, w_out, 1, pools, positions, window=window,
        qk_norm=qk_norm)
    return np.asarray(out)


@pytest.mark.parametrize("window", [0, 40])
def test_the_blocked_form_is_the_gather_s_scores_and_weights(
        blocked_scratch, window):
    """Seven rows over a table of 16 pages in blocks of two: dead, a row
    ending on a block's last position (63), one on the next block's
    first (64), dead, a row inside its first page, a row at the table's
    end (255), and one ending mid-block; with a window, over tables that
    start past 0 (the oldest page's leading columns fall to the bound).
    The blocked form's scores and weights are the gather's bit for bit:
    with V the identity on a page's rows its output IS the weights, and
    they are equal in every bit; with random V its outputs agree within
    float32 summation order, and a dead row's are zeros."""
    gather = config_of(dtype="bfloat16", paged_attention="gather",
                       qk_norm=False)
    kernel = dataclasses.replace(gather, paged_attention="kernel")
    rows, cap, pages, width = 7, 16, 80, 2 * 16
    keys = jax.random.split(jax.random.PRNGKey(11), 5)
    lengths = np.array([0, 63, 64, 0, 5, 255, 150], np.int32)
    first = np.zeros(rows, np.int32)
    if window:
        # what a window layer's table holds: from the page of the
        # window's lower edge on
        first = np.maximum(0, lengths - window + 1) // PAGE * PAGE
    tables = np.zeros((rows, cap), np.int32)
    at = 1
    for b in np.flatnonzero(lengths):
        held = (lengths[b] - first[b]) // PAGE + 1
        tables[b, :held] = np.arange(at, at + held)
        at += held
    pool_k = jax.random.normal(keys[0], (2, pages, PAGE, width), jnp.bfloat16)
    normed = jax.random.normal(keys[2], (rows, 1, 32), jnp.bfloat16)
    w_qkv = jax.random.normal(keys[3], (32, (8 + 4) * 16),
                              jnp.bfloat16) * 32 ** -0.5
    w_out = jnp.eye(8 * 16, dtype=jnp.bfloat16)  # the heads as attended
    positions = jnp.asarray(lengths)[:, None]
    live = lengths > 0

    def state_of(pool_v):
        named = (dict(win_pool_k=pool_k, win_pool_v=pool_v,
                      win_tables=jnp.asarray(tables),
                      win_first=jnp.asarray(first),
                      pool_k=pool_k[:, :4], pool_v=pool_v[:, :4],
                      tables=jnp.zeros((rows, cap), jnp.int32))
                 if window else
                 dict(pool_k=pool_k, pool_v=pool_v,
                      tables=jnp.asarray(tables)))
        return kvcache.PagedState(lengths=jnp.asarray(lengths), **named)

    # (1) V a one-hot of the position within its page, in the first key
    # head's channels: a head's output channel c is the sum of its
    # weights at in-page place c over the row's pages; with one page a
    # block that is each weight alone, and the sums' terms are one
    # weight and zeros wherever the other pages are masked
    marks = jnp.zeros((2, pages, PAGE, width), jnp.bfloat16).at[
        :, :, jnp.arange(PAGE), jnp.arange(PAGE)].set(1)
    want = _attended(gather, state_of(marks), normed, w_qkv, w_out,
                     positions, window)
    got = _attended(kernel, state_of(marks), normed, w_qkv, w_out,
                    positions, window)
    np.testing.assert_array_equal(got[4].view(np.uint16),
                                  want[4].view(np.uint16))  # one page: bits
    np.testing.assert_allclose(got[live].astype(np.float32),
                               want[live].astype(np.float32),
                               rtol=0, atol=2 ** -8)  # one bf16 step of a sum
    assert not got[~live].any()
    # (2) random V: the same weights against the same values, a page's
    # products summed first and the pages then: float32 summation order
    # on outputs of size 1, rounded to bf16 (one step is 2 ** -8)
    pool_v = jax.random.normal(keys[1], (2, pages, PAGE, width),
                               jnp.bfloat16)
    want = _attended(gather, state_of(pool_v), normed, w_qkv, w_out,
                     positions, window).astype(np.float32)
    got = _attended(kernel, state_of(pool_v), normed, w_qkv, w_out,
                    positions, window).astype(np.float32)
    np.testing.assert_allclose(got[live], want[live], rtol=0, atol=2 ** -7)
    assert (got[live] == want[live]).mean() > 0.9
    assert not got[~live].any()


def test_the_blocked_form_s_weights_are_the_whole_form_s_in_every_bit():
    """The kernel's two forms alone on one table (blocks of two pages of
    five): scores and softmax are the same operations on the same
    values, so with V a one-hot a page the output, the weights
    themselves, is equal in every bit; an int8 pool has no blocked
    form."""
    keys = jax.random.split(jax.random.PRNGKey(13), 2)
    q = jax.random.normal(keys[0], (3, 8, 16), jnp.bfloat16)
    pool_k = jax.random.normal(keys[1], (1, 12, PAGE, 32), jnp.bfloat16)
    pool_v = jnp.zeros((1, 12, PAGE, 32), jnp.bfloat16).at[
        :, :, 3, :].set(1)  # each page gives its fourth position's weight
    tables = jnp.asarray([[1, 2, 3, 4, 5], [0] * 5, [6, 7, 8, 0, 0]],
                         jnp.int32)
    positions = jnp.asarray([79, -1, 40], jnp.int32)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(paged_attention, "_PAD_VMEM_BUDGET", 4 * 1024)
        whole, blocked = (np.asarray(paged_attention.paged_decode_attention(
            q, pool_k, pool_v, tables, positions, 0, interpret=True,
            blocked=form)) for form in (False, True))
    assert not blocked[1].any() and whole[[0, 2]].any()
    # a row's output is the sum of its pages' fourth weights: row 2's
    # three pages' in another order of additions, within a bf16 step
    np.testing.assert_allclose(blocked.astype(np.float32),
                               whole.astype(np.float32), rtol=0,
                               atol=2 ** -9)
    with pytest.raises(ValueError, match="no int8"):
        paged_attention.paged_decode_attention(
            q, pool_k.astype(jnp.int8), pool_v.astype(jnp.int8), tables,
            positions, 0, interpret=True, blocked=True,
            scale_k=jnp.ones((1, 12, PAGE, 2)),
            scale_v=jnp.ones((1, 12, PAGE, 2)))


def test_which_form_a_table_takes_is_its_shape_s():
    """The form is static, by the scratch a table's shape needs: the
    four accepted cells' tables and this block's window layers' take the
    whole form, this block's full layer (64 heads over 64 pages of a
    1,024-wide pool) the blocked one, as would the other window block's
    full layer at its published 16,384 positions; past what the scores'
    scratch holds, the gather."""
    form = paged_attention.decode_scratch_form
    assert form(24, 128, 256, 24) == "whole"     # starcoder2-3b
    assert form(24, 128, 512, 32) == "whole"     # granite-4.0-h-small
    assert form(24, 128, 1024, 64) == "whole"    # solar-open2-250b
    assert form(64, 128, 512, 28) == "whole"     # smallthinker, full
    assert form(35, 128, 512, 28) == "whole"     # smallthinker, window
    assert form(4, 128, 1024, 64) == "whole"     # this block, window
    assert form(32, 128, 1024, 64) == "whole"    # this block at 4,096
    assert form(64, 128, 1024, 64) == "blocked"  # this block, full
    assert form(128, 128, 512, 28) == "blocked"  # smallthinker at 16,384
    assert form(128, 128, 1024, 64) == "blocked"
    assert form(256, 128, 1024, 64) == ""
    assert paged_attention.blocked_block_pages(64, 128, 1024) == 4
    assert paged_attention.block_pages(64, 128, 1024) == 2
    # "auto" asks the same function, and no option chooses
    cfg = config_of(dtype="bfloat16", paged_attention="kernel")
    big = dataclasses.replace(cfg, n_heads=64, n_kv_heads=8, head_dim=128)
    assert kvcache._use_paged_kernel(big, 128, 1024, 64) == "blocked"
    assert kvcache._use_paged_kernel(big, 128, 1024, 4) == "whole"
    assert kvcache._use_paged_kernel(
        dataclasses.replace(big, paged_attention="gather"), 128, 1024,
        64) == ""
    assert kvcache._use_paged_kernel(  # on the CPU "auto" is the gather
        dataclasses.replace(big, paged_attention="auto"), 128, 1024, 64) == ""


# ---- (e) a block without the new parts traces the program it traced -------


@contextlib.contextmanager
def _kernel_calls():
    """The ``blocked`` of each paged_decode_attention call traced inside
    (under the interpreter a lowered program's text holds the kernel's
    body and not its name: tests/test_chip_compile.py counts the names
    in programs lowered for the chip)."""
    calls, real = [], paged_attention.paged_decode_attention

    def recording(*args, blocked=False, **kw):
        calls.append(blocked)
        return real(*args, blocked=blocked, **kw)

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(paged_attention, "paged_decode_attention", recording)
        yield calls


def _operations(lowered) -> dict:
    """name -> count of the operations in a lowered program's text."""
    import collections
    import re

    return collections.Counter(
        re.findall(r"= \"?((?:stablehlo|func|tpu|chlo)\.[\w.]+)",
                   lowered.as_text()))


PARENT_OPERATIONS = {
    "starcoder2-3b.batchgen": 2272, "granite-4.0-h-small.batchgen": 6231,
    "solar-open2-250b.batchgen": 3883, "smallthinker-21ba3b.longmix": 8994}


@pytest.mark.parametrize("name", sorted(PARENT_OPERATIONS))
def test_an_accepted_configuration_s_window_holds_no_new_operation(name):
    """Each accepted configuration's block at a probe size (its pattern,
    its flags, small widths): the lowered decode window is the same
    operations whether the config spells the new keys at their defaults
    or was made before they existed (the defaults are all the parent
    has), it holds no blocked call, and a config that sets one of them
    lowers another program."""
    cell = cellspec.load_cell(name)
    model = dict(cell.config["model"])
    small = {"vocab": 256, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
             "head_dim": 16, "d_ff": 32}
    small.update({k: v for k, v in {
        "experts": 8, "experts_held": 4, "expert_top_k": 2, "shared_ff": 32,
        "ssm_heads": 4, "ssm_head_dim": 16, "ssm_state": 16,
        "ssm_gate_rank": 16, "attention_window": 32}.items() if k in model})
    if "head_dim" not in model:
        del small["head_dim"]
    model.update(small)
    model.pop("seq", None)
    pattern = model.get("layer_pattern") or []
    model["n_layers"] = len(pattern) or 2
    doc = document(model=None)
    doc["model"] = model
    if not pattern:
        doc["payload"]["serving_prefix_cache"] = True
    from kvedge_tpu.runtime.workload import derive_model_config

    one = jax.devices()[:1]
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax, "devices", lambda *a, **k: one)
        cfg, _ = derive_model_config(RuntimeConfig.from_mapping(doc), seq=SEQ)
    cfg = dataclasses.replace(cfg, paged_attention="kernel")
    assert (cfg.dense_layers, cfg.router_score, cfg.qk_norm,
            cfg.norm_after, cfg.router_bias, cfg.router_scale) == (
                0, "softmax", False, False, False, 1.0)

    def lowered(cfg):
        if cfg.layer_pattern:
            params = hybrid.init_params(jax.random.PRNGKey(0), cfg)
        else:
            from kvedge_tpu.models import init_params
            params = init_params(jax.random.PRNGKey(0), cfg)
        cache = kvcache.PagedKVCache(cfg, slots=2, pages=16, page_size=PAGE,
                                     window_advance=16)
        return cache.lower_decode_window(params, 4)

    with _kernel_calls() as calls:
        base = _operations(lowered(cfg))
    assert calls and not any(calls), calls  # the kernel, never blocked
    # counted on the parent of ISSUE 43 with this same construction
    assert sum(base.values()) == PARENT_OPERATIONS[name], dict(base)
    if pattern and set(pattern) & {"attention", "window"}:
        other = dataclasses.replace(cfg, qk_norm=True)
        assert sum(_operations(lowered(other)).values()) \
            > sum(base.values())


def test_this_block_s_window_holds_the_blocked_call_for_its_full_layers(
        cfg, params, blocked_scratch):
    """This block's lowered decode window at the preset's size, the
    kernel forced: one blocked call (the period's full layer, in the
    scan's body) and four in the whole form (the leading layer's before
    the scan, the period's three window layers'), and the scopes that
    name the block's parts in a capture."""
    kernel = dataclasses.replace(cfg, paged_attention="kernel")
    cache = kvcache.PagedKVCache(kernel, slots=2, pages=32, page_size=PAGE,
                                 window_advance=16)
    with _kernel_calls() as calls:
        lowered = cache.lower_decode_window(params, 4)
    assert calls == [False, False, False, True, False]  # w | w w f w
    named = lowered.as_text(debug_info=True)
    for scope in ("kvedge/dense", "kvedge/router", "kvedge/window",
                  "kvedge/attention", "kvedge/experts"):
        assert scope in named, scope


# ---- (f) refusals by name ---------------------------------------------------


@pytest.mark.parametrize("key, value", [
    ("dense_layers", 1), ("dense_ff", 64), ("router_score", "sigmoid"),
    ("router_bias", True), ("router_scale", 2.5), ("qk_norm", True),
    ("norm_after", True)])
def test_a_plain_block_refuses_the_new_keys_by_name(key, value):
    plain = {"vocab": 256, "d_model": 32, "n_heads": 4, "n_layers": 2,
             "d_ff": 64, key: value}
    doc = document()
    doc["model"] = plain
    with pytest.raises(RuntimeConfigError, match=key):
        RuntimeConfig.from_mapping(doc)
    with pytest.raises(ValueError, match=key):
        TransformerConfig(vocab=256, d_model=32, n_heads=4, n_layers=2,
                          d_ff=64, max_seq=SEQ, **{key: value}).validate()


@pytest.mark.parametrize("model, said", [
    ({"dense_ff": 0}, "dense_ff"),
    ({"n_layers": 8}, "dense_layers"),
    ({"ffn_gated": False}, "ffn_gated"),
    ({"router_score": "tanh"}, "router_score"),
    ({"router_score": "softmax"}, "router_bias"),
    ({"dense_layers": 2, "n_layers": 10,
      "layer_pattern": ["window", "attention", "window", "attention"],
      "rotary": False}, "one kind"),
])
def test_a_pattern_refuses_what_does_not_go_together(model, said):
    with pytest.raises((RuntimeConfigError, Exception), match=said):
        config_of(model=model)


@pytest.mark.parametrize("payload, said", [
    ({"serving_prefix_cache": True}, "serving_prefix_cache"),
    ({"serving_speculative": 3}, "serving_speculative"),
    ({"serving_kv_dtype": "int8"}, "serving_kv_dtype"),
    ({"kind": "train", "corpus": "x"}, "layer_pattern"),
    ({"serving": "contiguous"}, "paged"),
])
def test_the_payload_refuses_what_the_block_cannot_run(payload, said):
    with pytest.raises(RuntimeConfigError, match=said):
        RuntimeConfig.from_mapping(document(payload))


def test_the_server_and_the_other_paths_refuse_by_name(cfg, params):
    from kvedge_tpu.models import init_params

    with pytest.raises(ValueError, match="prefix_cache"):
        PagedGenerationServer(params, cfg, slots=2, pages=16,
                              page_size=PAGE, prefix_cache=True)
    with pytest.raises(ValueError, match="layer_pattern"):
        init_params(jax.random.PRNGKey(0), cfg)  # the trainer's tree
    with pytest.raises(ValueError, match="window"):
        kvcache.PagedKVCache(cfg, slots=2, pages=16, page_size=PAGE,
                             kv_dtype="int8")


def test_the_document_states_the_new_keys_only_where_set(cfg):
    """``[model]`` round-trips through the TOML the product writes, and a
    block without the new keys keeps the document it had."""
    parsed = RuntimeConfig.from_mapping(document())
    text = parsed.to_toml()
    for line in ("dense_layers = 1", "dense_ff = 64",
                 'router_score = "sigmoid"', "router_bias = true",
                 "router_scale = 2.5", "qk_norm = true", "norm_after = true"):
        assert line in text, line
    assert RuntimeConfig.parse(text) == parsed
    assert (cfg.dense_layers, cfg.dense_ff, cfg.leading_kinds) == (
        1, 64, ("window",))
    assert (cfg.kv_layers, cfg.window_layers, cfg.periods) == (2, 7, 2)
    other = cellspec.load_cell("smallthinker-21ba3b.longmix")
    text = RuntimeConfig.from_mapping(
        cellspec.runtime_document(other, "<dir>", "tpu")).to_toml()
    for key in ("dense_layers", "dense_ff", "router_score", "router_bias",
                "router_scale", "qk_norm", "norm_after"):
        assert key not in text, key
