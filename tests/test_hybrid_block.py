"""The patterned block (models/hybrid.py, models/ssm.py, moe.held_experts_ffn)
on the paged serving path, held to the benchmark's plain reference
(benchmark/references/granite_moe_hybrid.py), never to decode.generate:
the block exists once.

A preset with every kind of layer at a size the CPU runs in seconds:
pattern m m a m over two periods, 8 routed experts of which 4 are held,
3 a token, a shared expert, 4 SSM heads of 8 with state 16, all four
multipliers other than 1, no rotary. The program computes in float32
here, so that what separates it from the float32 reference is the order
of its sums and nothing else.
"""

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

REFERENCE = cellspec.load_module(
    "granite_moe_hybrid_for_tests",
    os.path.join(cellspec.REPO, "benchmark", "references",
                 "granite_moe_hybrid.py"))

# The preset under the published key names, as a configuration's file
# holds them: the reference's model_of makes the program's [model] of it.
PUBLISHED = {
    "attention_bias": False, "attention_multiplier": 0.2,
    "embedding_multiplier": 3.0, "hidden_act": "silu", "hidden_size": 32,
    "intermediate_size": 16,
    "layer_types": ["mamba", "mamba", "attention", "mamba"] * 2,
    "logits_scaling": 2.0, "mamba_chunk_size": 16, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 8, "mamba_d_state": 16,
    "mamba_expand": 1, "mamba_n_groups": 1, "mamba_n_heads": 4,
    "mamba_proj_bias": False, "normalization_function": "rmsnorm",
    "num_attention_heads": 4, "num_experts_per_tok": 3,
    "num_hidden_layers": 8, "num_key_value_heads": 2,
    "num_local_experts": 4, "published": {"num_local_experts": 8},
    "position_embedding_type": "nope", "residual_multiplier": 0.5,
    "rms_norm_eps": 1e-5, "shared_intermediate_size": 24,
    "tie_word_embeddings": True, "vocab_size": 128,
}
MODEL = REFERENCE.model_of(PUBLISHED)
SEQ = 256


def document(payload: dict | None = None, model: dict | None = None,
             mesh: dict | None = None, **more) -> dict:
    return {
        "runtime": {"name": "hybrid-test", "state_dir": "/tmp/unused"},
        "tpu": {"platform": "cpu", "expected_chips": 1},
        "mesh": mesh or {"axes": {"data": 1}},
        "model": {**MODEL, **(model or {})},
        "payload": {"kind": "serve", "serving": "paged", "seq": SEQ,
                    "serving_prefix_cache": False, **(payload or {})},
        **more,
    }


def config_of(model: dict | None = None) -> TransformerConfig:
    """The program's config through the product's own path ([model] ->
    ModelSpec -> derive_model_config), in float32."""
    from kvedge_tpu.runtime.workload import derive_model_config

    cfg = RuntimeConfig.from_mapping(document(model=model))
    one = jax.devices()[:1]  # of the tests' eight virtual devices
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax, "devices", lambda *a, **k: one)
        tcfg, _ = derive_model_config(cfg, seq=SEQ)
    return dataclasses.replace(tcfg, dtype="float32")


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
    return [int(t) for t in
            np.random.default_rng(seed).integers(0, MODEL["vocab"], n)]


def server_of(params, cfg, **kw):
    kw = {"slots": 4, "pages": 64, "page_size": 16, "prefill_chunk": 16,
          "prefix_cache": False, "window": 4, **kw}
    return PagedGenerationServer(params, cfg, **kw)


def teacher_forced(cfg, params, sequence: list, n_prompt: int,
                   chunk: int = 16, recurrent=None):
    """Logits [len(sequence) - n_prompt + 1, V] of the positions from
    the prompt's last on, through the cache's own programs: the prompt
    prefilled in chunks of ``chunk``, then one decode step a token."""
    cache = kvcache.PagedKVCache(cfg, slots=2, pages=32, page_size=16)
    if recurrent is not None:
        cache.state = dataclasses.replace(
            cache.state, recurrent=recurrent(cache.state.recurrent))
    cache.admit(1, n_prompt)
    for lo in range(0, n_prompt, chunk):
        out = cache.prefill_chunk(
            params, 1, jnp.asarray(sequence[lo:min(n_prompt, lo + chunk)],
                                   jnp.int32), lo)
    rows = [np.asarray(out)]
    for token in sequence[n_prompt:]:
        logits = cache.step(params, jnp.asarray([0, token], jnp.int32),
                            active=[False, True])
        rows.append(np.asarray(logits[1]))
    return np.stack(rows), cache


# ---- (a) the served path against the reference's full forward pass -------

# Both sides are float32 and differ in the order of their sums alone (a
# product over all held experts for a loop over them, the chunk form of
# the SSM for the literal recurrence, a cache for none). Read here, on
# logits of size 0.07: 1.6e-7 between them; 1.9e-3 with the recurrent
# state kept in bf16 (each of the 63 positions' states rounded to 8
# bits); 1.9e-2 from the reference's int8 control. 2e-5 leaves the
# program a hundred times its reading and fails both of the others
# ninety times over.
LOGIT_TOLERANCE = 2e-5


def test_served_tokens_and_logits_are_the_reference_s(cfg, params, weights):
    prompt, n_new = prompt_of(1, 40), 24
    server = server_of(params, cfg)
    try:
        served = server.submit(prompt, n_new)
    finally:
        server.close()
    sequence, generated = served, served[len(prompt):]
    assert sequence[:len(prompt)] == prompt and len(generated) == n_new
    (want,) = REFERENCE.logits(MODEL, weights, [sequence],
                               [len(prompt) - 1])
    # every served token is the reference's choice, or ties with it
    gaps = want[:n_new].max(axis=-1) - want[np.arange(n_new), generated]
    assert gaps.max() <= LOGIT_TOLERANCE
    # and the logits the cache's programs give at those positions (prefill
    # in chunks of 16, then step by step over the served tokens) are the
    # reference's
    got, _ = teacher_forced(cfg, params, sequence[:-1], len(prompt))
    np.testing.assert_allclose(got, want[:n_new], rtol=0,
                               atol=LOGIT_TOLERANCE)

    # the tolerance tells the precisions apart: a bf16 recurrent state ...
    def in_bf16(recurrent):
        return {**recurrent, "ssm": recurrent["ssm"].astype(jnp.bfloat16)}

    rough, cache = teacher_forced(cfg, params, sequence[:-1], len(prompt),
                                  recurrent=in_bf16)
    assert cache.state.recurrent["ssm"].dtype == jnp.bfloat16
    assert np.abs(rough - want[:n_new]).max() > 10 * LOGIT_TOLERANCE
    # ... and the reference's own int8 control
    (control,) = REFERENCE.logits(MODEL, weights, [sequence],
                                  [len(prompt) - 1], quant="int8")
    assert np.abs(control[:n_new] - want[:n_new]).max() \
        > 10 * LOGIT_TOLERANCE


# ---- (b) the share -------------------------------------------------------


def test_the_two_halves_and_the_shared_expert_add_up_to_the_whole_layer():
    """The parts of the routed sum that the two chips of the deployment
    give, with the shared expert counted once, are the uncut reference's
    layer: experts 0-3 here, 4-7 on the other chip, all 8 in the
    reference."""
    with jax.default_matmul_precision("highest"):
        h = jax.random.normal(jax.random.PRNGKey(3), (24, MODEL["d_model"]))
        whole = REFERENCE.layer_weights(MODEL, 1, held=(0, 8))
        want, _ = REFERENCE.feed_forward(h, whole,
                                         top_k=MODEL["expert_top_k"])
        shared = REFERENCE._gated(h, whole["shared_in"],
                                  whole["shared_out"], "")
        total = shared
        picks = np.zeros(3, np.int64)
        for first in (0, 4):
            half = REFERENCE.layer_weights(MODEL, 1, held=(first, 4))
            part, counted = moe.held_experts_ffn(
                h, half["router"], half["experts_in"], half["experts_out"],
                top_k=MODEL["expert_top_k"], first=first, gated=True,
                renormalize=True)
            total = total + part
            picks += np.asarray(counted[:2].tolist() + [0])
            # the reference, given the same share, gives the same part
            ref_part, _ = REFERENCE.routed(h, half,
                                           top_k=MODEL["expert_top_k"])
            np.testing.assert_allclose(part, ref_part, atol=1e-5)
    np.testing.assert_allclose(total, want, atol=1e-5)
    # every pick falls on one chip or the other
    assert picks[1] == 24 * 3 and picks[0] == 2 * 24 * 3


# ---- (c), (d) slots and dead rows ---------------------------------------


def test_a_reused_slot_starts_from_a_zero_state(cfg, params):
    """A row's tokens do not depend on who had the slot: one slot, the
    same request before and after another."""
    server = server_of(params, cfg, slots=1)
    try:
        first = server.submit(prompt_of(5, 32), 12)
        server.submit(prompt_of(6, 48), 20)
        again = server.submit(prompt_of(5, 32), 12)
        resets = server.stats()["phase_ms"]["admit/state_reset"][0]
    finally:
        server.close()
    assert first == again
    assert resets == 3


def test_a_dead_row_s_state_stands_still_across_a_window(cfg, params):
    cache = kvcache.PagedKVCache(cfg, slots=4, pages=32, page_size=16)
    for slot, seed in ((0, 7), (2, 8)):
        cache.admit(slot, 32)
        cache.prefill(params, slot, jnp.asarray(prompt_of(seed, 32),
                                                jnp.int32))
    before = jax.tree_util.tree_map(np.asarray, cache.state.recurrent)
    assert np.abs(before["ssm"][:, 2]).max() > 0
    handle = cache.dispatch_window(
        params, np.asarray([1, 0, 0, 0], np.int32), 4,
        active=[True, False, False, False])
    cache.harvest_window(handle)
    after = jax.tree_util.tree_map(np.asarray, cache.state.recurrent)
    for leaf in ("ssm", "conv"):
        # slot 0 decoded; slot 2 (admitted, not active) and the empty
        # slots 1 and 3 did not move
        assert not np.array_equal(before[leaf][:, 0], after[leaf][:, 0])
        for dead in (1, 2, 3):
            np.testing.assert_array_equal(before[leaf][:, dead],
                                          after[leaf][:, dead])


# ---- (e) chunks ----------------------------------------------------------


@pytest.mark.parametrize("chunk", [16, 64])
def test_prefill_in_chunks_equals_prefill_in_one_piece(cfg, params, chunk):
    prompt = prompt_of(9, 128)
    whole, one = teacher_forced(cfg, params, prompt, len(prompt), chunk=128)
    pieces, many = teacher_forced(cfg, params, prompt, len(prompt),
                                  chunk=chunk)
    # float32, sums in another order: the state after 128 positions and
    # the last position's logits agree to rounding
    np.testing.assert_allclose(pieces, whole, atol=2e-5)
    for leaf in ("ssm", "conv"):
        np.testing.assert_allclose(
            np.asarray(many.state.recurrent[leaf][:, 1]),
            np.asarray(one.state.recurrent[leaf][:, 1]), atol=2e-5)


# ---- (f) what refuses to start, each by name ----------------------------


@pytest.mark.parametrize("change, named", [
    ({"payload": {"serving_prefix_cache": True}}, "serving_prefix_cache"),
    ({"payload": {"serving_speculative": 2}}, "serving_speculative"),
    ({"payload": {"kind": "train", "corpus": "/tmp/x"}}, "kind = 'train'"),
    ({"payload": {"serving": "contiguous"}}, "serving = \"paged\""),
])
def test_the_runtime_config_refuses_what_cannot_run_the_block(change, named):
    with pytest.raises(RuntimeConfigError) as refused:
        RuntimeConfig.from_mapping(document(**change))
    assert named in str(refused.value)
    assert "layer_pattern" in str(refused.value)


def test_a_mesh_of_several_devices_refuses_the_block():
    from kvedge_tpu.runtime.workload import (
        MeshConfigError, derive_model_config,
    )

    # "data": 0 takes every device there is: the tests' eight
    cfg = RuntimeConfig.from_mapping(
        document(mesh={"axes": {"data": 0}}))
    with pytest.raises(MeshConfigError, match="layer_pattern"):
        derive_model_config(cfg, seq=SEQ)


@pytest.mark.parametrize("kw, named", [
    ({"prefix_cache": True}, "serving_prefix_cache"),
    ({"prefix_cache": False, "speculative": 2}, "serving_speculative"),
])
def test_the_server_refuses_prefix_cache_and_speculation(cfg, params, kw,
                                                         named):
    with pytest.raises(ValueError, match=named):
        PagedGenerationServer(params, cfg, slots=2, pages=16, page_size=16,
                              **kw)


def test_the_other_paths_refuse_the_block_by_the_key_s_name(cfg, params):
    from kvedge_tpu.models import decode, transformer

    with pytest.raises(ValueError, match="layer_pattern"):
        decode.init_cache(cfg, 1)
    with pytest.raises(ValueError, match="layer_pattern"):
        transformer.init_params(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="layer_pattern"):
        transformer.forward_hidden(params, jnp.zeros((1, 8), jnp.int32),
                                   cfg)
    # the pattern's own keys mean nothing without it
    with pytest.raises(ValueError, match="set layer_pattern"):
        dataclasses.replace(cfg, layer_pattern=(), n_experts=0).validate()


# ---- (g) preemption carries the row's state with its pages --------------


def test_a_preempted_row_resumes_on_its_own_state(cfg, params):
    """A batch-class row is swapped out for an interactive one and back:
    its pages and its recurrent state travel together, verbatim, and its
    tokens are an uninterrupted run's."""
    long_prompt, n_new = prompt_of(11, 40), 48
    alone = server_of(params, cfg, slots=1)
    try:
        want = alone.submit(long_prompt, n_new)
    finally:
        alone.close()
    server = server_of(params, cfg, slots=1, window=2,
                       sched_swap_budget_mb=64)
    try:
        victim = server.submit_stream(long_prompt, n_new, priority="batch")
        first = next(victim)
        # slots = 1: the interactive request parks, the loop swaps the
        # batch row out at the next boundary, and this returns while the
        # victim waits in host memory
        server.submit(prompt_of(12, 32), 8)
        got = long_prompt + [first] + list(victim)
        stats = server.stats()
    finally:
        server.close()
    assert stats["sched_preemptions_total"] >= 1
    assert stats["sched_resumes_total"] >= 1
    assert got == want


def test_a_journaled_row_revives_on_its_own_state(cfg, params):
    """The pool is poisoned mid-request and revived: the journal's
    checkpoint holds the row's recurrent state beside its pages, as it
    stood at the boundary the checkpoint was taken at, and the revived
    row's tokens are an uninterrupted run's."""
    import threading
    import time

    prompt, n_new = prompt_of(21, 40), 40
    alone = server_of(params, cfg, slots=1)
    try:
        want = alone.submit(prompt, n_new)
    finally:
        alone.close()
    server = server_of(params, cfg, slots=2, window=2, checkpoint_every=1,
                       journal_budget_mb=64)
    dying = server._thread
    try:
        stream = server.submit_stream(prompt, n_new)
        first = next(stream)
        tail: list = []
        reader = threading.Thread(target=lambda: tail.extend(stream),
                                  daemon=True)
        real = server._cache.harvest_window
        armed = {"on": True, "harvests": 0}

        def dying_harvest(handle):
            # once the row holds a checkpoint some windows into its life
            armed["harvests"] += 1
            if (armed["on"] and len(server._journal)
                    and armed["harvests"] > 5):
                armed["on"] = False
                raise RuntimeError("injected: the pool died")
            return real(handle)

        server._cache.harvest_window = dying_harvest
        reader.start()
        deadline = time.monotonic() + 60
        while server.degraded is None:
            assert time.monotonic() < deadline, "pool never poisoned"
            time.sleep(0.01)
        dying.join(timeout=30)
        # the revived pool starts from fresh state: what the row resumes
        # on is what the journal kept
        assert server.revive() == 1
        reader.join(timeout=120)
        stats = server.stats()
    finally:
        server.close()
    assert stats["journal_restores_total"] == 1
    assert prompt + [first] + tail == want


def test_a_swap_snapshot_without_the_state_is_refused(cfg, params):
    cache = kvcache.PagedKVCache(cfg, slots=2, pages=16, page_size=16)
    cache.admit(0, 16)
    cache.prefill(params, 0, jnp.asarray(prompt_of(13, 16), jnp.int32))
    pages = cache.swapout_pages(cache.slot_pages(0))
    state = cache.swapout_row(0)
    assert len(state) == 2 and cache.row_state_bytes() == sum(
        a.nbytes for a in state)
    cache.release(0)
    cache.admit(1, 16)
    with pytest.raises(kvcache.PagedCacheError, match="zero state"):
        cache.swapin_slot(1, pages)
    cache.swapin_slot(1, pages + state)
    for leaf, want in zip(("ssm", "conv"), state):
        np.testing.assert_array_equal(
            np.asarray(cache.state.recurrent[leaf][:, 1]), want)


# ---- (h) the pick counters ----------------------------------------------


def test_the_pick_counters_are_the_router_s_own_picks(cfg, params, weights):
    """``stats()`` counts the decode windows' picks; counted again here
    on the host from the reference's router logits over the same
    tokens (the prompt's last position is prefill's and is not in it)."""
    prompt, n_new = prompt_of(15, 32), 17
    server = server_of(params, cfg)
    try:
        served = server.submit(prompt, n_new)
        stats = server.stats()
    finally:
        server.close()
    sequence = served
    picks: list = []
    REFERENCE.logits(MODEL, weights, [sequence], [0], picks=picks)
    decoded = slice(len(prompt), len(sequence) - 1)  # fed to a decode step
    by_expert = np.zeros(MODEL["experts"], np.int64)
    for layer_picks in picks:
        np.add.at(by_expert, layer_picks[0][decoded].ravel(), 1)
    steps = n_new - 1
    assert stats["expert_picks_total"] == (
        steps * MODEL["n_layers"] * MODEL["expert_top_k"])
    assert stats["expert_picks_total"] == by_expert.sum()
    held = by_expert[:MODEL["experts_held"]]
    assert stats["expert_picks_by_expert"] == held.tolist()
    assert stats["expert_picks_held_total"] == held.sum()
    assert stats["state_rows"] == 4
    # float32 here: 6 mamba layers, 4 slots, [32, 16] of state and
    # a conv tail of 3 x 64
    assert stats["state_gb"] == pytest.approx(
        6 * 4 * (32 * 16 + 3 * 64) * 4 / 1e9)


# ---- the weights, leaf by leaf -------------------------------------------


def test_the_initialiser_draws_what_the_reference_draws(cfg, params):
    """One recipe, stated in hybrid.py and copied by the reference: the
    program's tree, leaf by leaf, is the reference's layer by layer."""
    names = {"w_out": {"mamba": "m_out", "attention": "a_out"}}
    seen = {"mamba": 0, "attention": 0}
    pattern = cfg.layer_pattern
    for layer in range(cfg.n_layers):
        want = REFERENCE.layer_weights(MODEL, layer)
        period, j = divmod(layer, len(pattern))
        kind = pattern[j]
        assert want["kind"] == kind
        index = seen[kind] % pattern.count(kind)
        seen[kind] += 1
        for leaf, got in params[kind].items():
            if leaf in ("ln", "norm"):
                assert np.all(np.asarray(got) == 1.0)
                continue
            name = names.get(leaf, {}).get(kind, leaf)
            np.testing.assert_array_equal(np.asarray(got[period, index]),
                                          np.asarray(want[name]), leaf)
        for leaf, got in params["ffn"].items():
            if leaf != "ln":
                np.testing.assert_array_equal(
                    np.asarray(got[period, j]), np.asarray(want[leaf]), leaf)
    np.testing.assert_array_equal(np.asarray(params["embedding"]),
                                  np.asarray(REFERENCE.embedding(MODEL)))


def test_the_served_tree_is_drawn_in_the_serving_dtype(cfg):
    """No float32 tree stands on the device: each matrix leaf comes out
    of its own jitted call in the compute dtype; the router and the
    SSM's A_log, dt_bias and D stay float32, and serving_params has
    nothing left to cast."""
    from kvedge_tpu.models.transformer import serving_params

    served = dataclasses.replace(cfg, dtype="bfloat16")
    tree = hybrid.init_params(jax.random.PRNGKey(0), served)
    float32 = {"router", "A_log", "dt_bias", "D", "ln", "norm"}
    for kind in ("mamba", "attention", "ffn"):
        for leaf, array in tree[kind].items():
            assert array.dtype == (jnp.float32 if leaf in float32
                                   else jnp.bfloat16), (kind, leaf)
    assert tree["embedding"].dtype == jnp.bfloat16
    again = serving_params(tree, served)
    for a, b in zip(jax.tree_util.tree_leaves(tree),
                    jax.tree_util.tree_leaves(again)):
        assert a.dtype == b.dtype
    # bf16 state beside it: float32 SSM state, the conv's tail as computed
    recurrent = hybrid.fresh_recurrent(served, 3)
    assert recurrent["ssm"].dtype == jnp.float32
    assert recurrent["ssm"].shape == (6, 3, 32, 16)
    assert recurrent["conv"].dtype == jnp.bfloat16
    assert recurrent["conv"].shape == (6, 3, 3 * (32 + 2 * 16))


def test_the_model_section_round_trips_through_toml():
    cfg = RuntimeConfig.from_mapping(document())
    again = RuntimeConfig.parse(cfg.to_toml())
    assert again.model == cfg.model
    assert again.model.layer_pattern == ("mamba", "mamba", "attention",
                                         "mamba")
    # the plain block's document has none of the new keys
    plain = RuntimeConfig.from_mapping({
        "payload": {"kind": "serve", "serving": "paged"}}).to_toml()
    assert "layer_pattern" not in plain and "ssm_" not in plain
