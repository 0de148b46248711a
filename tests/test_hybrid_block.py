"""The patterned block (models/hybrid.py, models/ssm.py, models/delta.py,
moe.held_experts_ffn) on the paged serving path, held to the benchmark's
plain references (benchmark/references/granite_moe_hybrid.py and
solar_open2.py), never to decode.generate: the block exists once.

Two presets, one for each recurrent layer kind, at sizes the CPU runs in
seconds, and every test that says the same of both runs on both:

* ``mamba``: pattern m m a m over two periods, 8 routed experts of which
  4 are held, 3 a token, a shared expert, 4 SSM heads of 8 with state
  16, all four multipliers other than 1, no rotary, a tied head;
* ``delta``: pattern a d d d over two periods, 16 routed experts of
  which 2 are held (one of eight shares), 3 a token, a shared expert, 4
  delta-rule heads of 8 key and 8 value channels with gates of rank 8,
  4 attention heads of 16 over a hidden size of 32 (so the heads are not
  the hidden size divided up), an output gate on the attention layer, a
  head of its own.

The program computes in float32 here, so that what separates it from the
float32 reference is the order of its sums and nothing else.
"""

import dataclasses
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import cellspec
from kvedge_tpu.config.runtime_config import RuntimeConfig, RuntimeConfigError
from kvedge_tpu.models import hybrid, kvcache, moe
from kvedge_tpu.models.serving import PagedGenerationServer
from kvedge_tpu.models.transformer import TransformerConfig


def _reference(stem: str):
    return cellspec.load_module(
        stem + "_for_tests",
        os.path.join(cellspec.REPO, "benchmark", "references", stem + ".py"))


# The presets under the published key names, as a configuration's file
# holds them: the reference's model_of makes the program's [model] of it.
_MAMBA = {
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
_DELTA = {
    "model_type": "solar_open2", "partial_rotary_factor": 1,
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 8,
                           "num_heads": 4, "num_kv_heads": None},
    "hidden_size": 32, "num_hidden_layers": 8, "num_attention_heads": 4,
    "head_dim": 16, "num_key_value_heads": 2, "vocab_size": 128,
    "intermediate_size": 64, "moe_intermediate_size": 16,
    "rms_norm_eps": 1e-5, "rope_theta": 10000, "tie_word_embeddings": False,
    "max_position_embeddings": 4096, "first_k_dense_replace": 0,
    "use_rope": False, "gqa_interval": 3, "gqa_layers": [0, 4],
    "use_gqa_gate": True, "kda_use_full_proj": False,
    "kda_allow_neg_eigval": True, "n_routed_experts": 2,
    "published": {"n_routed_experts": 16}, "n_shared_experts": 1,
    "norm_topk_prob": True, "routed_scaling_factor": 1,
    "num_experts_per_tok": 3,
}


def _block(kind: str, published: dict, stem: str, **said) -> types.SimpleNamespace:
    reference = _reference(stem)
    return types.SimpleNamespace(
        kind=kind, reference=reference, published=published,
        model=reference.model_of(published), **said)


# What the tests below need to be told of a preset: the layers' leaves
# under the reference's names, the leaves the equations read in float32,
# one slot's state and conv tail, and the tolerance of the comparison
# that is this file's first test, with the readings it was set from.
BLOCKS = {
    "mamba": _block(
        "mamba", _MAMBA, "granite_moe_hybrid",
        pattern=("mamba", "mamba", "attention", "mamba"),
        names={("mamba", "w_out"): "m_out", ("attention", "w_out"): "a_out"},
        float32={"router", "A_log", "dt_bias", "D", "ln", "norm"},
        state=(32, 16), tail=3 * (32 + 2 * 16), state_tolerance=2e-5,
        # Both sides are float32 and differ in the order of their sums
        # alone (a product over all held experts for a loop over them,
        # the chunk form of the SSM for the literal recurrence, a cache
        # for none). Read here, on logits of size 0.07: 1.6e-7 between
        # them; 1.9e-3 with the recurrent state kept in bf16 (each of
        # the 63 positions' states rounded to 8 bits); 1.9e-2 from the
        # reference's int8 control. 2e-5 leaves the program a hundred
        # times its reading and fails both of the others ninety times
        # over.
        tolerance=2e-5),
    "delta": _block(
        "delta", _DELTA, "solar_open2",
        pattern=("attention", "delta", "delta", "delta"),
        names={("delta", "w_qkv"): "d_qkv", ("delta", "w_out"): "d_out",
               ("attention", "w_out"): "a_out"},
        float32={"router", "A_log", "dt_bias", "ln", "norm"},
        state=(4, 8, 8), tail=3 * 3 * 32,
        # A row's state after 128 positions, prefilled in pieces of two
        # lengths: the chunk form alone is within 1e-6 of the one-token
        # recurrence (tests/test_delta_block.py), but eight layers of
        # L2-normalised 8-channel keys carry a rounding on: 6.5e-5 read
        # between the two on states of size 2; the logits still agree
        # to 2e-5.
        state_tolerance=2e-4,
        # The same comparison (the chunk form's triangular solve and
        # the decode step's two reads for the literal recurrence), on
        # logits of size 0.4 (a head of its own at 0.02): 7.0e-6
        # between them; 3.7e-2 with the recurrent state kept in bf16;
        # 2.6e-1 from the reference's int8 control. 1e-4 leaves the
        # program fourteen times its reading and fails the others 370
        # and 2,600 times over.
        tolerance=1e-4),
}
SEQ = 256


@pytest.fixture(scope="module", params=list(BLOCKS))
def block(request):
    return BLOCKS[request.param]


def document(payload: dict | None = None, model: dict | None = None,
             mesh: dict | None = None, block=BLOCKS["mamba"], **more) -> dict:
    return {
        "runtime": {"name": "hybrid-test", "state_dir": "/tmp/unused"},
        "tpu": {"platform": "cpu", "expected_chips": 1},
        "mesh": mesh or {"axes": {"data": 1}},
        "model": {**block.model, **(model or {})},
        "payload": {"kind": "serve", "serving": "paged", "seq": SEQ,
                    "serving_prefix_cache": False, **(payload or {})},
        **more,
    }


def config_of(model: dict | None = None,
              block=BLOCKS["mamba"]) -> TransformerConfig:
    """The program's config through the product's own path ([model] ->
    ModelSpec -> derive_model_config), in float32."""
    from kvedge_tpu.runtime.workload import derive_model_config

    cfg = RuntimeConfig.from_mapping(document(model=model, block=block))
    one = jax.devices()[:1]  # of the tests' eight virtual devices
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax, "devices", lambda *a, **k: one)
        tcfg, _ = derive_model_config(cfg, seq=SEQ)
    return dataclasses.replace(tcfg, dtype="float32")


@pytest.fixture(scope="module")
def cfg(block):
    return config_of(block=block)


@pytest.fixture(scope="module")
def params(cfg):
    return hybrid.init_params(jax.random.PRNGKey(0), cfg)


@pytest.fixture(scope="module")
def weights(block):
    return block.reference.make_weights(block.model)


def prompt_of(seed: int, n: int) -> list:
    return [int(t) for t in
            np.random.default_rng(seed).integers(0, _MAMBA["vocab_size"], n)]


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


def test_served_tokens_and_logits_are_the_reference_s(block, cfg, params,
                                                      weights):
    tolerance = block.tolerance
    prompt, n_new = prompt_of(1, 40), 24
    server = server_of(params, cfg)
    try:
        served = server.submit(prompt, n_new)
    finally:
        server.close()
    sequence, generated = served, served[len(prompt):]
    assert sequence[:len(prompt)] == prompt and len(generated) == n_new
    (want,) = block.reference.logits(block.model, weights, [sequence],
                                     [len(prompt) - 1])
    # every served token is the reference's choice, or ties with it
    gaps = want[:n_new].max(axis=-1) - want[np.arange(n_new), generated]
    assert gaps.max() <= tolerance
    # and the logits the cache's programs give at those positions (prefill
    # in chunks of 16, then step by step over the served tokens) are the
    # reference's
    got, _ = teacher_forced(cfg, params, sequence[:-1], len(prompt))
    np.testing.assert_allclose(got, want[:n_new], rtol=0, atol=tolerance)

    # the tolerance tells the precisions apart: a bf16 recurrent state ...
    def in_bf16(recurrent):
        return {**recurrent, "ssm": recurrent["ssm"].astype(jnp.bfloat16)}

    rough, cache = teacher_forced(cfg, params, sequence[:-1], len(prompt),
                                  recurrent=in_bf16)
    assert cache.state.recurrent["ssm"].dtype == jnp.bfloat16
    assert np.abs(rough - want[:n_new]).max() > 10 * tolerance
    # ... and the reference's own int8 control
    (control,) = block.reference.logits(block.model, weights, [sequence],
                                        [len(prompt) - 1], quant="int8")
    assert np.abs(control[:n_new] - want[:n_new]).max() > 10 * tolerance


def test_rows_admitted_at_different_steps_read_the_reference_s_logits(
        block, cfg, params, weights):
    """Three sequences through two slots of one cache, teacher-forced:
    A is prefilled in chunks of 16 and decodes alone, B is prefilled in
    chunks of 8 into the other slot while A is ten steps in and decodes
    beside it, A finishes and C takes its slot (reset, not inherited)
    while B goes on. Every row of logits the cache's programs gave, at
    whatever step and beside whatever neighbour, is the reference's full
    forward pass over that sequence at that position."""
    lengths = {"A": (24, 40), "B": (40, 64), "C": (16, 30)}
    tokens = {name: prompt_of(30 + i, total)
              for i, (name, (_, total)) in enumerate(lengths.items())}
    cache = kvcache.PagedKVCache(cfg, slots=2, pages=32, page_size=16)
    got = {name: [] for name in lengths}
    slot_of, at = {}, {}

    def admit(name, slot, chunk):
        n_prompt = lengths[name][0]
        cache.admit(slot, n_prompt)
        for lo in range(0, n_prompt, chunk):
            out = cache.prefill_chunk(
                params, slot, jnp.asarray(
                    tokens[name][lo:min(n_prompt, lo + chunk)], jnp.int32),
                lo)
        got[name].append(np.asarray(out))
        slot_of[slot], at[name] = name, n_prompt

    def step():
        fed, active = [0, 0], [False, False]
        for slot, name in slot_of.items():
            fed[slot], active[slot] = tokens[name][at[name]], True
        logits = cache.step(params, jnp.asarray(fed, jnp.int32),
                            active=active)
        for slot, name in list(slot_of.items()):
            got[name].append(np.asarray(logits[slot]))
            at[name] += 1
            if at[name] == lengths[name][1]:
                cache.release(slot)
                del slot_of[slot]

    admit("A", 0, 16)
    for _ in range(10):
        step()
    admit("B", 1, 8)
    while "A" in slot_of.values():
        step()
    admit("C", 0, 16)
    while slot_of:
        step()
    names = list(lengths)
    want = block.reference.logits(
        block.model, weights, [tokens[n] for n in names],
        [lengths[n][0] - 1 for n in names])
    for name, rows in zip(names, want):
        assert len(got[name]) == len(rows)
        np.testing.assert_allclose(np.stack(got[name]), rows, rtol=0,
                                   atol=block.tolerance, err_msg=name)


# ---- (b) the share -------------------------------------------------------


def test_the_shares_and_the_shared_expert_add_up_to_the_whole_layer(block):
    """The parts of the routed sum that the chips of the deployment
    give (two halves of 8 experts; eight shares of 2 of 16), with the
    shared expert counted once, are the uncut reference's layer."""
    reference, model = block.reference, block.model
    total_experts, held = model["experts"], model["experts_held"]
    top_k = model["expert_top_k"]
    with jax.default_matmul_precision("highest"):
        h = jax.random.normal(jax.random.PRNGKey(3), (24, model["d_model"]))
        whole = reference.layer_weights(model, 1, held=(0, total_experts))
        want, _ = reference.feed_forward(h, whole, top_k=top_k)
        total = reference._gated(h, whole["shared_in"], whole["shared_out"],
                                 "")
        picks = np.zeros(2, np.int64)
        for first in range(0, total_experts, held):
            share = reference.layer_weights(model, 1, held=(first, held))
            part, counted = moe.held_experts_ffn(
                h, share["router"], share["experts_in"],
                share["experts_out"], top_k=top_k, first=first, gated=True,
                renormalize=True)
            total = total + part
            picks += np.asarray(counted[:2])
            # the reference, given the same share, gives the same part
            ref_part, _ = reference.routed(h, share, top_k=top_k)
            np.testing.assert_allclose(part, ref_part, atol=1e-5)
    np.testing.assert_allclose(total, want, atol=1e-5)
    # every pick falls on one chip or another
    shares = total_experts // held
    assert picks[1] == 24 * top_k and picks[0] == shares * 24 * top_k


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


def test_a_newcomer_joins_a_running_pipeline_on_its_own_state(cfg, params):
    """An admission does not drain the decode pipeline (ISSUE 37): the
    newcomer's state is zeroed and prefilled by programs queued behind
    the window in flight, in which its row is dead and stands still,
    and it enters the next overlapped window from the host's row. Its
    tokens, and those of the request it joined, are what each gets
    alone."""
    import time

    long, late = (prompt_of(11, 24), 150), (prompt_of(12, 40), 30)
    server = server_of(params, cfg)
    try:
        alone = [server.submit(*long), server.submit(*late)]
        harvest = server._cache.harvest_window

        def slow(handle):  # a window is in flight while `late` admits
            time.sleep(0.02)
            return harvest(handle)

        server._cache.harvest_window = slow
        stream = server.submit_stream(*long)
        first = next(stream)
        joined = server.submit(*late)
        together = long[0] + [first] + list(stream)
        stats = server.stats()
    finally:
        server.close()
    assert stats["pipeline_joins_total"] == 1
    assert not any(stats["pipeline_collapses"].values())
    assert [together, joined] == alone


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
def test_prefill_in_chunks_equals_prefill_in_one_piece(block, cfg, params,
                                                       chunk):
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
            np.asarray(one.state.recurrent[leaf][:, 1]),
            atol=block.state_tolerance)


# ---- (f) what refuses to start, each by name ----------------------------


@pytest.mark.parametrize("change, named, why", [
    ({"payload": {"serving_prefix_cache": True}}, "serving_prefix_cache",
     "layer_pattern"),
    # refused for every block since PR 48, this one included
    ({"payload": {"serving_speculative": 2}}, "serving_speculative",
     "does not speculate"),
    ({"payload": {"kind": "train", "corpus": "/tmp/x"}}, "kind = 'train'",
     "layer_pattern"),
    ({"payload": {"serving": "contiguous"}}, "serving = \"paged\"",
     "layer_pattern"),
])
def test_the_runtime_config_refuses_what_cannot_run_the_block(block, change,
                                                              named, why):
    with pytest.raises(RuntimeConfigError) as refused:
        RuntimeConfig.from_mapping(document(block=block, **change))
    assert named in str(refused.value)
    assert why in str(refused.value)


def test_a_mesh_of_several_devices_refuses_the_block(block):
    from kvedge_tpu.runtime.workload import (
        MeshConfigError, derive_model_config,
    )

    # "data": 0 takes every device there is: the tests' eight
    cfg = RuntimeConfig.from_mapping(
        document(block=block, mesh={"axes": {"data": 0}}))
    with pytest.raises(MeshConfigError, match="layer_pattern"):
        derive_model_config(cfg, seq=SEQ)


def test_the_server_refuses_the_prefix_cache(cfg, params):
    with pytest.raises(ValueError, match="serving_prefix_cache"):
        PagedGenerationServer(params, cfg, slots=2, pages=16, page_size=16,
                              prefix_cache=True)


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


@pytest.mark.parametrize("change, named", [
    ({"layer_pattern": ("attention", "mamba", "delta", "delta")},
     "one recurrent kind"),
    ({"layer_pattern": ("attention", "window")}, "'window'"),
    ({"ssm_gate_rank": 0}, "ssm_gate_rank"),
    ({"ssm_heads": 0}, "delta layers: ssm_heads"),
])
def test_a_pattern_the_block_cannot_run_is_refused_by_name(change, named):
    cfg = config_of(block=BLOCKS["delta"])
    with pytest.raises(ValueError, match=named):
        dataclasses.replace(cfg, **change).validate()


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
    # bit for bit what was taken out, in the other slot
    for leaf, want in zip(("ssm", "conv"), state):
        np.testing.assert_array_equal(
            np.asarray(cache.state.recurrent[leaf][:, 1]), want)


# ---- (h) the pick counters ----------------------------------------------


def test_the_pick_counters_are_the_router_s_own_picks(block, cfg, params,
                                                      weights):
    """``stats()`` counts the decode windows' picks; counted again here
    on the host from the reference's router logits over the same
    tokens (the prompt's last position is prefill's and is not in it):
    every pick, those on a held expert, each held expert's, and
    ``expert_touched_total``, the (layer, held expert, step) triples in
    which the expert got one or more."""
    model = block.model
    prompt, n_new = prompt_of(15, 32), 17
    server = server_of(params, cfg)
    try:
        served = server.submit(prompt, n_new)
        stats = server.stats()
    finally:
        server.close()
    sequence = served
    picks: list = []
    block.reference.logits(model, weights, [sequence], [0], picks=picks)
    decoded = slice(len(prompt), len(sequence) - 1)  # fed to a decode step
    by_expert = np.zeros(model["experts"], np.int64)
    first, n_held = model["expert_first"], model["experts_held"]
    touched = 0
    for layer_picks in picks:
        np.add.at(by_expert, layer_picks[0][decoded].ravel(), 1)
        # one live row: a step touches the held experts among its picks
        touched += int(((layer_picks[0][decoded] >= first)
                        & (layer_picks[0][decoded] < first + n_held)).sum())
    steps = n_new - 1
    assert stats["expert_picks_total"] == (
        steps * model["n_layers"] * model["expert_top_k"])
    assert stats["expert_picks_total"] == by_expert.sum()
    held = by_expert[first:first + n_held]
    assert stats["expert_picks_by_expert"] == held.tolist()
    assert stats["expert_picks_held_total"] == held.sum()
    assert stats["expert_touched_total"] == touched
    assert stats["expert_reads_per_step"] == model["n_layers"] * n_held
    assert 0 < touched <= steps * stats["expert_reads_per_step"]
    assert stats["state_rows"] == 4
    # float32 here: 6 recurrent layers, 4 slots, a slot's state and a
    # conv tail of 3 positions
    assert stats["state_gb"] == pytest.approx(
        6 * 4 * (np.prod(block.state) + block.tail) * 4 / 1e9)


def test_two_rows_touch_an_expert_once_a_step():
    """``expert_touched_total`` by hand where rows share an expert: 6
    tokens over 4 of 8 experts held from the third on, 2 a token; a dead
    row's picks touch nothing."""
    d, e, f = 8, 8, 4
    key = jax.random.PRNGKey(4)
    x = jax.random.normal(key, (6, d))
    router = jax.random.normal(jax.random.fold_in(key, 1), (d, e))
    w_in = jax.random.normal(jax.random.fold_in(key, 2), (4, d, 2 * f))
    w_out = jax.random.normal(jax.random.fold_in(key, 3), (4, f, d))
    live = np.asarray([True, True, False, True, True, False])
    _, idx, _ = moe._route(x, router, 2)
    idx = np.asarray(idx)
    _, picks = moe.held_experts_ffn(x, router, w_in, w_out, top_k=2, first=2,
                                    gated=True, renormalize=True,
                                    live=jnp.asarray(live))
    picks = np.asarray(picks)
    want = [int((idx[live] == expert).sum()) for expert in range(2, 6)]
    assert picks.shape == (3 + 4,)
    assert picks[0] == 4 * 2 and picks[1] == sum(want)
    assert picks[2:-1].tolist() == want
    assert picks[-1] == sum(n > 0 for n in want) <= 4
    _, every = moe.held_experts_ffn(x, router, w_in, w_out, top_k=2, first=2,
                                    gated=True, renormalize=True)
    assert np.asarray(every)[-1] >= picks[-1]


def test_the_delta_block_on_the_walk_serves_what_the_one_product_serves(
        monkeypatch):
    """The delta block at widths the walk over the touched experts
    tiles (a hidden size and experts of 128, 16 slots), three requests
    decoding together beside thirteen dead rows: put onto the walk
    (ops/expert_walk.py in the interpreter; ``moe.walks_touched`` asks
    for a TPU, so the test answers in its place), prefill chunks and
    windows serve the tokens the one product serves, the reference's
    choice at every position, and count the same picks. ``stats()``'s
    ``expert_reads_total`` is what each program read: the touched
    matrices on the walk, every held one a step on the one product.
    ``/metrics`` renders it."""
    from concurrent.futures import ThreadPoolExecutor

    from kvedge_tpu.ops import expert_walk
    from kvedge_tpu.runtime.status import render_metrics
    from tests.test_tracing import check_prometheus_text

    block = BLOCKS["delta"]
    published = _DELTA | {"hidden_size": 128, "moe_intermediate_size": 128,
                          "n_routed_experts": 4}
    model = block.reference.model_of(published)
    cfg = config_of(model=model, block=block)
    assert (cfg.d_model, cfg.d_ff, cfg.held_experts, cfg.n_experts) \
        == (128, 128, 4, 16)
    params = hybrid.init_params(jax.random.PRNGKey(0), cfg)
    prompts = [prompt_of(seed, n) for seed, n in ((3, 40), (4, 23), (5, 32))]
    n_new = 12

    def serve():
        jax.clear_caches()  # a program's trace is cached by its shapes
        server = server_of(params, cfg, slots=16)
        try:
            with ThreadPoolExecutor(3) as pool:
                served = list(pool.map(
                    lambda prompt: server.submit(prompt, n_new), prompts))
            return served, server.stats()
        finally:
            server.close()

    try:
        one, one_stats = serve()
        assert not hybrid.walks_touched(cfg, 16)
        # wherever the kernel tiles: the windows' 16 rows and a chunk
        # of 16, and not the prompts' tails of 8 and 7
        monkeypatch.setattr(
            moe, "walks_touched",
            lambda n, top_k, experts, held, d, f: expert_walk.tiles(n, d, f))
        assert hybrid.walks_touched(cfg, 16)
        assert not hybrid.walks_touched(cfg, 8)
        walk, walk_stats = serve()
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert walk == one
    weights = block.reference.make_weights(model)
    wants = block.reference.logits(model, weights, walk,
                                   [len(p) - 1 for p in prompts])
    for served, prompt, want in zip(walk, prompts, wants):
        generated = served[len(prompt):]
        gaps = want[:n_new].max(axis=-1) - want[np.arange(n_new), generated]
        assert gaps.max() <= block.tolerance
    # (how many steps the three rows shared, and so how often two of
    # them touched one expert in one step, is the threads' timing)
    for key in ("expert_picks_total", "expert_picks_held_total",
                "expert_picks_by_expert", "expert_reads_per_step"):
        assert walk_stats[key] == one_stats[key], key
    per_step = one_stats["expert_reads_per_step"]
    assert per_step == 8 * 4
    assert one_stats["expert_reads_total"] \
        == one_stats["decode_steps_total"] * per_step
    assert walk_stats["expert_reads_total"] \
        == walk_stats["expert_touched_total"]
    assert 0 < walk_stats["expert_reads_total"] \
        < walk_stats["decode_steps_total"] * per_step
    text = render_metrics({"ok": True, "boot_count": 1, "uptime_s": 2.5,
                           "heartbeat_seq": 3, "heartbeat_age_s": 0.1,
                           "serving": walk_stats})
    assert check_prometheus_text(text)[
        "kvedge_serve_expert_reads_total"] == "counter"
    assert (f"kvedge_serve_expert_reads_total "
            f"{walk_stats['expert_reads_total']}\n") in text


# ---- the weights, leaf by leaf -------------------------------------------


def test_the_initialiser_draws_what_the_reference_draws(block, cfg, params):
    """One recipe, stated in hybrid.py and copied by the reference: the
    program's tree, leaf by leaf, is the reference's layer by layer."""
    reference, model = block.reference, block.model
    seen = dict.fromkeys(cfg.layer_pattern, 0)
    pattern = cfg.layer_pattern
    assert pattern == block.pattern
    for layer in range(cfg.n_layers):
        want = reference.layer_weights(model, layer)
        period, j = divmod(layer, len(pattern))
        kind = pattern[j]
        assert want["kind"] == kind
        index = seen[kind] % pattern.count(kind)
        seen[kind] += 1
        for leaf, got in params[kind].items():
            if leaf in ("ln", "norm", "D"):
                assert np.all(np.asarray(got) == 1.0)
                continue
            name = block.names.get((kind, leaf), leaf)
            np.testing.assert_array_equal(np.asarray(got[period, index]),
                                          np.asarray(want[name]), leaf)
        for leaf, got in params["ffn"].items():
            if leaf != "ln":
                np.testing.assert_array_equal(
                    np.asarray(got[period, j]), np.asarray(want[leaf]), leaf)
    tables = reference.make_weights(model)
    assert set(tables) == {"embedding", "head"} & set(params)
    for name, table in tables.items():
        np.testing.assert_array_equal(np.asarray(params[name]),
                                      np.asarray(table))


def test_the_served_tree_is_drawn_in_the_serving_dtype(block, cfg):
    """No float32 tree stands on the device: each matrix leaf comes out
    of its own jitted call in the compute dtype; the router, the
    recurrent layers' A_log, dt_bias and D and the gains stay float32,
    and serving_params has nothing left to cast."""
    from kvedge_tpu.models.transformer import serving_params

    served = dataclasses.replace(cfg, dtype="bfloat16")
    tree = hybrid.init_params(jax.random.PRNGKey(0), served)
    assert set(tree) - {"embedding", "head", "ln_final"} == {
        block.kind, "attention", "ffn"}
    for kind in (block.kind, "attention", "ffn"):
        for leaf, array in tree[kind].items():
            assert array.dtype == (jnp.float32 if leaf in block.float32
                                   else jnp.bfloat16), (kind, leaf)
    assert tree["embedding"].dtype == jnp.bfloat16
    assert ("head" in tree) == served.untied_head
    assert ("w_gate" in tree["attention"]) == served.attention_gate
    again = serving_params(tree, served)
    for a, b in zip(jax.tree_util.tree_leaves(tree),
                    jax.tree_util.tree_leaves(again)):
        assert a.dtype == b.dtype
    # bf16 state beside it: float32 state, the conv's tail as computed
    recurrent = hybrid.fresh_recurrent(served, 3)
    assert recurrent["ssm"].dtype == jnp.float32
    assert recurrent["ssm"].shape == (6, 3, *block.state)
    assert recurrent["conv"].dtype == jnp.bfloat16
    assert recurrent["conv"].shape == (6, 3, block.tail)
    assert recurrent["picks"].shape == (3 + served.held_experts,)


def test_the_model_section_round_trips_through_toml(block):
    cfg = RuntimeConfig.from_mapping(document(block=block))
    text = cfg.to_toml()
    again = RuntimeConfig.parse(text)
    assert again.model == cfg.model
    assert again.model.layer_pattern == block.pattern
    # a block without the later keys keeps the document it had
    for key in ("ssm_gate_rank", "head_dim", "attention_gate",
                "untied_head"):
        assert (f"\n{key} = " in text) == (block.kind == "delta"), key
    # the plain block's document has none of the new keys
    plain = RuntimeConfig.from_mapping({
        "payload": {"kind": "serve", "serving": "paged"}}).to_toml()
    assert "layer_pattern" not in plain and "ssm_" not in plain
