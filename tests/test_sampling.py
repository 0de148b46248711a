"""Sampling (temperature / top-p) for the serving paths.

The contract under test: token ``t`` of row ``r`` samples with the key
``fold_in(fold_in(PRNGKey(seed), r), t)`` through ONE shared nucleus
filter — a pure function of (seed, row, token index), so results are
reproducible, independent of batch composition, and IDENTICAL between
the contiguous scan backend and the continuous-batching paged server.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kvedge_tpu.models import TransformerConfig, generate, init_params
from kvedge_tpu.models.decode import nucleus_filter
from kvedge_tpu.models.serving import PagedGenerationServer

CFG = TransformerConfig(
    vocab=128, d_model=32, n_heads=4, n_kv_heads=2, n_layers=2, d_ff=64,
    max_seq=64,
)


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


def _sampling(seed, rows, temperature, top_p):
    base = jax.random.PRNGKey(seed)
    keys = jax.vmap(lambda i: jax.random.fold_in(base, i))(
        jnp.arange(rows)
    )
    return (keys, jnp.float32(temperature), jnp.float32(top_p))


def test_sampled_generate_is_reproducible(params):
    prompt = jnp.asarray([[5, 9, 2, 7]], jnp.int32)
    a = generate(params, prompt, CFG, n_new=8,
                 sampling=_sampling(7, 1, 0.9, 0.95), sampled=True)
    b = generate(params, prompt, CFG, n_new=8,
                 sampling=_sampling(7, 1, 0.9, 0.95), sampled=True)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_different_seeds_diverge(params):
    prompt = jnp.asarray([[5, 9, 2, 7]], jnp.int32)
    outs = {
        tuple(np.asarray(generate(
            params, prompt, CFG, n_new=10,
            sampling=_sampling(seed, 1, 1.0, 1.0), sampled=True,
        ))[0].tolist())
        for seed in range(4)
    }
    assert len(outs) > 1  # 4 seeds all colliding would be ~impossible


def test_tiny_top_p_equals_greedy(params):
    prompt = jnp.asarray([[5, 9, 2, 7], [1, 2, 3, 4]], jnp.int32)
    greedy = generate(params, prompt, CFG, n_new=8)
    sampled = generate(params, prompt, CFG, n_new=8,
                       sampling=_sampling(3, 2, 1.0, 1e-6), sampled=True)
    np.testing.assert_array_equal(np.asarray(greedy), np.asarray(sampled))


def test_nucleus_filter_keeps_top_token_and_masks_tail():
    logits = jnp.asarray([[3.0, 2.0, 1.0, -4.0]], jnp.float32)
    out = np.asarray(nucleus_filter(logits, jnp.float32(1.0),
                                    jnp.float32(0.5)))
    assert np.isfinite(out[0, 0])        # top token always survives
    assert out[0, 3] == -np.inf          # the tail is masked
    tiny = np.asarray(nucleus_filter(logits, jnp.float32(1.0),
                                     jnp.float32(1e-9)))
    assert np.isfinite(tiny[0, 0]) and np.all(tiny[0, 1:] == -np.inf)


def test_paged_server_sampling_matches_contiguous(params):
    """The cross-backend contract: identical (seed, row, token) schedule
    -> identical sampled tokens, even though the paged server decodes
    the rows as independent continuous-batched requests."""
    prompts = [[5, 9, 2, 7], [1, 1, 4]]
    n_new = 8
    temperature, top_p, seed = 0.8, 0.9, 11

    # Contiguous backend needs uniform rows: run each row alone (batch 1)
    # so ragged prompts stay honest; per-row seed key = fold_in(base, i).
    base = jax.random.PRNGKey(seed)
    want = []
    for i, p in enumerate(prompts):
        keys = jax.random.fold_in(base, i)[None]
        out = generate(
            params, jnp.asarray([p], jnp.int32), CFG, n_new=n_new,
            sampling=(keys, jnp.float32(temperature), jnp.float32(top_p)),
            sampled=True,
        )
        want.append([int(t) for t in np.asarray(out)[0]])

    server = PagedGenerationServer(params, CFG, slots=2, pages=16)
    try:
        got = [
            server.submit(
                p, n_new,
                sampling=(jax.random.fold_in(base, i),
                          jnp.float32(temperature), jnp.float32(top_p)),
            )
            for i, p in enumerate(prompts)
        ]
    finally:
        server.close()
    assert got == want


def test_serve_endpoint_sampling_fields(tmp_path):
    from tests.test_serve import _cfg
    from kvedge_tpu.runtime.workload import run_serve_payload

    check, serve_fn = run_serve_payload(_cfg(tmp_path))
    assert check.ok, check.error
    req = {"tokens": [[5, 9, 2]], "n_new": 6,
           "temperature": 0.9, "top_p": 0.95, "seed": 3}
    a = serve_fn(req)
    b = serve_fn(req)
    assert a["tokens"] == b["tokens"]  # reproducible for a fixed seed

    for bad in (
        {"tokens": [[1, 2]], "temperature": -1},
        {"tokens": [[1, 2]], "top_p": 0},
        {"tokens": [[1, 2]], "top_p": 1.5},
        {"tokens": [[1, 2]], "seed": "x"},
    ):
        with pytest.raises(ValueError):
            serve_fn(bad)


def test_serve_endpoint_paged_and_contiguous_sampling_agree(tmp_path):
    from tests.test_serve import _cfg
    from kvedge_tpu.runtime.workload import run_serve_payload

    _, contiguous_fn = run_serve_payload(_cfg(tmp_path))
    _, paged_fn = run_serve_payload(
        _cfg(tmp_path, payload_serving="paged")
    )
    try:
        req = {"tokens": [[5, 9, 2, 7], [1, 1, 4, 3]], "n_new": 6,
               "temperature": 0.7, "top_p": 0.9, "seed": 5}
        assert paged_fn(req)["tokens"] == contiguous_fn(req)["tokens"]
    finally:
        paged_fn.close()
        contiguous_fn.close()


def test_sampled_windows_match_per_step_and_contiguous(params):
    """Round-5 on-device sampling: sampled requests decoded through
    multi-step device windows (kvcache.dispatch_window_sampled) emit
    exactly the tokens of (a) one-step windows (window=1, a harvest
    a token) and (b) the contiguous scan backend — the key schedule
    fold_in(seed, base + i) rides the scan carry bit-exactly."""
    import threading

    prompt, n_new = [5, 9, 2, 7], 24
    temperature, top_p, seed = 0.8, 0.9, 11
    base = jax.random.PRNGKey(seed)
    row_key = jax.random.fold_in(base, 0)
    sampling = (row_key, jnp.float32(temperature), jnp.float32(top_p))

    out = generate(
        params, jnp.asarray([prompt], jnp.int32), CFG, n_new=n_new,
        sampling=(row_key[None], jnp.float32(temperature),
                  jnp.float32(top_p)),
        sampled=True,
    )
    contiguous = [int(t) for t in np.asarray(out)[0]]

    results = {}
    for name, window in (("windowed", 16), ("per_step", 1)):
        server = PagedGenerationServer(params, CFG, slots=2, pages=16,
                                       page_size=4, window=window)
        try:
            results[name] = server.submit(prompt, n_new,
                                          sampling=sampling)
        finally:
            server.close()
    assert results["windowed"] == contiguous
    assert results["per_step"] == contiguous

    # Mixed batch: a greedy co-tenant rides the SAME mixed window and
    # still equals its greedy contiguous decode; the sampled tokens
    # are unchanged by the co-tenant (row independence).
    server = PagedGenerationServer(params, CFG, slots=2, pages=24,
                                   page_size=4, window=16)
    try:
        mixed = {}
        t = threading.Thread(
            target=lambda: mixed.update(
                g=server.submit([3, 1, 4, 1, 5], 20)
            )
        )
        t.start()
        mixed["s"] = server.submit(prompt, n_new, sampling=sampling)
        t.join(timeout=300)
        greedy_want = generate(
            params, jnp.asarray([[3, 1, 4, 1, 5]], jnp.int32), CFG,
            n_new=20,
        )
        assert mixed["s"] == contiguous
        assert mixed["g"] == [int(x) for x in np.asarray(greedy_want)[0]]
    finally:
        server.close()
