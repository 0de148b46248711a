"""int8 KV-cache quantization (kv_dtype="int8" on the paged backend).

The contract: per-token-row symmetric quantization (one fp32 scale per
row per kv head, values round(x/scale) int8) halves the cached-token
HBM bill; decode through the quantized pool is NEAR the bf16 pool —
bounded per-row error, high token agreement on the test model — and
every serving mechanism (windows, prefix sharing,
persistence, the slice protocol) composes with it unchanged.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kvedge_tpu.models import TransformerConfig, generate, init_params
from kvedge_tpu.models.kvcache import (
    PagedKVCache,
    _kv_dequantize,
    _kv_quantize,
)
from kvedge_tpu.models.serving import PagedGenerationServer

CFG = TransformerConfig(
    vocab=128, d_model=32, n_heads=4, n_kv_heads=2, n_layers=2, d_ff=64,
    max_seq=64,
)


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


def reference(params, prompt, n_new):
    out = generate(params, jnp.asarray([prompt], jnp.int32), CFG,
                   n_new=n_new)
    return [int(t) for t in np.asarray(out)[0]]


def test_quantize_roundtrip_error_bounded():
    """Dequant(quant(x)) is within half an int8 step of each row's
    amax/127 — the per-row error bound everything else rests on."""
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 4, 64),
                          jnp.float32) * 3.0
    q, scale = _kv_quantize(x)
    back = np.asarray(_kv_dequantize(q, scale, jnp.float32))
    err = np.abs(back - np.asarray(x))
    bound = np.asarray(scale)[..., None] * 0.5 + 1e-6
    assert (err <= bound).all()
    assert q.dtype == jnp.int8
    # An all-zero row must not divide by zero and round-trips to zero.
    q0, s0 = _kv_quantize(jnp.zeros((2, 64)))
    assert np.asarray(_kv_dequantize(q0, s0, jnp.float32)).max() == 0.0


def test_int8_cache_decode_near_bf16():
    """Greedy decode (per-step AND windowed) through an int8 pool
    agrees with the bf16 pool on the test model — quantization noise
    is far below this model's typical top-2 logit gaps."""
    params = init_params(jax.random.PRNGKey(0), CFG)
    prompts = {0: [5, 9, 2], 1: [7, 7, 7, 7, 7]}

    def decode(kv_dtype, n=12):
        c = PagedKVCache(CFG, slots=2, pages=16, page_size=4,
                         kv_dtype=kv_dtype)
        toks = np.zeros((2,), np.int32)
        for s, pr in prompts.items():
            c.admit(s, len(pr))
            logits = c.prefill(params, s, jnp.asarray(pr, jnp.int32))
            toks[s] = int(jnp.argmax(logits))
        out = [toks.copy()]
        for _ in range(n // 2):
            logits = c.step(params, jnp.asarray(toks))
            toks = np.asarray(jnp.argmax(logits, axis=-1), np.int32)
            out.append(toks.copy())
        w = n - n // 2
        prod = c.harvest_window(c.dispatch_window(params, toks, w))[:w]
        for row in prod:
            out.append(np.asarray(row, np.int32))
        return np.stack(out)

    agree = (decode("") == decode("int8")).mean()
    assert agree >= 0.9, agree


def test_int8_serving_end_to_end(params):
    """The full server over an int8 pool: concurrent greedy requests,
    a sampled request — everything serves, and
    greedy output stays near the exact contiguous decode."""
    import threading

    server = PagedGenerationServer(params, CFG, slots=2, pages=24,
                                   page_size=4, kv_dtype="int8")
    try:
        results: dict = {}
        t = threading.Thread(target=lambda: results.update(
            a=server.submit([5, 9, 2], 10)))
        t.start()
        key = jax.random.fold_in(jax.random.PRNGKey(7), 0)
        results["s"] = server.submit(
            [9, 8, 7], 6,
            sampling=(key, jnp.float32(0.8), jnp.float32(0.9)),
        )
        t.join(timeout=300)
        want = reference(params, [5, 9, 2], 10)
        matches = [x == y for x, y in zip(results["a"], want)]
        prefix = (matches.index(False) if False in matches
                  else len(matches))
        assert prefix >= len(want) // 2, (results["a"], want)
        assert len(results["s"]) == 9
    finally:
        server.close()


def test_int8_prefix_persistence_round_trip(params, tmp_path):
    """Dump from an int8 pool (dequantized file format) and re-pin into
    a fresh int8 server: entries load and the warm prefix still serves
    (one quantization round trip is within the documented bound)."""
    path = str(tmp_path / "pc.npz")
    server = PagedGenerationServer(params, CFG, slots=2, pages=24,
                                   page_size=4, kv_dtype="int8")
    try:
        base = [7, 3, 9, 1, 5, 5, 2, 8]
        first = server.submit(base + [4, 6], n_new=6)
        # A finished request registers its whole committed stream
        # (prompt and generated tokens, the last one has no K/V yet):
        # 10 + 6 - 1 = 15 tokens, three full pages of 4.
        assert server.dump_prefix_cache(path, "int8-fp") == 3
    finally:
        server.close()

    fresh = PagedGenerationServer(params, CFG, slots=2, pages=24,
                                  page_size=4, kv_dtype="int8")
    try:
        assert fresh.load_prefix_cache(path, "int8-fp") == 3
        again = fresh.submit(base + [4, 6], n_new=6)
        assert fresh.stats()["prefix_hits"] == 1
        assert again == first
    finally:
        fresh.close()


def test_int8_slice_cache_matches_local(params):
    """The slice protocol carries int8 pools + scales: a single-process
    slice cache's decode equals the plain int8 cache's."""
    from jax.sharding import Mesh

    from kvedge_tpu.runtime.sliceserve import SlicePagedKVCache

    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    prompts = {0: [5, 9, 2], 1: [7, 7, 7]}

    def decode(cache, n=8):
        toks = np.zeros((2,), np.int32)
        for s, pr in prompts.items():
            cache.admit(s, len(pr))
            logits = cache.prefill(params, s, jnp.asarray(pr, jnp.int32))
            toks[s] = int(np.argmax(np.asarray(logits)))
        out = [toks.copy()]
        prod = cache.harvest_window(
            cache.dispatch_window(params, toks, n))[:n]
        for row in prod:
            out.append(np.asarray(row, np.int32))
        return np.stack(out)

    plain = PagedKVCache(CFG, slots=2, pages=16, page_size=4,
                         kv_dtype="int8")
    slice_cache = SlicePagedKVCache(CFG, slots=2, pages=16, page_size=4,
                                    mesh=mesh, kv_dtype="int8")
    assert decode(slice_cache).tolist() == decode(plain).tolist()


def test_kv_bytes_metric_halves():
    """What a cached token costs in the pool itself: the bytes of the
    pool and scale arrays over ``pages * page_size`` positions."""
    pages, page_size = 16, 4

    def bytes_per_token(kv_dtype):
        state = PagedKVCache(CFG, slots=2, pages=pages,
                             page_size=page_size, kv_dtype=kv_dtype).state
        arrays = [state.pool_k, state.pool_v, state.scale_k,
                  state.scale_v]
        held = sum(a.nbytes for a in arrays if a is not None)
        assert held % (pages * page_size) == 0
        return held // (pages * page_size)

    bf16 = bytes_per_token("")
    i8 = bytes_per_token("int8")
    assert bf16 == CFG.n_layers * 2 * CFG.kv_heads * CFG.d_head * 2
    assert i8 == CFG.n_layers * 2 * CFG.kv_heads * (CFG.d_head + 4)
    assert i8 < 0.8 * bf16  # d_head 8 here; ~0.53x at d_head 64


def test_int8_kernel_path_matches_int8_gather(params):
    """The Pallas kernel's int8 variant (pages stream as stored, scales
    folded post-dot): a decode step through paged_attention='kernel' +
    int8 produces logits within numeric tolerance of the int8 gather
    path on the same quantized pool (interpret mode on CPU). Logits,
    not token sequences: a wrong page or wrong scale slot moves logits
    by whole units (measured legitimate diff ~4e-3 here), while token
    sequences cascade at this tiny model's sub-noise top-2 gaps. A
    window runs afterwards as a smoke of the scan path."""
    prompts = {0: [5, 9, 2], 1: [7, 7, 7, 7, 7]}

    def step_logits(paged_attention):
        cfg = dataclasses.replace(CFG, paged_attention=paged_attention)
        c = PagedKVCache(cfg, slots=2, pages=16, page_size=4,
                         kv_dtype="int8")
        toks = np.zeros((2,), np.int32)
        for s, pr in prompts.items():
            c.admit(s, len(pr))
            logits = c.prefill(params, s, jnp.asarray(pr, jnp.int32))
            toks[s] = int(jnp.argmax(logits))
        logits = np.asarray(c.step(params, jnp.asarray(toks)),
                            np.float32)
        nxt = jnp.asarray(np.argmax(logits, -1), jnp.int32)
        window = c.harvest_window(c.dispatch_window(params, nxt, 6))[:6]
        return logits, window

    lk, wk = step_logits("kernel")
    lg, wg = step_logits("gather")
    np.testing.assert_allclose(lk, lg, atol=0.05, rtol=0.05)
    assert wk.shape == wg.shape == (6, 2)


def test_forced_kernel_oversized_scales_refused():
    """A forced kernel whose int8 scale arrays exceed the VMEM budget
    refuses at construction — never a silent downgrade to the gather."""
    big = dataclasses.replace(CFG, paged_attention="kernel",
                              max_seq=64)
    with pytest.raises(ValueError, match="VMEM budget"):
        # 2M pages x 4 x 2 kv heads = 16M fp32 elements per array.
        PagedKVCache(big, slots=2, pages=2_000_000, page_size=4,
                     kv_dtype="int8")
