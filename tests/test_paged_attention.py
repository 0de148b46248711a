"""The Pallas paged-attention decode kernel vs the gather path.

The kernel (ops/paged_attention.py) computes decode attention directly
over the block table; the gather path materializes the padded pool view
(kvcache._gathered). The contract is BIT-IDENTITY, not tolerance: the
two-phase kernel stages the gather's own rounded score rows and runs
the same softmax + flat V contraction, so every comparison here is
exact (raw-bits equality). On CPU the kernel runs under the Pallas
interpreter (cfg.paged_attention = "kernel" forces it; "auto" resolves
to the gather here), which is how these tests pin it without TPU
hardware; chip_smoke.py re-asserts the same bit-identity on the real
chip. A row handed over at a negative position is not decoding: the
kernel does nothing for it and returns zeros, and the rows around it
keep their bits (the gather has no such rows to be held to).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kvedge_tpu.models import TransformerConfig, init_params
from kvedge_tpu.models.kvcache import PagedKVCache
from kvedge_tpu.ops.paged_attention import paged_decode_attention

CFG = TransformerConfig(
    vocab=128, d_model=32, n_heads=4, n_kv_heads=2, n_layers=2, d_ff=64,
    max_seq=64, paged_attention="gather",
)
KERNEL_CFG = dataclasses.replace(CFG, paged_attention="kernel")


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


def _gather_reference(q, pool_k, pool_v, tables, q_pos, parts=False):
    """kvcache._paged_attend_layer's gather math at q_len == 1, inlined
    shape-for-shape (the einsum dims, mask, softmax upcast, and weight
    rounding all match the serving path) — the thing the kernel must
    reproduce BITWISE, not approximately. ``parts`` returns the rounded
    weights [B, K, G, 1, S] and the gathered V [B, S, K, Dh] instead of
    their contraction."""
    B, H, Dh = q.shape
    _, page, KV, _ = pool_k.shape
    MP = tables.shape[1]
    G = H // KV
    k = pool_k[tables].reshape(B, MP * page, KV, Dh)
    v = pool_v[tables].reshape(B, MP * page, KV, Dh)
    qg = q.reshape(B, 1, KV, G, Dh)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k) / (Dh ** 0.5)
    allowed = jnp.arange(MP * page)[None, :] <= q_pos[:, None]
    s = jnp.where(allowed[:, None, None, None], s, jnp.finfo(q.dtype).min)
    w = jax.nn.softmax(s.astype(jnp.float32), -1).astype(q.dtype)
    if parts:
        return w, v
    att = jnp.einsum("bkgqs,bskd->bqkgd", w, v)
    return att.reshape(B, 1, H, Dh)[:, 0]


def _kernel(q, slab_k, slab_v, tables, q_pos, scale_k=None, scale_v=None):
    """The kernel over per-head slabs [P, page, K, Dh] (what the gather
    reference reads), handed over the way PagedState stores them: a
    pool of one layer with the kv heads merged, [1, P, page, K*Dh],
    and layer index 0."""
    def pool(slab):
        return slab.reshape(1, *slab.shape[:2], -1)

    if scale_k is not None:
        scale_k, scale_v = scale_k[None], scale_v[None]
    return paged_decode_attention(
        q, pool(slab_k), pool(slab_v), tables, q_pos, 0,
        scale_k=scale_k, scale_v=scale_v, interpret=True)


def _assert_bit_identical(got, want):
    """Exact equality, compared as raw bits: any tolerance here would
    let the 0.92-agreement regression (r05) back in."""
    got16 = np.asarray(got).view(np.uint16)
    want16 = np.asarray(want).view(np.uint16)
    np.testing.assert_array_equal(got16, want16)


def _ragged_pool(B, H, KV, Dh, page, q_pos_list, seed=0):
    """Random pool + block tables whose rows live exactly through
    ``q_pos_list`` (page 0 left as the shared dead-page alias)."""
    MP = max(qp // page + 1 for qp in q_pos_list) + 1
    P = sum(qp // page + 1 for qp in q_pos_list) + 1
    kq, kk, kv_ = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(kq, (B, H, Dh), jnp.bfloat16)
    pool_k = jax.random.normal(kk, (P, page, KV, Dh), jnp.bfloat16)
    pool_v = jax.random.normal(kv_, (P, page, KV, Dh), jnp.bfloat16)
    tables = np.zeros((B, MP), np.int32)
    nxt = 1
    for b, qp in enumerate(q_pos_list):
        for j in range(qp // page + 1):
            tables[b, j] = nxt
            nxt += 1
    return (q, pool_k, pool_v, jnp.asarray(tables),
            jnp.asarray(q_pos_list, jnp.int32))


def test_kernel_matches_gather_bitwise_ragged_lengths():
    """Raw op check: block-table streaming == padded gather + einsum,
    BIT-FOR-BIT, across rows whose live lengths span <1 page to several
    pages (dead pages in between must contribute nothing)."""
    q, pool_k, pool_v, tables, q_pos = _ragged_pool(
        3, 8, 2, 64, 16, [40, 17, 3])
    want = _gather_reference(q, pool_k, pool_v, tables, q_pos)
    got = _kernel(q, pool_k, pool_v, tables, q_pos)
    _assert_bit_identical(got, want)


@pytest.mark.window
def test_kernel_bitwise_at_page_boundary_and_longctx():
    """The r05 regression pinned forever: at live lengths straddling a
    page boundary (511/512/513, page 128 — partial page, exact page,
    one-past) and at live 4096, the kernel output equals the gather's
    bit-for-bit. The old online-softmax kernel disagreed here
    (paged_longctx_token_agreement = 0.92 at live 512).

    One output of the first case is pinned apart (ROADMAP D6): row 0,
    head 3, column 1 is a weights-times-V sum of 511 terms near 2e-3
    that cancels to 3.96e-6, and under JAX 0.9.0 XLA:CPU's einsum of
    the REFERENCE sums it in an order that lands one bf16 step under
    the correctly rounded value, which the kernel's flat dot gives. So
    there, and only there, the kernel is held to the float64 dot of the
    reference's own weights and V rounded once, and the reference to
    one step of it; the other 1,535 outputs and the long case stay
    exact against the gather. Compiled for the chip the whole
    comparison is exact, and chip_smoke.py fails if it is not."""
    q, pool_k, pool_v, tables, q_pos = _ragged_pool(
        3, 8, 2, 64, 128, [510, 511, 512])
    want = np.asarray(_gather_reference(q, pool_k, pool_v, tables, q_pos))
    got = np.asarray(_kernel(q, pool_k, pool_v, tables, q_pos))
    b, h, d = pinned = (0, 3, 1)
    w, v = _gather_reference(q, pool_k, pool_v, tables, q_pos, parts=True)
    group = q.shape[1] // pool_k.shape[2]
    exact = np.dot(
        np.asarray(w[b, h // group, h % group, 0], np.float64),
        np.asarray(v[b, :, h // group, d], np.float64))
    rounded = np.asarray(jnp.asarray(exact, jnp.bfloat16))
    assert got[pinned].view(np.uint16) == rounded.view(np.uint16)
    assert abs(int(want[pinned].view(np.uint16))
               - int(rounded.view(np.uint16))) <= 1
    want = want.copy()
    want[pinned] = got[pinned]
    _assert_bit_identical(got, want)

    q, pool_k, pool_v, tables, q_pos = _ragged_pool(
        1, 8, 2, 64, 128, [4095], seed=1)
    want = _gather_reference(q, pool_k, pool_v, tables, q_pos)
    got = _kernel(q, pool_k, pool_v, tables, q_pos)
    _assert_bit_identical(got, want)


@pytest.mark.window
def test_kernel_bitwise_int8_pool():
    """The int8 variant dequantizes pages in VMEM with the gather's
    exact elementwise formula before any compute — so it too is
    bit-identical, including at a page boundary."""
    B, H, KV, Dh, page = 2, 8, 2, 64, 128
    MP, P = 5, 9
    keys = jax.random.split(jax.random.PRNGKey(7), 5)
    q = jax.random.normal(keys[0], (B, H, Dh), jnp.bfloat16)
    pool_k = jax.random.randint(keys[1], (P, page, KV, Dh), -127, 128,
                                jnp.int8)
    pool_v = jax.random.randint(keys[2], (P, page, KV, Dh), -127, 128,
                                jnp.int8)
    sk = jax.random.uniform(keys[3], (P, page, KV), jnp.float32,
                            0.001, 0.02)
    sv = jax.random.uniform(keys[4], (P, page, KV), jnp.float32,
                            0.001, 0.02)
    tables = jnp.asarray([[1, 2, 3, 4, 0], [5, 6, 0, 0, 0]], jnp.int32)
    q_pos = jnp.asarray([512, 255], jnp.int32)

    k = (pool_k[tables].astype(jnp.float32)
         * sk[tables][..., None]).astype(jnp.bfloat16)
    v = (pool_v[tables].astype(jnp.float32)
         * sv[tables][..., None]).astype(jnp.bfloat16)
    want = _gather_reference(
        q, k.reshape(B * MP, page, KV, Dh),
        v.reshape(B * MP, page, KV, Dh),
        jnp.arange(B * MP, dtype=jnp.int32).reshape(B, MP), q_pos)
    got = _kernel(q, pool_k, pool_v, tables, q_pos, sk, sv)
    _assert_bit_identical(got, want)


@pytest.mark.window
@pytest.mark.parametrize("layer", [0, 2], ids=["first", "last"])
@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
def test_layer_indexed_kernel_equals_kernel_on_that_layers_slab(layer, int8):
    """The pool is never taken apart: handed the whole [L, P, page,
    K*Dh] pool and a layer index (traced, as the layer loop's is), the
    kernel DMAs that layer's pages where they lie and returns, bit for
    bit, what it returns for that layer's slab alone — and what the
    gather returns for it — at live lengths 127/128/129 (the last
    column of a page, a full page, one past)."""
    L, B, H, KV, Dh, page, MP = 3, 3, 8, 2, 64, 128, 3
    P = B * MP + 1
    keys = jax.random.split(jax.random.PRNGKey(25), 5)
    q = jax.random.normal(keys[0], (B, H, Dh), jnp.bfloat16)
    shape = (L, P, page, KV, Dh)
    if int8:
        slabs_k = jax.random.randint(keys[1], shape, -127, 128, jnp.int8)
        slabs_v = jax.random.randint(keys[2], shape, -127, 128, jnp.int8)
        sk = jax.random.uniform(keys[3], shape[:-1], jnp.float32,
                                0.001, 0.02)
        sv = jax.random.uniform(keys[4], shape[:-1], jnp.float32,
                                0.001, 0.02)
    else:
        slabs_k = jax.random.normal(keys[1], shape, jnp.bfloat16)
        slabs_v = jax.random.normal(keys[2], shape, jnp.bfloat16)
        sk = sv = None
    tables = jnp.asarray(1 + np.arange(B * MP).reshape(B, MP), jnp.int32)
    q_pos = jnp.asarray([126, 127, 128], jnp.int32)

    whole = jax.jit(lambda l: paged_decode_attention(
        q, slabs_k.reshape(L, P, page, KV * Dh),
        slabs_v.reshape(L, P, page, KV * Dh), tables, q_pos, l,
        scale_k=sk, scale_v=sv, interpret=True))
    got = whole(jnp.asarray(layer, jnp.int32))
    alone = _kernel(q, slabs_k[layer], slabs_v[layer], tables, q_pos,
                    *((sk[layer], sv[layer]) if int8 else ()))
    _assert_bit_identical(got, alone)

    k, v = slabs_k[layer], slabs_v[layer]
    if int8:
        k = (k.astype(jnp.float32) * sk[layer][..., None]).astype(q.dtype)
        v = (v.astype(jnp.float32) * sv[layer][..., None]).astype(q.dtype)
    _assert_bit_identical(got, _gather_reference(q, k, v, tables, q_pos))


def _pool_with_dead_rows(q_pos_list, *, int8, page=16, H=8, KV=2, Dh=64,
                         seed=0):
    """A pool for rows of which some are dead (position < 0), as
    ``_paged_attend_layer`` hands a row that is not decoding. Page 0 is
    the alias unused table entries point at, page 1 is poison (NaN: in
    the data of a bf16 pool, in the scales of an int8 one); a dead
    row's table holds the poison page among ids far outside the pool.
    Returns the kernel's operands and, for the reference, the pool in
    the compute dtype (an int8 pool dequantized as the gather does)."""
    live_pages = [qp // page + 1 for qp in q_pos_list if qp >= 0]
    B, MP, P = len(q_pos_list), max(live_pages + [1]) + 1, sum(live_pages) + 2
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(keys[0], (B, H, Dh), jnp.bfloat16)
    shape = (P, page, KV, Dh)
    if int8:
        pool_k = jax.random.randint(keys[1], shape, -127, 128, jnp.int8)
        pool_v = jax.random.randint(keys[2], shape, -127, 128, jnp.int8)
        sk = jax.random.uniform(keys[3], shape[:-1], jnp.float32, 0.001, 0.02)
        sv = jax.random.uniform(keys[4], shape[:-1], jnp.float32, 0.001, 0.02)
        sk, sv = sk.at[1].set(jnp.nan), sv.at[1].set(jnp.nan)
        scales = (sk, sv)
        ref_k = (pool_k.astype(jnp.float32) * sk[..., None]).astype(q.dtype)
        ref_v = (pool_v.astype(jnp.float32) * sv[..., None]).astype(q.dtype)
    else:
        pool_k = jax.random.normal(keys[1], shape, jnp.bfloat16).at[1].set(
            jnp.nan)
        pool_v = jax.random.normal(keys[2], shape, jnp.bfloat16).at[1].set(
            jnp.nan)
        scales, ref_k, ref_v = (), pool_k, pool_v
    tables = np.zeros((B, MP), np.int32)
    nxt = 2
    for b, qp in enumerate(q_pos_list):
        if qp < 0:
            tables[b] = 2 ** 30 + np.arange(MP)
            tables[b, 0] = 1
            continue
        for j in range(qp // page + 1):
            tables[b, j] = nxt
            nxt += 1
    return (q, pool_k, pool_v, jnp.asarray(tables),
            jnp.asarray(q_pos_list, jnp.int32), scales, ref_k, ref_v)


def _assert_dead_rows_do_nothing(q_pos_list, *, int8, page=16):
    """Live rows: the gather's bits, and the bits the same rows get in
    a batch of their own. Dead rows: exact +0.0, whatever their tables
    hold."""
    q, pool_k, pool_v, tables, q_pos, scales, ref_k, ref_v = (
        _pool_with_dead_rows(q_pos_list, int8=int8, page=page))
    got = np.asarray(_kernel(q, pool_k, pool_v, tables, q_pos, *scales))
    live = np.flatnonzero(np.asarray(q_pos_list) >= 0)
    dead = np.flatnonzero(np.asarray(q_pos_list) < 0)
    assert not got[dead].view(np.uint16).any()
    if not live.size:
        return
    _assert_bit_identical(got[live], _gather_reference(
        q[live], ref_k, ref_v, tables[live], q_pos[live]))
    alone = _kernel(q[live], pool_k, pool_v, tables[live], q_pos[live],
                    *scales)
    _assert_bit_identical(got[live], alone)


# Positions of a batch's rows; -1 is a row that is not decoding. The
# cross-row prefetch has two edges: a live row behind a dead one (its
# first block was started by an earlier row, or by row 0 on its behalf)
# and a dead row at the end of the grid (nothing is started for it).
_DEAD_ROW_BATCHES = {
    "interleaved": [-1, 40, -1, 17, 3, -1],
    "live_behind_dead_and_dead_last": [17, -1, 40, -1],
    "dead_first_rows": [-1, -1, 5, 33],
    "one_live_row": [21],
    "one_dead_row": [-1],
    "all_dead": [-1, -1, -1],
}


@pytest.mark.window
@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("batch", sorted(_DEAD_ROW_BATCHES))
def test_dead_rows_do_nothing_and_live_rows_keep_their_bits(batch, int8):
    """A dead row's program starts no copy and writes zeros (its table
    points at a poison page and far outside the pool); a live row's
    output does not depend on which rows around it are dead, B = 1
    included, for the bf16 and the int8 pool alike."""
    _assert_dead_rows_do_nothing(_DEAD_ROW_BATCHES[batch], int8=int8)


@pytest.mark.window
@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
def test_lengths_around_a_block_boundary(monkeypatch, int8):
    """Pages arrive in blocks; with blocks of two 16-token pages, live
    lengths of 31, 32 and 33 (the last column of a block, a full block,
    one past) and of 64, 65 and 96 (two and three blocks, the second
    slot reused), a dead row among them."""
    import kvedge_tpu.ops.paged_attention as pa

    monkeypatch.setattr(pa, "_MAX_BLOCK_PAGES", 2)
    assert pa.block_pages(7, 16, 128) == 2
    # The depth is read when the kernel is traced, and traces are kept.
    pa.paged_decode_attention.clear_cache()
    try:
        _assert_dead_rows_do_nothing([30, 31, 32, 63, -1, 64, 95], int8=int8)
    finally:
        pa.paged_decode_attention.clear_cache()


@pytest.mark.window
def test_lengths_around_the_kernels_own_block_boundary():
    """The same at the depth the kernel chooses for 128-token pages of
    this width, eight pages: live lengths 1,023, 1,024 and 1,025."""
    from kvedge_tpu.ops.paged_attention import block_pages

    assert block_pages(10, 128, 128) == 8
    _assert_dead_rows_do_nothing([1022, 1023, 1024], int8=False, page=128)


def test_block_depth_follows_page_bytes_and_cap():
    """Landing pads are a fixed share of the scratch budget: 8 pages a
    block at the benchmark cell's 64 KB pages, 4 at an MHA width's 128
    KB, 8 for an int8 pool of that width, never more than a row has."""
    from kvedge_tpu.ops.paged_attention import (
        block_pages, decode_scratch_fits_vmem,
    )

    assert block_pages(24, 128, 256) == 8
    assert block_pages(16, 128, 512) == 4
    assert block_pages(16, 128, 512, itemsize=1) == 8
    assert block_pages(3, 128, 256) == 3
    assert block_pages(1, 16, 128) == 1
    assert decode_scratch_fits_vmem(24, 128, 256, 24)


@pytest.mark.window
@pytest.mark.parametrize("kv_dtype", ["", "int8"], ids=["bf16", "int8"])
def test_half_prefilled_slot_is_a_dead_row_of_the_step(params, kv_dtype):
    """A half-prefilled slot is inactive but carries its final length
    (``_decode_step_core``): the kernel is told, reads none of its
    pages, and the decoding row beside it gets the logits, bit for bit,
    that it gets under the gather; the slot's length stays, its logits
    are finite, and once its prefill is finished both rows decode to
    the gather's tokens."""
    prompts = [[5, 9, 2, 7, 7, 1], [3, 3, 8, 1, 4, 4, 6, 2, 9]]

    def run(cfg):
        cache = PagedKVCache(cfg, slots=2, pages=16, page_size=4,
                             kv_dtype=kv_dtype)
        cache.admit(0, len(prompts[0]))
        first = cache.prefill(params, 0, jnp.asarray(prompts[0], jnp.int32))
        cache.admit(1, len(prompts[1]))
        cache.prefill_chunk(params, 1, jnp.asarray(prompts[1][:4],
                                                   jnp.int32), 0)
        tokens = np.asarray([int(jnp.argmax(first)), 0], np.int32)
        logits = np.asarray(cache.step(
            params, jnp.asarray(tokens), active=np.array([True, False])
        ).astype(jnp.float32))
        lengths = np.asarray(cache.state.lengths).tolist()
        last = cache.prefill_chunk(
            params, 1, jnp.asarray(prompts[1][4:], jnp.int32), 4)
        tokens = np.asarray([int(np.argmax(logits[0])),
                             int(jnp.argmax(last))], np.int32)
        after = cache.harvest_window(
            cache.dispatch_window(params, tokens, 6))[:6]
        return logits, lengths, np.asarray(after).tolist()

    gather_logits, gather_lengths, gather_tokens = run(CFG)
    logits, lengths, tokens = run(KERNEL_CFG)
    np.testing.assert_array_equal(logits[0].view(np.uint32),
                                  gather_logits[0].view(np.uint32))
    assert np.isfinite(logits[1]).all()
    assert lengths == gather_lengths == [len(prompts[0]) + 1,
                                         len(prompts[1])]
    assert tokens == gather_tokens


def _greedy_tokens(cfg, params, prompts, n_new):
    """Greedy decode through the paged cache: per-step and windowed."""
    cache = PagedKVCache(cfg, slots=len(prompts), pages=32, page_size=4)
    pend = np.zeros((len(prompts),), np.int32)
    for s, p in enumerate(prompts):
        cache.admit(s, len(p))
        logits = cache.prefill(params, s, jnp.asarray(p, jnp.int32))
        pend[s] = int(jnp.argmax(logits))
    out = [pend.copy()]
    toks = pend
    # Half the budget per-step, half windowed — both decode paths run
    # through the kernel under test.
    for _ in range(n_new // 2):
        logits = cache.step(params, jnp.asarray(toks))
        toks = np.asarray(jnp.argmax(logits, axis=-1), np.int32)
        out.append(toks.copy())
    w = n_new - n_new // 2
    produced = cache.harvest_window(
        cache.dispatch_window(params, toks, w))[:w]
    for row in produced:
        out.append(np.asarray(row, np.int32))
    return np.stack(out)


def test_cache_decode_kernel_equals_gather_tokens(params):
    """End to end through PagedKVCache: greedy tokens (per-step AND
    windowed, ragged prompts, pages crossing boundaries) are identical
    under paged_attention='kernel' and 'gather'."""
    prompts = [[5, 9, 2], [7, 7, 7, 7, 7, 1, 4]]
    gather = _greedy_tokens(CFG, params, prompts, 12)
    kernel = _greedy_tokens(KERNEL_CFG, params, prompts, 12)
    assert kernel.tolist() == gather.tolist()


@pytest.mark.window
def test_longctx_token_agreement_at_page_boundaries():
    """End to end through PagedKVCache at prompt lengths straddling a
    page boundary (511/512/513 at page 128): windowed greedy decode
    under 'kernel' and 'gather' produces IDENTICAL tokens: agreement
    must be 1.0, the tier-1 pin that keeps a 0.92 once measured there
    from silently returning."""
    long_cfg = dataclasses.replace(CFG, max_seq=640)
    long_params = init_params(jax.random.PRNGKey(1), long_cfg)
    prompts = [
        np.asarray(jax.random.randint(
            jax.random.PRNGKey(10 + n), (n,), 0, long_cfg.vocab
        ), np.int32).tolist()
        for n in (511, 512, 513)
    ]

    def tokens(cfg):
        cache = PagedKVCache(cfg, slots=3, pages=18, page_size=128)
        pend = np.zeros((3,), np.int32)
        for s, p in enumerate(prompts):
            cache.admit(s, len(p))
            logits = cache.prefill(
                long_params, s, jnp.asarray(p, jnp.int32))
            pend[s] = int(jnp.argmax(logits))
        produced = cache.harvest_window(
            cache.dispatch_window(long_params, pend, 12))[:12]
        return np.concatenate([pend[None], produced])

    gather = tokens(long_cfg)
    kernel = tokens(dataclasses.replace(long_cfg,
                                        paged_attention="kernel"))
    agreement = float(np.mean(kernel == gather))
    assert agreement == 1.0, (
        f"paged_longctx_token_agreement regressed to {agreement}"
    )


def test_prefill_path_unaffected_by_kernel_flag(params):
    """Prefill is multi-query — it keeps the gather path, so its
    logits under the kernel flag are the gather config's exactly."""
    def prefill_run(cfg):
        cache = PagedKVCache(cfg, slots=2, pages=32, page_size=4)
        cache.admit(0, 4)
        logits = cache.prefill(params, 0,
                               jnp.asarray([6, 6, 6, 6], jnp.int32))
        return np.asarray(logits).tolist()

    assert prefill_run(KERNEL_CFG) == prefill_run(CFG)


def test_auto_never_picks_kernel_multiprocess(monkeypatch):
    """Slice pools must never auto-select the kernel: it has no
    partitioning rule, so a sharded trace would poison the first decode
    step on a real slice. All other auto conditions held true, the
    process count alone must veto."""
    import kvedge_tpu.models.kvcache as kvmod

    cfg = dataclasses.replace(CFG, paged_attention="auto", max_seq=4096)
    monkeypatch.setattr(kvmod.jax, "default_backend", lambda: "tpu")
    assert kvmod._use_paged_kernel(cfg, 128, 256)
    monkeypatch.setattr(kvmod.jax, "process_count", lambda: 2)
    assert not kvmod._use_paged_kernel(cfg, 128, 256)


@pytest.mark.parametrize("spec", ["replicated", "sharded"])
def test_pool_over_several_devices_settles_on_the_gather(params, spec):
    """Where arrays live is settled before the pool is built
    (settle_paged_attention): over params that span several devices —
    split or only replicated, Mosaic refuses both — "auto" means the
    gather, a forced "kernel" is refused at construction and not
    downgraded, and an injected pool that would trace the kernel is
    refused too. On one device nothing changes."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from kvedge_tpu.models.kvcache import settle_paged_attention
    from kvedge_tpu.models.serving import PagedGenerationServer
    from kvedge_tpu.parallel import shard_params

    auto = dataclasses.replace(CFG, paged_attention="auto")
    for cfg in (auto, KERNEL_CFG, CFG):
        assert settle_paged_attention(cfg, params) is cfg
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("data", "model"))
    placed = (shard_params(mesh, params) if spec == "sharded" else
              jax.device_put(params, NamedSharding(mesh, P())))
    assert settle_paged_attention(auto, placed).paged_attention == "gather"
    assert settle_paged_attention(CFG, placed) is CFG
    with pytest.raises(ValueError, match="cannot be partitioned"):
        PagedGenerationServer(placed, KERNEL_CFG, slots=2, pages=8,
                              page_size=4)
    injected = PagedKVCache(auto, slots=2, pages=8, page_size=4)
    with pytest.raises(ValueError, match="injected cache"):
        PagedGenerationServer(placed, auto, cache=injected)


def test_vmem_refusal_spares_gather_only_traces(params, monkeypatch):
    """The trace-time VMEM refusal fires only where the kernel could
    actually run (single-query decode). Prefill always takes the
    gather, so a forced-kernel int8 pool must still trace it —
    refusing there would kill a program the pool needs."""
    cfg = dataclasses.replace(CFG, paged_attention="kernel")
    # Distinct pool geometry: reusing another test's shapes would hit
    # the jit cache and skip the trace whose refusal is under test.
    cache = PagedKVCache(cfg, slots=2, pages=20, page_size=4,
                         kv_dtype="int8")
    monkeypatch.setattr("kvedge_tpu.ops.paged_attention.scales_fit_vmem",
                        lambda rows, kv_heads: False)
    cache.admit(0, 3)
    cache.prefill(params, 0, jnp.asarray([5, 9, 2], jnp.int32))
    active = np.array([True, False])
    with pytest.raises(ValueError, match="VMEM budget"):
        cache.step(params, jnp.asarray([1, 0], jnp.int32), active=active)
