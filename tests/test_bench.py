"""bench.py's reporting math (pure functions; the timed paths run on TPU).

The MFU figure bench.py prints is only as honest as the FLOPs model
behind it — these tests pin that model against hand-derived counts so a
refactor cannot silently inflate the headline.
"""

import dataclasses

from bench import kv_cache_bytes_per_token, model_flops_per_token
from __graft_entry__ import FLAGSHIP


def test_flagship_flops_per_token_hand_count():
    # FLAGSHIP: D=512, H=8 (MHA), dh=64, F=2048, L=8, V=32000, seq 512.
    seq = 512
    qkv = 2 * 512 * (8 + 16) * 64          # fused q|k|v projection
    attn = 2 * seq * 512 + 2 * seq * 512   # qk^T + weights@v per token
    out = 2 * 512 * 512
    ffn = 2 * 512 * 2048 * 2
    per_layer = qkv + attn + out + ffn
    fwd = 8 * per_layer + 2 * 512 * 32000  # + tied readout
    assert model_flops_per_token(FLAGSHIP, seq) == 3.0 * fwd  # fwd + 2x bwd


def test_flops_scale_with_sequence():
    # Only the attention term depends on seq; doubling seq adds exactly
    # the extra attention FLOPs.
    f1 = model_flops_per_token(FLAGSHIP, 512)
    f2 = model_flops_per_token(FLAGSHIP, 1024)
    extra_attn = 3.0 * FLAGSHIP.n_layers * (
        2 * 512 * FLAGSHIP.n_heads * FLAGSHIP.d_head * 2
    )
    assert f2 - f1 == extra_attn


def test_gqa_shrinks_kv_cache_not_flops_much():
    gqa = dataclasses.replace(FLAGSHIP, n_kv_heads=2)
    mha = dataclasses.replace(FLAGSHIP, n_kv_heads=0)
    # The cache bill shrinks by n_heads / n_kv_heads exactly.
    assert kv_cache_bytes_per_token(mha) == 4 * kv_cache_bytes_per_token(gqa)
    # L * 2 (K and V) * kv_heads * dh * 2 bytes (bf16)
    assert kv_cache_bytes_per_token(gqa) == 8 * 2 * 2 * 64 * 2


def test_paged_decode_bench_runs_and_counts_tokens():
    """The paged-decode window (VERDICT r2 #5, windowed per r3 #2) runs
    on the CPU backend and reports slot-weighted throughput:
    tokens/s == slots * steps/s — for both the windowed production path
    and the per-step host-loop comparison number."""
    from bench import measure_paged_decode

    small = dataclasses.replace(
        FLAGSHIP, d_model=64, n_layers=2, d_ff=128, vocab=256,
        max_seq=64, n_heads=4, n_kv_heads=2,
    )
    tps, sps, host_sps, overlap_tps, overlap_speedup = (
        measure_paged_decode(
            small, slots=3, prompt_len=8, n_new=10, page_size=4
        )
    )
    assert tps > 0 and sps > 0 and host_sps > 0
    assert abs(tps - 3 * sps) < 1e-6
    # The overlapped (double-buffered) leg: positive throughput and a
    # finite speedup ratio vs the serial windowed leg. No lower bound
    # here — with the host beside the device there is no round trip to
    # hide, so the ratio legitimately sits near 1.0 (the >= 1.3
    # prediction applies only when the measured host round trip per
    # dispatch is >= 20 ms).
    assert overlap_tps > 0
    assert overlap_speedup > 0


def test_paged_mixed_and_adversarial_spec_benches_run():
    """The round-5 legs: the mixed greedy+sampled window bench and the
    adversarial (random-prompt) spec bench both run on the CPU backend
    and report positive throughput; adversarial acceptance collapses
    toward 1 emitted/pass (drafts never land on random text)."""
    import dataclasses as dc

    from bench import measure_paged_mixed, measure_paged_spec

    small = dc.replace(
        FLAGSHIP, d_model=64, n_layers=2, d_ff=128, vocab=256,
        max_seq=64, n_heads=4, n_kv_heads=2,
    )
    tps = measure_paged_mixed(
        small, slots=3, prompt_len=8, n_new=10, page_size=4, window=8
    )
    assert tps > 0
    worst_tps, worst_epp = measure_paged_spec(
        small, slots=2, prompt_len=16, n_new=8, page_size=4,
        draft_len=4, adversarial=True,
    )
    assert worst_tps > 0
    assert worst_epp <= 2.0  # acceptance ~0: ~1 token per pass


def test_paged_longcontext_bench_runs_tiny():
    """The long-context A/B leg at tiny shapes on CPU: both impls run
    (kernel under the Pallas interpreter), logits proximity gate holds,
    timings and agreement report for each live length."""
    import dataclasses as dc

    from bench import measure_paged_longcontext

    small = dc.replace(
        FLAGSHIP, d_model=64, n_layers=2, d_ff=128, vocab=256,
        n_heads=4, n_kv_heads=2,
    )
    times, agree = measure_paged_longcontext(
        small, slots=2, page_size=4, lives=(8, 24), n_steps=4,
        max_seq=64,
    )
    for impl in ("gather", "kernel"):
        for live in (8, 24):
            assert times[(impl, live)] > 0
    assert set(agree) == {8, 24}
    assert all(0.0 <= v <= 1.0 for v in agree.values())
