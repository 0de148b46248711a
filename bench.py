"""Benchmark: flagship transformer train-step throughput on visible devices.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...extras}.

``vs_baseline`` context: the reference (levi106/kvedge) publishes no
benchmark numbers of any kind — it is a deployment accelerator with no
compute workload (BASELINE.md; BASELINE.json records metric "N/A" and
``published: {}``). There is therefore no reference number to normalize
against; vs_baseline is reported as 1.0 by convention and the absolute
throughput stands on its own. ``vs_r01`` tracks this repo's own round-1
floor (246,669 tok/s: pre-PR-1 chip run, record removed in PR 21, not
comparable with today's code) instead.

Config provenance (tools/bench_sweep.py and tools/bench_breakdown.py
regenerate it; the sweep and breakdown records they once wrote were
pre-PR-1 chip runs, removed in PR 21, not comparable with today's
code):

* attention="naive", remat=True/"full", batch 64/device was the best of
  that 36-variant sweep (flash/fused-xent/remat-off/dots all slower;
  remat=off at bpd>=64 failed to compile). At seq 512 XLA's fused naive
  attention matched the Pallas flash kernel (flash's domain is T≈4096
  up), and remat=OFF was consistently SLOWER than remat=full — XLA
  scheduled the rematerialized backward better than the
  activation-saving one.
* Every named mechanism against the step's non-dot device time was
  tried then: scan-unroll (negative), the fused cross-entropy Pallas
  kernel (tie — XLA already fuses the CE cotangent into the matmul
  operands), and a Pallas fused RMSNorm (tie, SWEEP_r04
  "rmsnorm_fusion"). MFU below is reported against the chip's NOMINAL
  peak (PEAK_FLOPS_BY_DEVICE_KIND), the honest industry convention.
* Steps run inside one jitted ``lax.scan`` (TIMED_STEPS per call), so a
  host round trip per dispatch is paid once per TIMED_STEPS, not every
  step as a Python step loop would.

Serving metrics: decode_tokens_per_sec drives the contiguous KV-cache
greedy decode (models/decode.py, the whole loop one jitted scan) for the
flagship shape in MHA and GQA (n_kv=2) forms, plus the per-token KV-cache
HBM bill for each. The paged continuous-batching path
(models/kvcache.py) is timed as the server runs it: device-side decode
windows (``cache.step_window`` — up to ``serving_window`` = 64 steps
per dispatched scan since round 5; round 4 capped windows at page_size,
which chained throughput to the host round trip), at full slot
occupancy, INCLUDING the per-window host read of the produced tokens
(the serving loop emits them and checks budgets — an async-pipelined
loop that never fetches tokens is not a loop the server can run).
``paged_decode_hostloop_steps_per_sec`` re-times the same steps with
the per-step host read — the r3-era baseline (sampled slots now ride
windows too: ``paged_mixed_tokens_per_sec``). Both are bound below by
the host round trip per dispatch; the windowed path amortizes it
~window x, and ``host_round_trip_ms`` is reported alongside so each
run's numbers are interpretable against the round trip they paid.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from __graft_entry__ import FLAGSHIP, _factor_mesh
from kvedge_tpu.models import (
    generate,
    init_params,
    make_train_step,
)
from kvedge_tpu.parallel import build_mesh, shard_batch, shard_params
from kvedge_tpu.runtime.compilecache import enable_compile_cache

SEQ = 512
BATCH_PER_DEVICE = 64
WARMUP_STEPS = 3
TIMED_STEPS = 10
# Round-1 floor: pre-PR-1 chip run, record removed in PR 21, not
# comparable with today's code.
R01_TOKENS_PER_SEC = 246669.3

# bf16 nominal peak per chip, the conventional MFU denominator, keyed by
# ``jax.devices()[0].device_kind``. Source: Google Cloud documentation,
# "TPU v5e" (197 TFLOP/s bf16); the chip reports itself as "TPU v5
# lite" (chip_smoke.py, PR 21). A device that is not here is an error,
# not a default: an MFU against the wrong peak is not a number.
PEAK_FLOPS_BY_DEVICE_KIND = {
    "TPU v5 lite": 197e12,
}


def peak_flops_per_chip() -> float:
    """The visible device's nominal bf16 peak, or an error naming it."""
    device = jax.devices()[0]
    try:
        return PEAK_FLOPS_BY_DEVICE_KIND[device.device_kind]
    except KeyError:
        raise SystemExit(
            f"bench.py has no peak FLOP/s for device_kind="
            f"{device.device_kind!r} (platform {device.platform!r}); it "
            f"measures {sorted(PEAK_FLOPS_BY_DEVICE_KIND)} — add the "
            "device's published peak with its source, or run on the chip"
        ) from None

DECODE_BATCH = 8
DECODE_PROMPT = 64
DECODE_NEW = 128


def model_flops_parts(cfg, seq: int) -> tuple[float, float]:
    """(layer-stack fwd FLOPs, readout fwd FLOPs) per token.

    Split out so tools/bench_breakdown.py can account remat recompute
    (layers re-run forward in backward; the readout does not)."""
    d, h, kv, dh, f = (cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.d_head,
                       cfg.d_ff)
    per_layer = (
        2 * d * (h + 2 * kv) * dh   # fused qkv projection
        + 2 * seq * h * dh          # q @ k^T (per query token)
        + 2 * seq * h * dh          # weights @ v
        + 2 * h * dh * d            # output projection
        + 2 * d * f + 2 * f * d     # ffn up + down
    )
    return cfg.n_layers * per_layer, 2 * d * cfg.vocab


def model_flops_per_token(cfg, seq: int) -> float:
    """Useful train FLOPs per token (fwd + 2x bwd; remat recompute NOT
    counted — MFU measures useful work). Attention counted unmasked, the
    standard convention (PaLM-style accounting)."""
    layers, readout = model_flops_parts(cfg, seq)
    return 3.0 * (layers + readout)


def measure(cfg, batch_per_device: int, seq: int, steps: int,
            warmup: int = WARMUP_STEPS):
    """Measure train-step throughput. Returns (tokens_per_sec, final_loss, n).

    Shared by the headline run below and tools/bench_sweep.py so the two
    always use identical methodology: the ``steps`` training steps run
    inside ONE jitted ``lax.scan`` (donated carry, so params/opt-state
    update in place), timed around a hard host sync. ``warmup`` is kept
    for signature stability and must be >= 1: one untimed call of the
    same scanned runner absorbs compilation and settles the allocator.
    """
    if warmup < 1:
        raise ValueError("measure() needs warmup >= 1")
    devices = jax.devices()
    n = len(devices)
    mesh = build_mesh(_factor_mesh(n), devices=devices)

    params = shard_params(mesh, init_params(jax.random.PRNGKey(0), cfg))
    init_opt, train_step = make_train_step(
        cfg, mesh=mesh if cfg.needs_mesh else None
    )
    opt_state = init_opt(params)
    batch = shard_batch(
        mesh,
        jax.random.randint(
            jax.random.PRNGKey(1), (batch_per_device * n, seq + 1), 0,
            cfg.vocab, dtype=jnp.int32,
        ),
    )

    @functools.partial(jax.jit, donate_argnums=(0, 1), static_argnums=(3,))
    def run_steps(params, opt_state, batch, k):
        def body(carry, _):
            p, s = carry
            p, s, loss = train_step(p, s, batch)
            return (p, s), loss

        (params, opt_state), losses = lax.scan(
            body, (params, opt_state), None, length=k
        )
        return params, opt_state, losses[-1]

    # Warmup: compiles the k=steps runner and runs it TWICE, so a
    # one-time program-load cost on the first post-compile execution is
    # not billed to the timed run. float() forces a device->host
    # transfer — a hard sync even on backends whose block_until_ready
    # returns early.
    for _ in range(max(2, warmup - 1)):
        params, opt_state, loss = run_steps(params, opt_state, batch, steps)
        float(loss)

    # Best of 2 timed runs: the device-side work is identical, the host
    # round trip per dispatch is not.
    tokens = batch_per_device * n * seq * steps
    best = 0.0
    final_loss = float("nan")
    for _ in range(2):
        start = time.perf_counter()
        params, opt_state, loss = run_steps(params, opt_state, batch, steps)
        final_loss = float(loss)
        elapsed = time.perf_counter() - start
        best = max(best, tokens / elapsed)
    return best, final_loss, n


def measure_decode(cfg, batch: int, prompt_len: int, n_new: int):
    """Greedy decode throughput (contiguous cache): new tokens/sec."""
    params = init_params(jax.random.PRNGKey(0), cfg)
    prompt = jax.random.randint(
        jax.random.PRNGKey(1), (batch, prompt_len), 0, cfg.vocab,
        dtype=jnp.int32,
    )
    gen = jax.jit(
        lambda p, t: generate(p, t, cfg, n_new=n_new)
    )
    # Two warmups: compile, then absorb the slow first execution
    # (see measure()).
    float(gen(params, prompt).sum())
    float(gen(params, prompt).sum())
    # Best of 3: one decode run is short (~0.1 s), so single samples
    # carry the host's jitter.
    best = 0.0
    for _ in range(3):
        start = time.perf_counter()
        out = gen(params, prompt)
        float(out.sum())
        elapsed = time.perf_counter() - start
        best = max(best, batch * n_new / elapsed)
    return best


PAGED_SLOTS = 4
PAGED_PAGE_SIZE = 16
# The serving_window default: steps per dispatched decode scan. Round 5
# decoupled the window from page_size (VERDICT r4 #2) — one host round
# trip now amortizes over 64 greedy tokens, not 16, which is what keeps
# paged decode near its device rate when the round trip is long.
PAGED_WINDOW = 64


def measure_host_round_trip(samples: int = 20) -> float:
    """Host round trip per dispatch (ms): enqueue a trivial program and
    read its scalar result back.

    The per-step-sync serving numbers are bound by it by construction,
    so the bench reports it as a covariate — a paged steps/s figure is
    only interpretable next to the round trip it paid.
    """
    x = jnp.ones((4,), jnp.int32)
    f = jax.jit(lambda x: x + 1)
    y = f(x)
    np.asarray(y)  # compile
    y = f(y)
    np.asarray(y)  # absorb the slow first execution
    start = time.perf_counter()
    for _ in range(samples):
        y = f(y)
        np.asarray(y)
    return (time.perf_counter() - start) / samples * 1000.0


def _floored_window(window: int, remaining: int) -> int:
    """The serving loop's window discipline (serving._window_steps):
    bounded by what remains, floored to a power of two — ONE definition
    shared by every windowed bench leg so the benched plan is exactly
    the server's."""
    w = min(window, remaining)
    return 1 << (w.bit_length() - 1) if w > 1 else w


def _prefill_slots(cache, params, prompts):
    """Admit + prefill every slot, returning the pending tokens [slots]
    with a hard sync so prefill work stays out of the timed region."""
    slots, prompt_len = prompts.shape
    last = []
    for s in range(slots):
        cache.admit(s, prompt_len)
        last.append(cache.prefill(params, s, prompts[s]))
    tokens = jnp.argmax(jnp.stack(last), axis=-1).astype(jnp.int32)
    float(tokens.sum())
    return tokens


def _best_time(run, cache, warmups: int = 3, reps: int = 3) -> float:
    """Warm (compile + the slow first execution + settle), then
    best-of-``reps`` — the paged benches' shared harness."""
    for _ in range(warmups):
        run(cache)
    return min(run(cache) for _ in range(reps))


def measure_paged_decode(cfg, slots: int, prompt_len: int, n_new: int,
                         page_size: int, window: int = PAGED_WINDOW):
    """Continuous-batching decode: (tokens/s, steps/s, hostloop steps/s).

    VERDICT r2 #5 added the paged measurement; VERDICT r3 #2 moved the
    production loop onto device-side windows; VERDICT r4 #2 widened the
    window past page_size. All ``slots`` sequences are admitted +
    prefilled (full occupancy — the server's steady state under load),
    then ``n_new`` decode steps run exactly as the serving loop runs
    them for greedy traffic: ``cache.step_window`` scans up to
    ``window`` steps per dispatch (power-of-two floored, the server's
    program-set discipline) with on-device argmax feedback, one host
    transfer per window. The third number re-times the same steps
    through per-step ``cache.step`` dispatches — the path sampled slots
    still take, and the round-3 baseline the window is measured against.
    """
    from kvedge_tpu.models.kvcache import PagedKVCache

    params = init_params(jax.random.PRNGKey(0), cfg)
    pages = slots * -(-(prompt_len + n_new) // page_size)
    prompts = jax.random.randint(
        jax.random.PRNGKey(2), (slots, prompt_len), 0, cfg.vocab,
        dtype=jnp.int32,
    )

    def run_windowed(cache) -> float:
        """The production greedy path: multi-page device windows
        (power-of-two floored at the remaining budget, exactly the
        server's _window_steps discipline), one host transfer of the
        window's tokens per dispatch — what the serving loop consumes
        to emit tokens and check budgets."""
        tokens = _prefill_slots(cache, params, prompts)
        start = time.perf_counter()
        remaining = n_new
        while remaining:
            w = _floored_window(window, remaining)
            produced = cache.step_window(params, tokens, w)
            np.asarray(produced)  # the serving loop emits these
            tokens = produced[w - 1]
            remaining -= w
        elapsed = time.perf_counter() - start
        for s in range(slots):
            cache.release(s)
        return elapsed

    def run_overlap(cache) -> float:
        """The overlapped (double-buffered) serving loop
        (serving_overlap, SERVING.md rung 16): window N+1 is enqueued
        on the device-resident carry BEFORE window N's tokens are
        fetched, so N's harvest transfer and host-side processing hide
        under N+1's device execution. Steps/s should approach
        1/max(R, W*t) where the serial windowed leg pays
        1/(R + W*t) per window — the win grows with the host round
        trip R and vanishes (ratio -> 1) when R << W*t."""
        tokens = _prefill_slots(cache, params, prompts)
        start = time.perf_counter()
        remaining = n_new
        w = _floored_window(window, remaining)
        inflight = cache.dispatch_window(params, tokens, w)
        remaining -= w
        while inflight is not None:
            nxt = None
            if remaining:
                w = _floored_window(window, remaining)
                nxt = cache.dispatch_window(params, None, w)
                remaining -= w
            # the serving loop emits these while the next window runs
            np.asarray(cache.harvest_window(inflight))
            inflight = nxt
        elapsed = time.perf_counter() - start
        cache.drop_carry()
        for s in range(slots):
            cache.release(s)
        return elapsed

    def run_hostloop(cache) -> float:
        """Per-step dispatch WITH the per-step host read the serving
        loop performs (the sampled-era baseline the window is measured
        against). Runs the loop the server actually runs for an
        all-greedy per-step batch — ``cache.step_tokens``, the fused
        step+argmax program serving._loop_once dispatches — so the
        read is [slots] ints, not [slots, V] logits plus a second
        argmax dispatch. Still one round trip and one forced read per
        token: an async loop that never fetches would look much faster
        here and would not be a loop the server can run, because it
        needs every token on the host to emit and to check budgets."""
        tokens = _prefill_slots(cache, params, prompts)
        start = time.perf_counter()
        for _ in range(n_new):
            tokens = cache.step_tokens(params, tokens)
            np.asarray(tokens)  # the serving loop emits these
        elapsed = time.perf_counter() - start
        for s in range(slots):
            cache.release(s)
        return elapsed

    cache = PagedKVCache(
        cfg, slots=slots, pages=pages, page_size=page_size
    )
    best = _best_time(run_windowed, cache)
    best_host = _best_time(run_hostloop, cache)
    best_overlap = _best_time(run_overlap, cache)
    return (slots * n_new / best, n_new / best, n_new / best_host,
            slots * n_new / best_overlap, best / best_overlap)


def measure_paged_mixed(cfg, slots: int, prompt_len: int, n_new: int,
                        page_size: int, window: int = PAGED_WINDOW):
    """Windowed decode with ONE sampled co-tenant in the batch
    (tokens/s): the round-5 on-device sampling path
    (kvcache.step_window_sampled). Before it, a single sampled request
    forced the whole batch onto per-step dispatch — the
    ``paged_decode_hostloop_steps_per_sec`` regime; now the mixed batch
    rides the same window cadence as all-greedy, so this number should
    sit near ``paged_decode_tokens_per_sec`` instead of collapsing to
    the host-loop rate."""
    from kvedge_tpu.models.kvcache import PagedKVCache

    params = init_params(jax.random.PRNGKey(0), cfg)
    pages = slots * -(-(prompt_len + n_new) // page_size)
    prompts = jax.random.randint(
        jax.random.PRNGKey(2), (slots, prompt_len), 0, cfg.vocab,
        dtype=jnp.int32,
    )
    from kvedge_tpu.models.serving import _raw_key_data

    seed = jax.random.fold_in(jax.random.PRNGKey(11), 0)
    raw = _raw_key_data(seed)
    key_data = np.zeros((slots,) + raw.shape, np.uint32)
    key_data[0] = raw  # slot 0 samples; the rest decode greedy
    smask = np.zeros((slots,), bool)
    smask[0] = True
    temps = np.ones((slots,), np.float32)
    temps[0] = 0.8
    top_ps = np.ones((slots,), np.float32)
    top_ps[0] = 0.9

    def run(cache) -> float:
        tokens = np.asarray(_prefill_slots(cache, params, prompts))
        start = time.perf_counter()
        done = 0
        while done < n_new:
            w = _floored_window(window, n_new - done)
            base = np.full((slots,), done + 1, np.int32)
            produced = cache.step_window_sampled(
                params, tokens, w, None, key_data, base, temps,
                top_ps, smask,
            )
            produced = np.asarray(produced)
            tokens = produced[w - 1]
            done += w
        elapsed = time.perf_counter() - start
        for s in range(slots):
            cache.release(s)
        return elapsed

    cache = PagedKVCache(
        cfg, slots=slots, pages=pages, page_size=page_size
    )
    return slots * n_new / _best_time(run, cache)


def measure_paged_spec(cfg, slots: int, prompt_len: int, n_new: int,
                       page_size: int, draft_len: int,
                       adversarial: bool = False):
    """Batched speculative decoding through the paged cache (round 4's
    serving_speculative mode): (tokens/s, emitted_per_pass).

    All ``slots`` sequences admit REPETITIVE prompts (prompt-lookup
    drafting's favorable case, matching measure_speculative's input so
    the two capabilities are comparable), then the serving loop's spec
    schedule runs: host drafts per slot, ONE (1+draft_len)-query verify
    pass for the batch per dispatch, up to draft_len+1 tokens per slot
    per pass. One dispatch + one host read per pass — the same
    RTT-per-pass profile as the windowed path at window≈emitted.

    ``adversarial=True`` (VERDICT r4 #8) feeds RANDOM prompts instead —
    prompt-lookup's worst case, acceptance ≈ 0 — so the committed
    evidence brackets both ends: the favorable number is the mode's
    headroom, the adversarial one is the pure verify-pass overhead a
    mixed-traffic operator pays when drafts never land."""
    import types

    from kvedge_tpu.models.kvcache import PagedKVCache
    from kvedge_tpu.models.serving import PagedGenerationServer

    params = init_params(jax.random.PRNGKey(0), cfg)
    mpps = -(-(prompt_len + n_new + draft_len) // page_size)
    if adversarial:
        prompt = jax.random.randint(
            jax.random.PRNGKey(5), (prompt_len,), 0, cfg.vocab,
            dtype=jnp.int32,
        )
    else:
        pattern = jax.random.randint(
            jax.random.PRNGKey(3), (1, 16), 0, cfg.vocab,
            dtype=jnp.int32,
        )
        prompt = jnp.tile(pattern, (1, prompt_len // 16))[0]

    def run(cache) -> tuple[float, float]:
        reqs = []
        tokens0 = []
        for s in range(slots):
            cache.admit(s, prompt_len)
            logits = cache.prefill(params, s, prompt)
            reqs.append(types.SimpleNamespace(
                prompt=[int(t) for t in np.asarray(prompt)],
                generated=[], next_token=int(jnp.argmax(logits)),
            ))
            tokens0.append(reqs[-1].next_token)
        float(jnp.asarray(tokens0).sum())  # sync prefill out of timing
        passes = 0
        start = time.perf_counter()
        active = np.ones((slots,), bool)
        spec_mask = np.ones((slots,), bool)
        while any(len(r.generated) < n_new for r in reqs):
            tokens = np.zeros((slots, draft_len + 1), np.int32)
            for s, r in enumerate(reqs):
                if not active[s]:
                    continue
                tokens[s, 0] = r.next_token
                tokens[s, 1:] = PagedGenerationServer._draft(
                    r, draft_len
                )
            emitted, accepted, _ = cache.step_spec(
                params, tokens, active=active, spec_mask=spec_mask
            )
            emitted = np.asarray(emitted)
            passes += 1
            for s, r in enumerate(reqs):
                if not active[s]:
                    continue
                a = int(accepted[s])
                seq = [r.next_token] + [int(t) for t in emitted[s, :a]]
                room = n_new - len(r.generated)
                r.generated.extend(seq[:room])
                r.next_token = (seq[room] if room < len(seq)
                                else int(emitted[s, a]))
                if len(r.generated) >= n_new:
                    # Deactivate finished rows, matching the serving
                    # loop: they must stop advancing device lengths, or
                    # heterogeneous-prompt runs would eventually hit
                    # max_pages_per_seq (and skew the timing).
                    active[s] = False
                    spec_mask[s] = False
        elapsed = time.perf_counter() - start
        for s in range(slots):
            cache.release(s)
        return elapsed, slots * n_new / passes / slots

    cache = PagedKVCache(
        cfg, slots=slots, pages=slots * mpps, page_size=page_size,
        max_pages_per_seq=mpps,
    )
    for _ in range(3):
        run(cache)
    results = [run(cache) for _ in range(3)]
    best = min(r[0] for r in results)
    return slots * n_new / best, results[0][1]


def measure_paged_spec_window(cfg, slots: int, prompt_len: int,
                              n_new: int, page_size: int,
                              draft_len: int, window: int):
    """Device-resident speculative windows (SERVING.md rung 20):
    (tokens/s, emitted_per_window).

    Same favorable repetitive input as measure_paged_spec, but the
    draft + verify + commit loop runs ON DEVICE: one dispatch carries
    ``window`` passes (n-gram drafting over a device-resident context,
    accept/reject, KV commit, budget freezing), pipelined two-deep so
    the harvest round trip hides under the next window's execution.
    Where the legacy leg pays one host RTT per verify pass (~1+accept
    tokens), this one pays ~one RTT per window — up to window*(1+K)
    tokens — which is exactly the amortization the spec-mode economics
    probe prices. The emitted tokens are bit-identical to the legacy
    path (pinned by tests/test_spec_window.py); this leg is the
    throughput half of that claim."""
    from kvedge_tpu.models.kvcache import PagedKVCache

    params = init_params(jax.random.PRNGKey(0), cfg)
    mpps = -(-(prompt_len + n_new + draft_len) // page_size)
    pattern = jax.random.randint(
        jax.random.PRNGKey(3), (1, 16), 0, cfg.vocab, dtype=jnp.int32,
    )
    prompt = jnp.tile(pattern, (1, prompt_len // 16))[0]
    prompt_host = [int(t) for t in np.asarray(prompt)]
    s_ctx = prompt_len + n_new + draft_len + 2

    def run(cache) -> tuple[float, float]:
        pend = np.zeros((slots,), np.int32)
        generated = [[] for _ in range(slots)]
        for s in range(slots):
            cache.admit(s, prompt_len)
            logits = cache.prefill(params, s, prompt)
            pend[s] = int(jnp.argmax(logits))
        ctx = np.zeros((slots, s_ctx), np.int32)
        ctx_len = np.zeros((slots,), np.int32)
        for s in range(slots):
            seq = prompt_host + [int(pend[s])]
            ctx[s, :len(seq)] = seq
            ctx_len[s] = len(seq)
        inflight = np.zeros((slots,), np.int64)
        pending_handles = []
        windows = 0
        start = time.perf_counter()

        def budgets_now():
            return np.array(
                [max(n_new - len(generated[s]) - int(inflight[s]), 0)
                 for s in range(slots)], np.int32,
            )

        def harvest_oldest():
            handle = pending_handles.pop(0)
            emitted, counts, _ = cache.harvest_spec_window(handle)
            inflight[:] -= np.asarray(handle["caps"], np.int64)
            for s in range(slots):
                for p in range(window):
                    c = int(counts[p, s])
                    if c == 0:
                        continue
                    seq = [int(pend[s])] + [int(t)
                                            for t in emitted[p, s, :c - 1]]
                    room = n_new - len(generated[s])
                    generated[s].extend(seq[:room])
                    pend[s] = int(emitted[p, s, c - 1])

        first = True
        while any(len(g) < n_new for g in generated):
            budgets = budgets_now()
            if budgets.sum() > 0 and len(pending_handles) < 2:
                handle = cache.dispatch_spec_window(
                    params, pend if first else None, window, draft_len,
                    budgets,
                    **({"ctx": ctx, "ctx_len": ctx_len} if first
                       else {}),
                )
                inflight[:] += np.asarray(handle["caps"], np.int64)
                pending_handles.append(handle)
                windows += 1
                first = False
                continue
            harvest_oldest()
        while pending_handles:
            harvest_oldest()
        elapsed = time.perf_counter() - start
        for s in range(slots):
            cache.release(s)
        cache.drop_carry()
        return elapsed, slots * n_new / windows / slots

    cache = PagedKVCache(
        cfg, slots=slots, pages=slots * mpps, page_size=page_size,
        max_pages_per_seq=mpps,
    )
    for _ in range(3):
        run(cache)
    results = [run(cache) for _ in range(3)]
    best = min(r[0] for r in results)
    return slots * n_new / best, results[0][1]


# Overload leg (SERVING.md rung 17): 2 clients per slot, half batch
# (arriving first, owning every slot) and half interactive (a burst
# released the moment batch holds all slots — event-driven, so the
# contention happens at any machine speed). Batch jobs run 2x the
# interactive budget (they are the long co-tenants the scheduler
# exists to preempt); window 16 keeps preemption boundaries
# fine-grained.
SCHED_OVERLOAD_FACTOR = 2
SCHED_OVERLOAD_N_NEW = 64
SCHED_OVERLOAD_WINDOW = 16


def _hist_quantile(snap: dict, q: float) -> float:
    """Quantile estimate from a scheduler _Hist snapshot (Prometheus
    shape: ``le`` edges, per-bucket counts, last slot = +Inf).
    Conservative by construction — returns the upper edge of the bucket
    holding the q-th observation, so "p99 <= x" is literally true of
    the recorded waits."""
    counts = snap["counts"]
    edges = snap["edges"]
    total = sum(counts)
    if total == 0:
        return 0.0
    acc = 0
    for i, c in enumerate(counts):
        acc += c
        if acc >= q * total:
            return edges[i] if i < len(edges) else edges[-1]
    return edges[-1]


def measure_sched_overload(cfg, slots: int, prompt_len: int, n_new: int,
                           page_size: int) -> tuple[dict, dict]:
    """The rung-17 scheduler under 2x slot oversubscription, through the
    REAL server (queue wait and preemption are serving-layer behaviors;
    a cache-level harness would measure nothing). The same workload runs
    twice — ``sched_policy="fifo"`` with no swap budget (the pre-rung-17
    admission behavior) and ``"strict"`` with preemptive swap — and each
    run reports per-class queue-wait p50/p99 ms (from the server's own
    admission histograms), preemption count, and goodput (completed
    tokens per wall-clock second). The acceptance signal: interactive
    p99 under "strict" must come in BELOW "fifo", because strict admits
    the interactive burst by swapping batch tenants to host at the next
    window boundary instead of making it wait out their full budgets.

    Two wait measurements per class, deliberately redundant (rung 26's
    strict-vs-fifo diagnosis): ``*_wait_p{50,99}_ms`` come from the
    server's fixed-bucket admission histograms — a quantile there is
    the BUCKET UPPER EDGE, so past 10 s the edges quantize to 30/60/
    120 s and adjacent runs can report 3x apart while the true waits
    differ by percent. ``*_ttft_p{50,99}_ms`` are exact client-side
    first-token latencies (submit call to first streamed token), no
    bucketing, measured through the same streaming path a frontend
    uses. Disagreement between the two columns is bucket-quantization
    artifact, not scheduler behavior.

    Returns ``(fifo_metrics, strict_metrics)`` dicts."""
    import threading

    from kvedge_tpu.models.serving import PagedGenerationServer

    params = init_params(jax.random.PRNGKey(0), cfg)
    n_clients = SCHED_OVERLOAD_FACTOR * slots
    batch_n_new = 2 * n_new
    pages = slots * -(-(prompt_len + batch_n_new) // page_size)
    rng = np.random.default_rng(7)
    prompts = rng.integers(
        0, cfg.vocab, size=(n_clients, prompt_len)
    ).astype(np.int32)

    def run(policy: str) -> dict:
        server = PagedGenerationServer(
            params, cfg, slots=slots, pages=pages, page_size=page_size,
            prefix_cache=False, window=SCHED_OVERLOAD_WINDOW,
            sched_policy=policy,
            sched_swap_budget_mb=(256 if policy != "fifo" else 0),
        )
        lock = threading.Lock()
        tokens_done = [0]
        ttft_ms: dict[str, list[float]] = {"interactive": [], "batch": []}
        errors: list[Exception] = []

        def client(ci: int, pclass: str, budget: int) -> None:
            try:
                t_submit = time.perf_counter()
                stream = server.submit_stream(
                    [int(t) for t in prompts[ci]], budget,
                    timeout=600.0, priority=pclass)
                first = None
                for tok in stream:
                    if first is None:
                        first = time.perf_counter()
            except Exception as e:  # pragma: no cover - fail loudly
                errors.append(e)
                return
            with lock:
                tokens_done[0] += budget
                if first is not None:
                    ttft_ms[pclass].append((first - t_submit) * 1e3)

        batch_threads = [
            threading.Thread(target=client,
                             args=(ci, "batch", batch_n_new),
                             daemon=True)
            for ci in range(n_clients // 2)
        ]
        inter_threads = [
            threading.Thread(target=client,
                             args=(ci, "interactive", n_new),
                             daemon=True)
            for ci in range(n_clients // 2, n_clients)
        ]
        start = time.perf_counter()
        for t in batch_threads:
            t.start()
        # Release the interactive burst the moment batch owns every
        # slot — event-driven, so contention is guaranteed whether a
        # batch job takes 50 ms or 50 s on this device.
        deadline = start + 120.0
        while (server.stats()["free_slots"] > 0
               and time.perf_counter() < deadline):
            time.sleep(0.001)
        for t in inter_threads:
            t.start()
        for t in batch_threads + inter_threads:
            t.join()
        elapsed = time.perf_counter() - start
        stats = server.stats()
        server.close()
        if errors:
            raise errors[0]
        wait_i = stats["sched_queue_wait_ms_interactive"]
        wait_b = stats["sched_queue_wait_ms_batch"]

        def _exact(xs: list[float], q: float) -> float:
            return float(np.percentile(np.asarray(xs), 100 * q)) if xs \
                else 0.0

        return {
            "goodput_tokens_per_sec": tokens_done[0] / elapsed,
            "interactive_wait_p50_ms": _hist_quantile(wait_i, 0.50),
            "interactive_wait_p99_ms": _hist_quantile(wait_i, 0.99),
            "batch_wait_p50_ms": _hist_quantile(wait_b, 0.50),
            "batch_wait_p99_ms": _hist_quantile(wait_b, 0.99),
            "interactive_ttft_p50_ms": _exact(ttft_ms["interactive"], .50),
            "interactive_ttft_p99_ms": _exact(ttft_ms["interactive"], .99),
            "batch_ttft_p50_ms": _exact(ttft_ms["batch"], 0.50),
            "batch_ttft_p99_ms": _exact(ttft_ms["batch"], 0.99),
            "preemptions": int(stats["sched_preemptions_total"]),
        }

    # Warmup run compiles the full program set BOTH measured runs need —
    # prefill, the window ladder, and (because the warmup itself runs
    # the scheduler and preempts) the swap gather/scatter. Without it
    # the strict run's first preemption pays the swap compile inside an
    # interactive admission wait, and the leg measures XLA compile
    # time, not scheduling.
    run("strict")
    return run("fifo"), run("strict")


# Open-loop arrivals (SERVING.md rung 21): requests land on the server's
# clock, not the completion loop's — the way a production frontend sees
# traffic. The overload leg above is CLOSED-loop (every client re-enters
# the queue the moment it finishes), which measures scheduling shape but
# cannot show the capacity scaling curve: at 4 slots and at 256 the
# closed population self-limits. Here the SAME Poisson/trace arrival
# schedule replays against several slot capacities (bucketed compile
# cache on, min_bucket 4), and goodput + p99 queue wait diverge exactly
# where capacity runs out.
OPENLOOP_CAPACITIES = (4, 64, 256)
OPENLOOP_REQUESTS = 32
OPENLOOP_N_NEW = 32
OPENLOOP_WINDOW = 16
OPENLOOP_MIN_BUCKET = 4
OPENLOOP_BURST = 8  # trace-replay: bursts of 8 at the same mean rate


def _hist_delta_quantile(before: dict, after: dict, q: float) -> float:
    """``_hist_quantile`` over the observations one leg ADDED to a
    cumulative histogram (the server instance persists across legs so
    compiled programs are reused; the stats must not)."""
    counts = [a - b for b, a in zip(before["counts"], after["counts"],
                                    strict=True)]
    return _hist_quantile({"counts": counts, "edges": after["edges"]}, q)


def _openloop_offsets(mode: str, n: int, rate: float,
                      rng: np.random.Generator) -> np.ndarray:
    """Arrival offsets (seconds from leg start) for ``n`` requests at
    mean ``rate`` req/s. ``poisson`` = exponential inter-arrivals;
    ``trace`` = a deterministic bursty trace (bursts of OPENLOOP_BURST
    released together, burst starts evenly spaced at the same mean
    rate) — the adversarial arrival shape a smooth-rate model misses."""
    if mode == "poisson":
        return np.cumsum(rng.exponential(1.0 / rate, size=n))
    period = OPENLOOP_BURST / rate
    return np.array([(i // OPENLOOP_BURST) * period for i in range(n)])


def measure_openloop(cfg, prompt_len: int, page_size: int,
                     capacities=OPENLOOP_CAPACITIES) -> dict:
    """Goodput and p99 queue wait vs slot capacity under ONE open-loop
    arrival schedule.

    Per capacity C: a server with ``slots=C``, an auto-sized page pool,
    and the bucketed compile cache (``min_bucket=4`` — programs compile
    per power-of-two row bucket on demand, so C=256 never compiles a
    256-row program for 32 residents). Rates are calibrated from the
    measured 4-slot closed-loop service rate ``rho4``: a "low" rate the
    smallest capacity can clear (0.75 rho4) and a "high" rate it cannot
    (3 rho4) — at the high rate the backlog caps 4-slot goodput at its
    service ceiling while larger capacities absorb the same schedule,
    which IS the scaling curve this leg exists to publish. Trace-replay
    runs the bursty schedule at the high rate. Returns
    ``{rates: {low, high}, legs: {(capacity, mode, rate_name): {...}}}``
    with goodput (completed tokens / wall s from leg start to last
    completion) and queue-wait p50/p99 ms per leg."""
    import threading

    from kvedge_tpu.models.serving import PagedGenerationServer

    params = init_params(jax.random.PRNGKey(0), cfg)
    n_new = OPENLOOP_N_NEW
    mpps = -(-(prompt_len + n_new) // page_size)
    rng = np.random.default_rng(11)
    prompts = rng.integers(
        0, cfg.vocab, size=(OPENLOOP_REQUESTS, prompt_len)
    ).astype(np.int32)

    def burst(server, n, budget) -> float:
        """Closed-loop burst of ``n`` concurrent requests; returns the
        wall seconds the burst took."""
        errors: list[Exception] = []

        def client(ci: int) -> None:
            try:
                server.submit([int(t) for t in prompts[ci % len(prompts)]],
                              budget, timeout=600.0)
            except Exception as e:  # pragma: no cover - fail loudly
                errors.append(e)

        threads = [threading.Thread(target=client, args=(ci,), daemon=True)
                   for ci in range(n)]
        start = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errors:
            raise errors[0]
        return time.perf_counter() - start

    def make_server(slots: int) -> PagedGenerationServer:
        return PagedGenerationServer(
            params, cfg, slots=slots, pages=slots * mpps,
            page_size=page_size, prefix_cache=False,
            window=OPENLOOP_WINDOW,
            min_bucket=min(OPENLOOP_MIN_BUCKET, slots),
        )

    # Rate calibration: rho4 = the 4-slot service rate in requests/s,
    # measured closed-loop AFTER a compile warmup burst.
    cal = make_server(4)
    burst(cal, 4, n_new)            # compile warmup (prefill + windows)
    round_s = burst(cal, 4, n_new)  # measured service round
    cal.close()
    rho4 = 4.0 / round_s
    rates = {"low": 0.75 * rho4, "high": 3.0 * rho4}

    legs: dict[tuple, dict] = {}
    for cap in capacities:
        server = make_server(cap)
        # Warmup walks the whole bucket ladder at the leg's budget so
        # every program the measured legs can touch — per-bucket
        # prefill and the window shapes n_new implies — is compiled up
        # front. Bottom-up matters: the pool steps DOWN to min_bucket
        # when idle, so a leg may start at any rung and the arrival
        # schedule would otherwise pay XLA compile inside queue waits.
        peak = min(cap, OPENLOOP_REQUESTS)
        rung = min(OPENLOOP_MIN_BUCKET, cap)
        while True:
            burst(server, min(rung, peak), n_new)
            if rung >= peak:
                break
            rung = min(rung * 2, cap)
        try:
            for mode, rate_name in (("poisson", "low"),
                                    ("poisson", "high"),
                                    ("trace", "high")):
                rate = rates[rate_name]
                offsets = _openloop_offsets(
                    mode, OPENLOOP_REQUESTS, rate,
                    np.random.default_rng(13),
                )
                before = server.stats()["queue_ms"]
                lock = threading.Lock()
                tokens_done = [0]
                errors: list[Exception] = []

                def client(ci: int) -> None:
                    try:
                        server.submit(
                            [int(t) for t in prompts[ci]], n_new,
                            timeout=600.0,
                        )
                    except Exception as e:  # pragma: no cover
                        errors.append(e)
                        return
                    with lock:
                        tokens_done[0] += n_new

                threads = [
                    threading.Thread(target=client, args=(ci,),
                                     daemon=True)
                    for ci in range(OPENLOOP_REQUESTS)
                ]
                start = time.perf_counter()
                for ci, t in enumerate(threads):
                    # Open loop: the arrival clock never waits for the
                    # server — a late completion only deepens the queue.
                    lag = start + offsets[ci] - time.perf_counter()
                    if lag > 0:
                        time.sleep(lag)
                    t.start()
                for t in threads:
                    t.join()
                elapsed = time.perf_counter() - start
                if errors:
                    raise errors[0]
                after = server.stats()["queue_ms"]
                legs[(cap, mode, rate_name)] = {
                    "goodput_tokens_per_sec": tokens_done[0] / elapsed,
                    "wait_p50_ms": _hist_delta_quantile(
                        before, after, 0.50),
                    "wait_p99_ms": _hist_delta_quantile(
                        before, after, 0.99),
                    "bucket_final": server.stats()["bucket"],
                }
        finally:
            server.close()
    return {"rates": rates, "legs": legs}


PREFIX_SYS_TOKENS = 64   # the common system prompt (4 full pages)
PREFIX_TAIL_TOKENS = 16  # per-request unique user suffix
PREFIX_TURN1 = 8         # turn-1 conversations (warmup + replay base)
PREFIX_CAL = 4           # calibration burst after compile warmup
PREFIX_REQUESTS = 24     # measured open-loop arrivals
PREFIX_N_NEW = 16
PREFIX_SLOTS = 8


def measure_prefix_openloop(cfg, page_size: int) -> dict:
    """Shared-prefix serving (SERVING.md rung 24): ONE open-loop
    arrival schedule — every prompt opens with a common 64-token
    system prompt, and every second arrival is a multi-turn replay
    embedding a full turn-1 transcript — replayed on two identical
    servers, ``prefix_cache`` off then on. Same offsets, same prompts,
    greedy: the radix cache may only change WHERE prompt K/V comes
    from, so the leg asserts every emitted stream is bit-identical
    across the two runs and reports what the cache bought — prefill
    tokens saved (registered system-prompt pages for fresh arrivals,
    prompt AND generated pages for replays) and the TTFT p50/p99
    shift at the same arrival rate."""
    import threading

    from kvedge_tpu.models.serving import PagedGenerationServer

    params = init_params(jax.random.PRNGKey(0), cfg)
    n_new = PREFIX_N_NEW
    rng = np.random.default_rng(17)
    sys_prompt = [int(t) for t in
                  rng.integers(0, cfg.vocab, PREFIX_SYS_TOKENS)]
    tails = rng.integers(
        0, cfg.vocab,
        size=(PREFIX_TURN1 + PREFIX_CAL + PREFIX_REQUESTS,
              PREFIX_TAIL_TOKENS),
    )
    t1_prompts = [sys_prompt + [int(t) for t in tails[i]]
                  for i in range(PREFIX_TURN1)]
    # Worst-case request: a replay's transcript prompt plus its budget.
    longest = (PREFIX_SYS_TOKENS + PREFIX_TAIL_TOKENS + n_new
               + PREFIX_TAIL_TOKENS + n_new)
    mpps = -(-longest // page_size)
    offsets: np.ndarray | None = None
    rate = [0.0]

    def burst(server, prompts, outs=None) -> float:
        errors: list[Exception] = []

        def client(ci: int) -> None:
            try:
                got = server.submit(prompts[ci], n_new, timeout=600.0)
                if outs is not None:
                    outs[ci] = got
            except Exception as e:  # pragma: no cover - fail loudly
                errors.append(e)

        threads = [threading.Thread(target=client, args=(ci,),
                                    daemon=True)
                   for ci in range(len(prompts))]
        start = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errors:
            raise errors[0]
        return time.perf_counter() - start

    def run(prefix_on: bool) -> dict:
        nonlocal offsets
        server = PagedGenerationServer(
            params, cfg, slots=PREFIX_SLOTS,
            pages=PREFIX_SLOTS * mpps, page_size=page_size,
            prefix_cache=prefix_on, window=OPENLOOP_WINDOW,
            min_bucket=min(OPENLOOP_MIN_BUCKET, PREFIX_SLOTS),
        )
        try:
            # Turn 1 (closed loop, unmeasured): compiles every program
            # the measured leg touches and produces the transcripts
            # the replay arrivals embed.
            warm: dict[int, list[int]] = {}
            burst(server, t1_prompts, warm)
            # Rate calibration on a post-compile burst; the offsets
            # computed on the FIRST (cache-off) run are reused verbatim
            # for the cache-on run — same schedule, same rate.
            cal_prompts = [
                sys_prompt + [int(t) for t in tails[PREFIX_TURN1 + i]]
                for i in range(PREFIX_CAL)
            ]
            cal_s = burst(server, cal_prompts)
            if offsets is None:
                rate[0] = 1.5 * PREFIX_CAL / cal_s
                offsets = np.cumsum(np.random.default_rng(13).exponential(
                    1.0 / rate[0], size=PREFIX_REQUESTS))
            prompts = []
            for ci in range(PREFIX_REQUESTS):
                tail = [int(t) for t in
                        tails[PREFIX_TURN1 + PREFIX_CAL + ci]]
                if ci % 2:
                    # Multi-turn replay: the full turn-1 transcript
                    # (prompt + generated) plus a fresh follow-up.
                    prompts.append(warm[ci % PREFIX_TURN1] + tail)
                else:
                    prompts.append(sys_prompt + tail)
            base = server.stats()
            emitted: dict[int, list[int]] = {}
            errors: list[Exception] = []

            def client(ci: int) -> None:
                try:
                    emitted[ci] = server.submit(prompts[ci], n_new,
                                                timeout=600.0)
                except Exception as e:  # pragma: no cover
                    errors.append(e)

            threads = [threading.Thread(target=client, args=(ci,),
                                        daemon=True)
                       for ci in range(PREFIX_REQUESTS)]
            start = time.perf_counter()
            for ci, t in enumerate(threads):
                lag = start + offsets[ci] - time.perf_counter()
                if lag > 0:
                    time.sleep(lag)
                t.start()
            for t in threads:
                t.join()
            elapsed = time.perf_counter() - start
            if errors:
                raise errors[0]
            st = server.stats()
            return {
                "warm": warm,
                "emitted": emitted,
                "goodput_tokens_per_sec":
                    PREFIX_REQUESTS * n_new / elapsed,
                "ttft_p50_ms": _hist_delta_quantile(
                    base["ttft_ms"], st["ttft_ms"], 0.50),
                "ttft_p99_ms": _hist_delta_quantile(
                    base["ttft_ms"], st["ttft_ms"], 0.99),
                "prompt_tokens": sum(len(p) for p in prompts),
                "prefill_tokens_saved":
                    st["prefix_tokens_saved"]
                    - base["prefix_tokens_saved"],
                "prefix_hits": st["prefix_hits"] - base["prefix_hits"],
                "cow_copies": st["prefix_cow_copies"],
                "bytes_saved": st["prefix_bytes_saved"],
            }
        finally:
            server.close()

    off = run(False)
    on = run(True)
    # The whole point: reuse changes cost, never content.
    for ci in range(PREFIX_TURN1):
        if off["warm"][ci] != on["warm"][ci]:
            raise RuntimeError(
                f"prefix cache changed turn-1 stream {ci}")
    for ci in range(PREFIX_REQUESTS):
        if off["emitted"][ci] != on["emitted"][ci]:
            raise RuntimeError(
                f"prefix cache changed emitted stream {ci}")
    for leg in (off, on):
        del leg["warm"], leg["emitted"]
    return {
        "requests": PREFIX_REQUESTS,
        "rate_req_per_sec": rate[0],
        "saved_frac": on["prefill_tokens_saved"] / on["prompt_tokens"],
        "bit_identical": True,
        "off": off,
        "on": on,
    }


def measure_trace_overhead(cfg, slots: int, prompt_len: int, n_new: int,
                           page_size: int) -> tuple[float, float]:
    """The rung-18 tracing bill on the paged decode leg, through the
    REAL server (the spans live under the serving work lock and in the
    decode loop — a cache-level harness would measure nothing). The
    same fully-loaded decode runs twice, ``serving_trace`` off then on
    (sample 1.0 — every request traced, the worst case), and the pair
    prices the flight recorder: each span is one deque append of a
    plain tuple, so the delta should be noise (< 5%, pinned by the
    tracing design contract).

    Returns ``(tokens_per_sec_off, tokens_per_sec_on)``."""
    import threading

    from kvedge_tpu.models.serving import PagedGenerationServer
    from kvedge_tpu.runtime.tracing import Tracer

    params = init_params(jax.random.PRNGKey(0), cfg)
    pages = slots * -(-(prompt_len + n_new) // page_size)
    rng = np.random.default_rng(11)
    prompts = rng.integers(
        0, cfg.vocab, size=(slots, prompt_len)
    ).astype(np.int32)

    def run(tracer) -> float:
        server = PagedGenerationServer(
            params, cfg, slots=slots, pages=pages, page_size=page_size,
            prefix_cache=False, window=PAGED_WINDOW, tracer=tracer,
        )
        errors: list[Exception] = []

        def client(ci: int) -> None:
            try:
                server.submit([int(t) for t in prompts[ci]], n_new,
                              timeout=600.0,
                              request_id=f"bench-trace-{ci}")
            except Exception as e:  # pragma: no cover - fail loudly
                errors.append(e)

        threads = [
            threading.Thread(target=client, args=(ci,), daemon=True)
            for ci in range(slots)
        ]
        start = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        elapsed = time.perf_counter() - start
        server.close()
        if errors:
            raise errors[0]
        return slots * n_new / elapsed

    # Warmup compiles the program set both measured runs share (jit
    # caches by shape, process-wide) — without it the off run would
    # eat the compile and flatter the traced run. Each mode then takes
    # its best of three INTERLEAVED rounds: a single ~1 s decode run is
    # at the mercy of scheduler/GC transients bigger than the effect
    # being measured, and interleaving decorrelates slow host drift
    # from the off/on comparison.
    run(None)
    off = on = 0.0
    for _ in range(3):
        off = max(off, run(None))
        on = max(on, run(Tracer(sample=1.0)))
    return off, on


def measure_obs_overhead(cfg, slots: int, prompt_len: int, n_new: int,
                         page_size: int) -> tuple[float, float]:
    """The rung-25 observability bill on the paged decode leg: the
    same fully-loaded decode through the REAL server with the whole
    stack off, then EVERYTHING on at once — full-sample tracing, the
    SLO engine (snapshots every boundary the throttle admits), and the
    occupancy timeline ring. Each boundary's marginal work is three
    ``_Hist.snapshot()`` copies plus a deque append of O(1) gauges, so
    the design contract is < 5% (pinned by tests/test_slo.py on the
    checked-in bench doc).

    Returns ``(tokens_per_sec_off, tokens_per_sec_on)``."""
    import threading

    from kvedge_tpu.models.serving import PagedGenerationServer
    from kvedge_tpu.runtime.slo import SloObjectives
    from kvedge_tpu.runtime.tracing import Tracer

    params = init_params(jax.random.PRNGKey(0), cfg)
    pages = slots * -(-(prompt_len + n_new) // page_size)
    rng = np.random.default_rng(12)
    prompts = rng.integers(
        0, cfg.vocab, size=(slots, prompt_len)
    ).astype(np.int32)

    def run(obs: bool) -> float:
        # A tight fast window pushes the SLO snapshot throttle to its
        # floor (~0.03 s) so the measured run takes MORE boundary
        # snapshots per second than any production config would.
        extra = dict(
            tracer=Tracer(sample=1.0), slo=SloObjectives(fast_window_s=1.0),
            occupancy_ring=256,
        ) if obs else {}
        server = PagedGenerationServer(
            params, cfg, slots=slots, pages=pages, page_size=page_size,
            prefix_cache=False, window=PAGED_WINDOW, **extra,
        )
        errors: list[Exception] = []

        def client(ci: int) -> None:
            try:
                server.submit([int(t) for t in prompts[ci]], n_new,
                              timeout=600.0,
                              request_id=f"bench-obs-{ci}")
            except Exception as e:  # pragma: no cover - fail loudly
                errors.append(e)

        threads = [
            threading.Thread(target=client, args=(ci,), daemon=True)
            for ci in range(slots)
        ]
        start = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        elapsed = time.perf_counter() - start
        server.close()
        if errors:
            raise errors[0]
        return slots * n_new / elapsed

    # Same interleaved best-of-3 protocol as measure_trace_overhead:
    # warmup eats the compile, interleaving decorrelates host drift.
    run(False)
    off = on = 0.0
    for _ in range(3):
        off = max(off, run(False))
        on = max(on, run(True))
    return off, on


CHECKPOINT_EVERY = 16


def measure_checkpoint_overhead(cfg, slots: int, prompt_len: int,
                                n_new: int, page_size: int
                                ) -> tuple[float, float]:
    """The rung-22 durability bill on the paged decode leg: the same
    fully-loaded decode through the REAL server with boundary
    checkpoints off (``serving_checkpoint_every = 0``, today's
    fail-and-retry semantics) then on at the documented default cadence
    (16). Each checkpoint is a ``swapout_pages`` of the pages dirtied
    since the last one plus a host-side journal append, so the bill is
    ~pages_dirty x swap bandwidth amortized over the cadence — the
    SERVING.md rung-22 contract pins the delta < 5% at the default.

    Returns ``(tokens_per_sec_off, tokens_per_sec_on)``."""
    import threading

    from kvedge_tpu.models.serving import PagedGenerationServer

    params = init_params(jax.random.PRNGKey(0), cfg)
    pages = slots * -(-(prompt_len + n_new) // page_size)
    rng = np.random.default_rng(13)
    prompts = rng.integers(
        0, cfg.vocab, size=(slots, prompt_len)
    ).astype(np.int32)

    def run(every: int) -> float:
        server = PagedGenerationServer(
            params, cfg, slots=slots, pages=pages, page_size=page_size,
            prefix_cache=False, window=PAGED_WINDOW,
            checkpoint_every=every,
        )
        errors: list[Exception] = []

        def client(ci: int) -> None:
            try:
                server.submit([int(t) for t in prompts[ci]], n_new,
                              timeout=600.0,
                              request_id=f"bench-ckpt-{ci}")
            except Exception as e:  # pragma: no cover - fail loudly
                errors.append(e)

        threads = [
            threading.Thread(target=client, args=(ci,), daemon=True)
            for ci in range(slots)
        ]
        start = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        elapsed = time.perf_counter() - start
        server.close()
        if errors:
            raise errors[0]
        return slots * n_new / elapsed

    # Same discipline as the tracing leg: one warmup run compiles the
    # shared program set (including the swapout gather the cadenced run
    # adds), then best-of-three interleaved rounds per mode so host
    # drift decorrelates from the off/on comparison.
    run(CHECKPOINT_EVERY)
    off = on = 0.0
    for _ in range(3):
        off = max(off, run(0))
        on = max(on, run(CHECKPOINT_EVERY))
    return off, on


LONGCTX_MAX_SEQ = 8192
LONGCTX_WINDOW = 32
LONGCTX_PAGE_SIZE = 128


def measure_paged_longcontext(cfg_base, slots: int = 4,
                              page_size: int = LONGCTX_PAGE_SIZE,
                              lives=(512, 4096),
                              n_steps: int = LONGCTX_WINDOW,
                              max_seq: int = LONGCTX_MAX_SEQ):
    """Long-context decode: the Pallas block-table kernel vs the padded
    gather, ms/step at different LIVE lengths under one pool CAP.

    The gather path's per-step cost scales with the cap (it
    materializes [B, max_pages x page, K, Dh] every step regardless of
    content); the kernel's scales with each sequence's live length
    (dead pages clamp their DMA away — ops/paged_attention.py). Both
    decode the same state; before anything is timed, the FIRST decode
    step's logits are asserted BIT-IDENTICAL between the two impls (the
    two-phase kernel stages scores and V into scratch and reduces in
    one flat softmax+dot, the same float schedule as the gather — so
    any difference at all is a wrong page, a mask off-by-one, or a
    head-mix bug), and the first window's token-agreement fraction is
    asserted == 1.0 (bit-identical logits admit no argmax flips).
    Returns ``({(impl, live): ms_per_step}, {live: agreement_fraction})``
    with every agreement pinned at 1.0.

    Timing note: windows advance lengths, so later reps run slightly
    longer-lived sequences than ``live`` (+n_steps per window, ~3
    windows per impl) — a few-percent drift against an effect measured
    in multiples.
    """
    import dataclasses as _dc

    from kvedge_tpu.models.kvcache import PagedKVCache

    cfgs = {
        impl: _dc.replace(cfg_base, max_seq=max_seq,
                          paged_attention=impl)
        for impl in ("gather", "kernel")
    }
    params = init_params(jax.random.PRNGKey(0), cfgs["gather"])
    mpps = max_seq // page_size
    out: dict = {}
    agreement: dict = {}
    for live in lives:
        prompts = jax.random.randint(
            jax.random.PRNGKey(4), (slots, live), 0, cfg_base.vocab,
            dtype=jnp.int32,
        )
        first_logits = {}
        first_tokens = {}
        for impl, cfg in cfgs.items():
            cache = PagedKVCache(
                cfg, slots=slots, pages=slots * mpps,
                page_size=page_size, max_pages_per_seq=mpps,
            )
            tokens = _prefill_slots(cache, params, prompts)
            # One single step for the exactness anchor (same state in
            # both impls), then the first window doubles as compile
            # warmup.
            logits0 = cache.step(params, tokens)
            first_logits[impl] = np.asarray(logits0, np.float32)
            if impl == "kernel":
                # Fail fast BEFORE paying the kernel's timing loop.
                # The contract is exact: the two-phase kernel runs the
                # gather's float schedule, so ANY nonzero diff is a
                # correctness bug, not rounding.
                diff = np.abs(
                    first_logits["kernel"] - first_logits["gather"]
                ).max()
                if diff != 0.0:
                    raise AssertionError(
                        f"paged kernel logits diverged from gather at "
                        f"live={live} (max abs diff {diff}) — the "
                        "kernel is pinned bit-identical; refusing to "
                        "report its timing"
                    )
            tokens = jnp.argmax(logits0, axis=-1).astype(jnp.int32)
            produced = cache.step_window(params, tokens, n_steps)
            first_tokens[impl] = np.asarray(produced)
            tokens = produced[n_steps - 1]

            def run(cache, tokens=tokens, params=params):
                start = time.perf_counter()
                p = cache.step_window(params, tokens, n_steps)
                np.asarray(p)
                return time.perf_counter() - start

            best = _best_time(run, cache, warmups=1, reps=2)
            out[(impl, live)] = best / n_steps * 1000.0
        agreement[live] = float(
            (first_tokens["kernel"] == first_tokens["gather"]).mean()
        )
        if agreement[live] != 1.0:
            raise AssertionError(
                f"paged kernel token agreement {agreement[live]} != "
                f"1.0 at live={live} — bit-identical logits admit no "
                "argmax flips; a drifted window means state divergence"
            )
    return out, agreement


SPEC_DRAFT_LEN = 4
# Passes per device-resident spec window (SERVING.md rung 20): 8 is
# deep enough that the per-window round trip amortizes ~8x against the
# legacy per-pass leg when the host bounds it, shallow enough that a frozen
# row's wasted passes stay bounded.
SPEC_WINDOW_PASSES = 8

# The demonstrated speculative-decode crossover shape: ONE definition,
# shared with tools/bench_spec_crossover.py so the headline
# spec_decode_big_* metrics always measure exactly the shape the
# committed SPEC_CROSSOVER_r04.json curve names.
SPEC_BIG = dataclasses.replace(
    FLAGSHIP, n_layers=16, d_model=1024, d_ff=4096, n_heads=16,
    n_kv_heads=4,
)
SPEC_BIG_NAME = "L16-d1024"

# Train-at-scale leg (VERDICT r4 #5): the same 209M shape, trained.
# remat_policy="dots" (save matmul outputs, recompute elementwise)
# measured best at this scale — 63.5k vs 61.5k tok/s for remat="full"
# at batch 32/device; remat=off and fused_xent both fail to compile at
# this shape on one chip (OOM-class). Batch 32 and 64 tie (~0.5%), so
# the smaller reservation wins.
TRAIN_BIG_BATCH_PER_DEVICE = 32
TRAIN_BIG = dataclasses.replace(SPEC_BIG, remat_policy="dots")


def measure_speculative(cfg, prompt_len: int, n_new: int,
                        draft_len: int = SPEC_DRAFT_LEN):
    """Speculative vs plain greedy decode, single sequence (the
    latency workload speculation exists for), on a REPETITIVE prompt —
    prompt-lookup drafting's favorable case, so the number reports the
    capability's headroom; ``accepted_per_step`` quantifies how much of
    it this input reached. Returns (spec_tps, plain_tps, accepted)."""
    from kvedge_tpu.models import generate_speculative, init_params

    if prompt_len % 16:
        raise ValueError(
            f"prompt_len {prompt_len} must be a multiple of the 16-token "
            "repeat pattern (a silent truncation would bench the wrong "
            "prompt)"
        )
    params = init_params(jax.random.PRNGKey(0), cfg)
    pattern = jax.random.randint(
        jax.random.PRNGKey(3), (1, 16), 0, cfg.vocab, dtype=jnp.int32
    )
    prompt = jnp.tile(pattern, (1, prompt_len // 16))

    def timed(fn):
        float(fn()[0].sum())  # compile
        float(fn()[0].sum())  # absorb the slow first execution
        best = 0.0
        for _ in range(3):
            start = time.perf_counter()
            out = fn()
            float(out[0].sum())
            best = max(best, n_new / (time.perf_counter() - start))
        return best, out

    spec_tps, (tokens, rate) = timed(
        lambda: generate_speculative(params, prompt, cfg, n_new=n_new,
                                     draft_len=draft_len)
    )
    plain_tps, _ = timed(
        lambda: (generate(params, prompt, cfg, n_new=n_new),)
    )
    return spec_tps, plain_tps, float(rate)


def kv_cache_bytes_per_token(cfg, kv_dtype: str = "") -> int:
    """Per-token KV-cache HBM bill: L layers x (K+V) x kv_heads x
    (dh x bf16 | dh x int8 + one fp32 scale per row)."""
    per_head = (cfg.d_head + 4 if kv_dtype == "int8"
                else cfg.d_head * 2)
    return cfg.n_layers * 2 * cfg.kv_heads * per_head


def measure_longcontext_attention(seq: int = 4096, bh: int = 32,
                                  dh: int = 64):
    """Flash vs naive attention forward at long context (ms, ms, ratio).

    The headline train config uses naive attention because at seq 512 XLA's
    fused path wins on this device; the flash kernel's case is long
    context. At shapes where both fit the forward speedup is modest
    (~1.05-1.15x measured); the decisive difference is MEMORY — see
    ``attn_t8192_bh64_*`` in the output: [64, 8192] naive needs ~8.6 GB
    of bf16 scores plus the fp32 softmax upcast and fails to compile on
    one chip, while flash runs it (O(G·block²) VMEM).
    """
    import jax.nn

    from kvedge_tpu.ops.attention import flash_attention

    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kq, (bh, seq, dh), jnp.bfloat16)
    k = jax.random.normal(kk, (bh, seq, dh), jnp.bfloat16)
    v = jax.random.normal(kv, (bh, seq, dh), jnp.bfloat16)

    def naive(q, k, v):
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,)))) / (dh ** 0.5)
        causal = jnp.tril(jnp.ones((seq, seq), jnp.bool_))
        s = jnp.where(causal[None], s, jnp.finfo(q.dtype).min)
        w = jax.nn.softmax(s.astype(jnp.float32), -1).astype(q.dtype)
        return jax.lax.dot_general(w, v, (((2,), (1,)), ((0,), (0,))))

    naive_ms = _timed_op(naive, q, k, v)
    flash_ms = _timed_op(flash_attention, q, k, v)
    return naive_ms, flash_ms, naive_ms / flash_ms


def _timed_op(fn, *arrays, reps: int = 5, rounds: int = 2) -> float:
    """Best-of-``rounds`` mean ms/call — the one timing harness for the
    attention microbenches, with the same discipline as
    :func:`measure`: double warmup (compile + slow first execution) and
    a scalar fetch as the only trustworthy sync."""
    g = jax.jit(lambda *a: jnp.sum(fn(*a).astype(jnp.float32)))
    float(g(*arrays))
    float(g(*arrays))
    best = float("inf")
    for _ in range(rounds):
        start = time.perf_counter()
        out = None
        for _ in range(reps):
            out = g(*arrays)
        float(out)
        best = min(best, (time.perf_counter() - start) / reps)
    return best * 1000.0


def measure_flash_only(seq: int, bh: int, dh: int = 64) -> float:
    """Flash forward at a shape the naive path cannot fit (ms)."""
    from kvedge_tpu.ops.attention import flash_attention

    kq, kk, kv = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(kq, (bh, seq, dh), jnp.bfloat16)
    k = jax.random.normal(kk, (bh, seq, dh), jnp.bfloat16)
    v = jax.random.normal(kv, (bh, seq, dh), jnp.bfloat16)
    return _timed_op(flash_attention, q, k, v, reps=3, rounds=1)


def main() -> int:
    enable_compile_cache()  # before the first compile
    peak_flops = peak_flops_per_chip()  # an unknown device fails here
    tokens_per_sec, final_loss, n = measure(
        FLAGSHIP, BATCH_PER_DEVICE, SEQ, TIMED_STEPS
    )
    flops_token = model_flops_per_token(FLAGSHIP, SEQ)
    mfu = tokens_per_sec * flops_token / (n * peak_flops)

    mha = dataclasses.replace(FLAGSHIP, n_kv_heads=0)
    gqa = dataclasses.replace(FLAGSHIP, n_kv_heads=2)
    decode_mha = measure_decode(mha, DECODE_BATCH, DECODE_PROMPT, DECODE_NEW)
    decode_gqa = measure_decode(gqa, DECODE_BATCH, DECODE_PROMPT, DECODE_NEW)
    host_round_trip_ms = measure_host_round_trip()
    (paged_tps, paged_sps, paged_host_sps,
     paged_overlap_tps, paged_overlap_speedup) = measure_paged_decode(
        gqa, PAGED_SLOTS, DECODE_PROMPT, DECODE_NEW, PAGED_PAGE_SIZE
    )
    spec_tps, plain_b1_tps, spec_accept = measure_speculative(
        gqa, DECODE_PROMPT, DECODE_NEW
    )
    paged_mixed_tps = measure_paged_mixed(
        gqa, PAGED_SLOTS, DECODE_PROMPT, DECODE_NEW, PAGED_PAGE_SIZE
    )
    paged_spec_tps, paged_spec_epp = measure_paged_spec(
        gqa, PAGED_SLOTS, DECODE_PROMPT, DECODE_NEW, PAGED_PAGE_SIZE,
        SPEC_DRAFT_LEN,
    )
    paged_spec_worst_tps, paged_spec_worst_epp = measure_paged_spec(
        gqa, PAGED_SLOTS, DECODE_PROMPT, DECODE_NEW, PAGED_PAGE_SIZE,
        SPEC_DRAFT_LEN, adversarial=True,
    )
    paged_specw_tps, paged_specw_epw = measure_paged_spec_window(
        gqa, PAGED_SLOTS, DECODE_PROMPT, DECODE_NEW, PAGED_PAGE_SIZE,
        SPEC_DRAFT_LEN, SPEC_WINDOW_PASSES,
    )
    sched_fifo, sched_strict = measure_sched_overload(
        gqa, PAGED_SLOTS, DECODE_PROMPT, SCHED_OVERLOAD_N_NEW,
        PAGED_PAGE_SIZE,
    )
    openloop = measure_openloop(gqa, DECODE_PROMPT, PAGED_PAGE_SIZE)
    prefix_ol = measure_prefix_openloop(gqa, PAGED_PAGE_SIZE)
    trace_off_tps, trace_on_tps = measure_trace_overhead(
        gqa, PAGED_SLOTS, DECODE_PROMPT, DECODE_NEW, PAGED_PAGE_SIZE
    )
    obs_off_tps, obs_on_tps = measure_obs_overhead(
        gqa, PAGED_SLOTS, DECODE_PROMPT, DECODE_NEW, PAGED_PAGE_SIZE
    )
    ckpt_off_tps, ckpt_on_tps = measure_checkpoint_overhead(
        gqa, PAGED_SLOTS, DECODE_PROMPT, DECODE_NEW, PAGED_PAGE_SIZE
    )
    # Where speculation PAYS (VERDICT r3 #3): at the flagship scale the
    # per-verify fixed cost eats the acceptance (~1.05x above); the
    # crossover study (tools/bench_spec_crossover.py,
    # SPEC_CROSSOVER_r04.json) shows the speedup growing with model
    # cost — single-row decode is weight-bandwidth-bound, so a verify
    # pass streams the same weights as one decode step. SPEC_BIG
    # (L16-d1024, 209M params) is the measured crossover shape
    # (>= 1.3x): 1.67x there, 1.84x at 770M.
    spec_big_tps, spec_big_plain_tps, spec_big_accept = measure_speculative(
        SPEC_BIG, DECODE_PROMPT, DECODE_NEW
    )
    # Training at the scale where arithmetic dominates (VERDICT r4 #5):
    # the 38M flagship's MFU is ceiling-bound by non-dot overhead (the
    # r3 breakdown); at 209M the dots should carry it.
    train_big_tps, train_big_loss, n_big = measure(
        TRAIN_BIG, TRAIN_BIG_BATCH_PER_DEVICE, SEQ, TIMED_STEPS
    )
    if not (train_big_loss == train_big_loss):  # NaN: a diverged run's
        raise AssertionError(                   # throughput is garbage
            "train_big loss is NaN — refusing to publish its throughput"
        )
    train_big_flops = model_flops_per_token(TRAIN_BIG, SEQ)
    train_big_mfu = (train_big_tps * train_big_flops
                     / (n_big * peak_flops))
    naive_ms, flash_ms, flash_speedup = measure_longcontext_attention()
    flash_big_ms = measure_flash_only(seq=8192, bh=64)
    longctx, longctx_agree = measure_paged_longcontext(gqa)

    print(
        json.dumps(
            {
                "metric": "flagship_train_tokens_per_sec",
                "value": round(tokens_per_sec, 1),
                "unit": "tokens/s",
                "vs_baseline": 1.0,
                "vs_r01": round(tokens_per_sec / R01_TOKENS_PER_SEC, 4),
                "mfu": round(mfu, 4),
                "model_flops_per_token": flops_token,
                "peak_flops_per_chip": peak_flops,
                "platform": jax.devices()[0].platform,
                "device_kind": jax.devices()[0].device_kind,
                "device_count": len(jax.devices()),
                "decode_tokens_per_sec": round(decode_gqa, 1),
                "decode_mha_tokens_per_sec": round(decode_mha, 1),
                "paged_decode_tokens_per_sec": round(paged_tps, 1),
                "paged_decode_steps_per_sec": round(paged_sps, 1),
                "paged_decode_hostloop_steps_per_sec": round(
                    paged_host_sps, 1
                ),
                "paged_decode_slots": PAGED_SLOTS,
                "paged_decode_window": PAGED_WINDOW,
                # Double-buffered window pipeline (serving_overlap,
                # SERVING.md rung 16): window N+1 is enqueued on the
                # device-resident carry before window N's tokens are
                # read back, hiding the harvest round trip under
                # device execution — steps/s approaches 1/max(R, W*t)
                # vs the serial leg's 1/(R + W*t). The speedup is a
                # round-trip play: read it against host_round_trip_ms
                # (predicted >= 1.3x whenever R >= 20 ms; ~1.0x when
                # the host sits beside the device and W*t dominates).
                "paged_decode_overlap_tokens_per_sec": round(
                    paged_overlap_tps, 1
                ),
                "paged_decode_overlap_speedup": round(
                    paged_overlap_speedup, 3
                ),
                # Batched speculative serving (serving_speculative=4)
                # on the same favorable repetitive input as the
                # single-row spec metrics: one verify pass advances
                # every slot up to 5 tokens — an RTT amortization of
                # emitted_per_pass, vs page_size (16) for the windowed
                # path. With a long host round trip the number is
                # therefore transport-bound and BELOW the windowed rate;
                # the mode pays where decode is model-cost-bound (a
                # sub-ms round trip or big models — the crossover
                # study's regime). host_round_trip_ms is the covariate
                # to read it against.
                "paged_spec_tokens_per_sec": round(paged_spec_tps, 1),
                "paged_spec_emitted_per_pass": round(paged_spec_epp, 2),
                # Worst case (random prompts, acceptance ≈ 0): the pure
                # verify-pass overhead — brackets the favorable number
                # above (VERDICT r4 #8).
                "paged_spec_worstcase_tokens_per_sec": round(
                    paged_spec_worst_tps, 1
                ),
                "paged_spec_worstcase_emitted_per_pass": round(
                    paged_spec_worst_epp, 2
                ),
                # Device-resident spec windows (serving_spec_window,
                # SERVING.md rung 20): the same favorable input as
                # paged_spec_tokens_per_sec, but W=8 draft+verify
                # passes run per dispatch, so the RTT bill drops from
                # one per pass to ~one per window. tokens/s goes
                # E*W / max(R, W*t) — bound by the host round trip the
                # speedup approaches W; when device math dominates it
                # approaches 1 (same arithmetic, fewer round trips).
                # Tokens are bit-identical to the legacy path
                # (tests/test_spec_window.py pins it).
                "paged_spec_window_passes": SPEC_WINDOW_PASSES,
                "paged_spec_window_tokens_per_sec": round(
                    paged_specw_tps, 1
                ),
                "paged_spec_window_emitted_per_window": round(
                    paged_specw_epw, 2
                ),
                "paged_spec_window_speedup": round(
                    paged_specw_tps / paged_spec_tps, 3
                ),
                # One sampled co-tenant in the windowed batch (round-5
                # on-device sampling): should sit near
                # paged_decode_tokens_per_sec, not collapse to the
                # host-loop rate as it did when sampling forced
                # per-step dispatch.
                "paged_mixed_tokens_per_sec": round(paged_mixed_tps, 1),
                # Overload leg (SERVING.md rung 17): 2x oversubscribed
                # mixed traffic (batch owns every slot when the
                # interactive burst lands) through the real server,
                # fifo baseline vs strict priority + preemptive swap.
                # The scheduler's claim is the interactive p99 queue
                # wait: strict preempts a batch tenant at the next
                # window boundary (<= window*step + swap), fifo makes
                # the burst wait out full batch budgets. Goodput is
                # completed tokens per wall second — strict's should be
                # near fifo's (swap costs a little; the win is latency
                # shaping, not throughput). Wait quantiles are bucket
                # upper bounds (conservative).
                "sched_overload_oversubscription": float(
                    SCHED_OVERLOAD_FACTOR
                ),
                "sched_overload_goodput_tokens_per_sec": round(
                    sched_strict["goodput_tokens_per_sec"], 1
                ),
                "sched_overload_fifo_goodput_tokens_per_sec": round(
                    sched_fifo["goodput_tokens_per_sec"], 1
                ),
                "sched_overload_interactive_wait_p50_ms":
                    sched_strict["interactive_wait_p50_ms"],
                "sched_overload_interactive_wait_p99_ms":
                    sched_strict["interactive_wait_p99_ms"],
                "sched_overload_batch_wait_p50_ms":
                    sched_strict["batch_wait_p50_ms"],
                "sched_overload_batch_wait_p99_ms":
                    sched_strict["batch_wait_p99_ms"],
                "sched_overload_fifo_interactive_wait_p99_ms":
                    sched_fifo["interactive_wait_p99_ms"],
                "sched_overload_fifo_batch_wait_p99_ms":
                    sched_fifo["batch_wait_p99_ms"],
                "sched_overload_preemptions":
                    sched_strict["preemptions"],
                # Open-loop arrivals (SERVING.md rung 21): one Poisson
                # (and one bursty trace-replay) arrival schedule
                # replayed against slot capacities 4/64/256 with the
                # bucketed compile cache on. Rates are calibrated from
                # the measured 4-slot service rate (low = clearable by
                # 4 slots, high = 3x that). The scaling claim: at the
                # high rate the largest capacity's goodput beats the
                # 4-slot configuration (which saturates at its service
                # ceiling while its queue — and p99 wait — grows), and
                # its p99 queue wait stays near-admission-instant.
                "sched_openloop_capacities": list(OPENLOOP_CAPACITIES),
                "sched_openloop_rate_low_req_per_sec": round(
                    openloop["rates"]["low"], 2
                ),
                "sched_openloop_rate_high_req_per_sec": round(
                    openloop["rates"]["high"], 2
                ),
                # Headline: largest capacity, Poisson, high rate.
                "sched_openloop_goodput_tokens_per_sec": round(
                    openloop["legs"][
                        (OPENLOOP_CAPACITIES[-1], "poisson", "high")
                    ]["goodput_tokens_per_sec"], 1
                ),
                "sched_openloop_wait_p99_ms": openloop["legs"][
                    (OPENLOOP_CAPACITIES[-1], "poisson", "high")
                ]["wait_p99_ms"],
                **{
                    f"sched_openloop_{mode}_{rate}_goodput"
                    f"_tokens_per_sec_c{cap}": round(
                        leg["goodput_tokens_per_sec"], 1
                    )
                    for (cap, mode, rate), leg in
                    openloop["legs"].items()
                },
                **{
                    f"sched_openloop_{mode}_{rate}_wait_p99_ms"
                    f"_c{cap}": leg["wait_p99_ms"]
                    for (cap, mode, rate), leg in
                    openloop["legs"].items()
                },
                # Shared-prefix serving (SERVING.md rung 24): one
                # open-loop schedule (common 64-token system prompt,
                # every second arrival a multi-turn replay) run
                # cache-off then cache-on at the SAME rate — emitted
                # streams verified bit-identical, the cache's win
                # reported as prefill tokens saved and the TTFT shift.
                "prefix_openloop_requests": prefix_ol["requests"],
                "prefix_openloop_rate_req_per_sec": round(
                    prefix_ol["rate_req_per_sec"], 2
                ),
                "prefix_openloop_bit_identical":
                    prefix_ol["bit_identical"],
                "prefix_openloop_prefill_tokens_saved":
                    prefix_ol["on"]["prefill_tokens_saved"],
                "prefix_openloop_prefill_saved_frac": round(
                    prefix_ol["saved_frac"], 3
                ),
                "prefix_openloop_cow_copies":
                    prefix_ol["on"]["cow_copies"],
                "prefix_openloop_goodput_tokens_per_sec": round(
                    prefix_ol["on"]["goodput_tokens_per_sec"], 1
                ),
                "prefix_openloop_off_goodput_tokens_per_sec": round(
                    prefix_ol["off"]["goodput_tokens_per_sec"], 1
                ),
                "prefix_openloop_ttft_p50_ms":
                    prefix_ol["on"]["ttft_p50_ms"],
                "prefix_openloop_off_ttft_p50_ms":
                    prefix_ol["off"]["ttft_p50_ms"],
                "prefix_openloop_ttft_p99_ms":
                    prefix_ol["on"]["ttft_p99_ms"],
                "prefix_openloop_off_ttft_p99_ms":
                    prefix_ol["off"]["ttft_p99_ms"],
                # Tracing bill (SERVING.md rung 18): the same loaded
                # paged decode with serving_trace off vs on (sample
                # 1.0, every request). A span is one deque append, so
                # the design contract is < 5% — negative values are
                # run-to-run noise saying the bill is unmeasurable.
                "paged_decode_trace_on_tokens_per_sec": round(
                    trace_on_tps, 1
                ),
                "paged_decode_trace_overhead_pct": round(
                    (trace_off_tps - trace_on_tps)
                    / trace_off_tps * 100.0, 2
                ),
                # Full observability bill (SERVING.md rung 25): the
                # whole stack at once — full-sample tracing + SLO
                # engine (throttle floored by a 1 s fast window) +
                # occupancy ring — vs everything off. Contract < 5%;
                # negative values are run-to-run noise.
                "paged_decode_obs_on_tokens_per_sec": round(
                    obs_on_tps, 1
                ),
                "paged_decode_obs_overhead_pct": round(
                    (obs_off_tps - obs_on_tps)
                    / obs_off_tps * 100.0, 2
                ),
                # Durability bill (SERVING.md rung 22): boundary
                # checkpoints off vs the default cadence (16). Each
                # checkpoint swaps out only the pages dirtied since the
                # last one (~pages_dirty x swap bandwidth, amortized
                # over the cadence), so the contract is < 5% on this
                # leg — negative values are run-to-run noise.
                "paged_decode_checkpoint_every": CHECKPOINT_EVERY,
                "paged_decode_checkpoint_on_tokens_per_sec": round(
                    ckpt_on_tps, 1
                ),
                "paged_decode_checkpoint_overhead_pct": round(
                    (ckpt_off_tps - ckpt_on_tps)
                    / ckpt_off_tps * 100.0, 2
                ),
                # Covariate: per-step-sync loops are bound by the host
                # round trip per dispatch; the windowed path amortizes
                # it ~window x.
                "host_round_trip_ms": round(host_round_trip_ms, 2),
                "spec_decode_tokens_per_sec": round(spec_tps, 1),
                "spec_decode_plain_b1_tokens_per_sec": round(
                    plain_b1_tps, 1
                ),
                "spec_decode_accepted_per_step": round(spec_accept, 2),
                "spec_decode_big_shape": "L16-d1024-209M",
                "spec_decode_big_tokens_per_sec": round(spec_big_tps, 1),
                "spec_decode_big_plain_tokens_per_sec": round(
                    spec_big_plain_tps, 1
                ),
                "spec_decode_big_speedup": round(
                    spec_big_tps / spec_big_plain_tps, 2
                ),
                "spec_decode_big_accepted_per_step": round(
                    spec_big_accept, 2
                ),
                # Train evidence at 200M+ (VERDICT r4 #5): same FLOPs
                # model as the headline (useful fwd + 2x bwd; remat
                # recompute not counted). MFU rises from ~35% (38M,
                # non-dot-overhead-bound per the r3 breakdown) to the
                # low-40s here — the remaining gap is the "dots" remat
                # policy's elementwise recompute plus the same non-dot
                # tail, now amortized over 5.5x the arithmetic.
                "train_big_shape": "L16-d1024-209M",
                "train_big_params": TRAIN_BIG.param_count,
                "train_big_batch_per_device":
                    TRAIN_BIG_BATCH_PER_DEVICE,
                "train_big_tokens_per_sec": round(train_big_tps, 1),
                "train_big_mfu": round(train_big_mfu, 4),
                "train_big_final_loss": round(train_big_loss, 3),
                "train_big_model_flops_per_token": train_big_flops,
                "kv_cache_bytes_per_token_gqa": kv_cache_bytes_per_token(gqa),
                "kv_cache_bytes_per_token_mha": kv_cache_bytes_per_token(mha),
                # int8 KV ([payload] serving_kv_dtype): per-token-row
                # quantized pools — ~0.53x the bf16 bill (dh int8 + one
                # fp32 scale per row per head), near-2x servable
                # context/slots per HBM byte. Lossy, opt-in.
                "kv_cache_bytes_per_token_gqa_int8":
                    kv_cache_bytes_per_token(gqa, "int8"),
                # Long-context paged decode (VERDICT r4 #4): one 8192-
                # token pool cap, two live lengths. The gather path's
                # ms/step is ~flat in live length (it pays the CAP
                # every step); the Pallas block-table kernel's tracks
                # the live length — the ratio at live=512 is the
                # dead-page bill the kernel stops paying. Logits
                # pinned close across impls before timing; the token-
                # agreement fraction quantifies near-tie argmax flips
                # (bf16 weight rounding) over the first 32-step window.
                "paged_longctx_cap_tokens": LONGCTX_MAX_SEQ,
                # Big pages: the kernel's per-page DMA loop is
                # latency-bound, so its win exists at page >= 64 (the
                # same condition paged_attention="auto" gates on).
                "paged_longctx_page_size": LONGCTX_PAGE_SIZE,
                "paged_longctx_gather_ms_per_step_live512": round(
                    longctx[("gather", 512)], 3
                ),
                "paged_longctx_kernel_ms_per_step_live512": round(
                    longctx[("kernel", 512)], 3
                ),
                "paged_longctx_gather_ms_per_step_live4096": round(
                    longctx[("gather", 4096)], 3
                ),
                "paged_longctx_kernel_ms_per_step_live4096": round(
                    longctx[("kernel", 4096)], 3
                ),
                "paged_longctx_kernel_speedup_live512": round(
                    longctx[("gather", 512)] / longctx[("kernel", 512)],
                    2,
                ),
                "paged_longctx_token_agreement": {
                    str(live): round(frac, 4)
                    for live, frac in longctx_agree.items()
                },
                "attn_t4096_naive_ms": round(naive_ms, 2),
                "attn_t4096_flash_ms": round(flash_ms, 2),
                "attn_t4096_flash_speedup": round(flash_speedup, 2),
                "attn_t8192_bh64_flash_ms": round(flash_big_ms, 2),
                # The same shape needs ~8.6 GB of bf16 scores (+ fp32
                # softmax upcast) on the naive path — it does not compile
                # on one chip; flash's O(block²) memory is the capability.
                "attn_t8192_bh64_naive_ms": None,
            }
        )
    )
    print(
        f"devices={n} platform={jax.devices()[0].platform} "
        f"loss={final_loss:.3f} mfu={mfu:.1%} "
        f"decode gqa={decode_gqa:.0f}/s mha={decode_mha:.0f}/s",
        file=sys.stderr,
    )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
