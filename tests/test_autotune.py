"""Online window controller (SERVING.md rung 26).

The controller closes the loop on the rung-16 throughput model:
steps/s = W / max(R, W*t) saturates at the smallest power-of-two
window whose device time covers the measured host turnaround, so the
law is ``W* = min pow2 in [lo, hi] with W*t >= R``. These tests pin

* the pure law (:func:`pick_window`) against a brute-force oracle,
* EWMA convergence to the model optimum under a seeded noisy
  synthetic (R, t) schedule, including a regime change,
* end-to-end bit-identity of ``window="auto"`` against the best
  static window and the contiguous reference (the window is pure
  scheduling — the controller must not be able to move a token),
* controller state surviving poison/revive and slice reformation
  (the server never recreates the instance),
* runtime-config parse/validate/to_toml round-trips for the new
  ``serving_window = "auto"`` / bounds knobs.

All fixed-seed and fast: tier-1.
"""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from kvedge_tpu.config.runtime_config import (
    RuntimeConfig,
    RuntimeConfigError,
)
from kvedge_tpu.models import TransformerConfig, generate, init_params
from kvedge_tpu.models.serving import PagedGenerationServer
from kvedge_tpu.runtime.autotune import WindowController, pick_window
from kvedge_tpu.runtime.failures import (
    OpBudgets,
    ServingFailure,
    SliceFollowerLost,
)
from kvedge_tpu.runtime.sliceserve import SlicePagedKVCache

pytestmark = pytest.mark.autotune

CFG = TransformerConfig(
    vocab=128, d_model=32, n_heads=4, n_kv_heads=2, n_layers=2, d_ff=64,
    max_seq=64,
)


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


@pytest.fixture(scope="module")
def mesh():
    devs = np.array(jax.devices()).reshape(2, 4)
    return Mesh(devs, ("data", "model"))


def reference(params, prompt, n_new):
    out = generate(params, jnp.asarray([prompt], jnp.int32), CFG,
                   n_new=n_new)
    return [int(t) for t in np.asarray(out)[0]]


# ---- the pure law against a brute-force oracle ---------------------------


def _oracle(r, t, lo, hi):
    """Literal transcription of the written-down optimum: walk the
    power-of-two ladder, return the first rung whose device time covers
    the host turnaround (or the cap)."""
    w = lo
    while w < hi and w * t < r:
        w *= 2
    return w


def test_pick_window_matches_oracle_on_grid():
    for r in (0.0, 0.1, 1.0, 3.7, 8.0, 64.0, 1e4):
        for t in (0.05, 0.5, 1.0, 7.3):
            for lo, hi in ((1, 256), (4, 64), (2, 2)):
                got = pick_window(r, t, lo, hi)
                assert got == _oracle(r, t, lo, hi), (r, t, lo, hi)
                assert lo <= got <= hi
                assert got & (got - 1) == 0  # power of two


def test_pick_window_saturation_is_minimal():
    # R=8, t=0.5: 16*0.5 >= 8 but 8*0.5 < 8 — the law picks the
    # SMALLEST saturating rung, not just any saturating one.
    assert pick_window(8.0, 0.5, 1, 256) == 16
    assert pick_window(7.9, 0.5, 1, 256) == 16
    assert pick_window(8.1, 0.5, 1, 256) == 32


def test_pick_window_free_device_pins_to_cap():
    # t <= 0: the device looks free; the largest window amortizes an
    # unmeasurably fast device best.
    assert pick_window(5.0, 0.0, 1, 64) == 64
    assert pick_window(5.0, -1.0, 1, 64) == 64


def test_pick_window_clamps_bounds_to_pow2():
    # Non-pow2 bounds floor to the compiled-program ladder {1,2,4,...}.
    assert pick_window(0.0, 1.0, 3, 100) == 2   # lo: floor(3) = 2
    assert pick_window(1e9, 1.0, 3, 100) == 64  # hi: floor(100) = 64
    assert pick_window(1e9, 1.0, 5, 3) == 4     # inverted: hi := lo


# ---- EWMA convergence to the model optimum -------------------------------


def _drive(ctl, rng, r_true, t_true, n):
    """Feed n synthetic harvests: the controller's own current pick is
    dispatched (as the serving loop does), measurements are the true
    (R, t) split under +/-10% multiplicative noise."""
    for _ in range(n):
        w = ctl.window()
        dev = w * t_true * rng.uniform(0.9, 1.1)
        host = 0.4 * r_true * rng.uniform(0.9, 1.1)
        transport = 0.6 * r_true * rng.uniform(0.9, 1.1)
        ctl.observe(rtt_ms=dev + transport, device_ms=dev,
                    host_ms=host, window=w)


def test_controller_converges_to_model_optimum():
    ctl = WindowController(lo=1, hi=256)
    rng = np.random.default_rng(0)
    _drive(ctl, rng, r_true=8.0, t_true=0.5, n=60)
    # Smallest pow2 with W*0.5 >= 8 is 16.
    assert ctl.window() == 16
    snap = ctl.snapshot()
    assert snap["updates"] == 60
    assert snap["window"] == pick_window(snap["r_ms"], snap["t_ms"],
                                         1, 256)
    # Regime change: host turnaround collapses (R 8 -> 1.6 ms). The
    # EWMA tracks down and the pick follows to 4 (4*0.5 >= 1.6).
    _drive(ctl, rng, r_true=1.6, t_true=0.5, n=100)
    assert ctl.window() == 4


def test_controller_first_observation_seeds_directly():
    # No warm-up bias toward zero: one observation fully determines the
    # estimate (EWMA seeds, not decays-from-zero).
    ctl = WindowController(lo=1, hi=256)
    ctl.observe(rtt_ms=12.0, device_ms=8.0, host_ms=4.0, window=16)
    snap = ctl.snapshot()
    assert snap["r_ms"] == pytest.approx(8.0)   # (12-8) + 4
    assert snap["t_ms"] == pytest.approx(0.5)   # 8 / 16
    assert ctl.window() == 16


def test_controller_default_before_first_observation():
    ctl = WindowController(lo=4, hi=64)
    assert ctl.window() == 64                     # no default: the cap
    assert ctl.window(default=16) == 16           # operator seed
    assert ctl.window(default=1) == 4             # clamped up to lo
    assert ctl.window(default=500) == 64          # clamped down to hi
    assert ctl.window(default=24) == 16           # pow2 floor


def test_controller_rejects_degenerate_construction():
    with pytest.raises(ValueError):
        WindowController(lo=64, hi=4)
    with pytest.raises(ValueError):
        WindowController(alpha=0.0)
    with pytest.raises(ValueError):
        WindowController(alpha=1.5)


def test_controller_ignores_nonpositive_window_observation():
    ctl = WindowController()
    ctl.observe(rtt_ms=1.0, device_ms=1.0, host_ms=1.0, window=0)
    assert ctl.snapshot()["updates"] == 0


# ---- end-to-end: auto is bit-identical to static -------------------------


def _run_concurrent(server, requests):
    results: dict[int, list[int]] = {}
    errors: list[Exception] = []

    def worker(i, prompt, n_new):
        try:
            results[i] = server.submit(prompt, n_new)
        except Exception as e:  # pragma: no cover - surfaced below
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(i, p, n))
               for i, (p, n) in enumerate(requests)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    assert not errors, errors
    return results


def test_auto_window_bit_identical_to_static(params):
    """``window="auto"`` must produce the same tokens as every static
    window — here the best static (the controller's own cap) — and the
    contiguous reference. The window is pure scheduling; the controller
    moves work between host and device, never a token."""
    requests = [([5, 9, 2], 8), ([1, 1, 4, 3, 7, 7], 6), ([42], 10)]
    out = []
    for window in (8, "auto"):
        server = PagedGenerationServer(
            params, CFG, slots=2, pages=24, page_size=4,
            window=window, window_min=1, window_max=8,
            prefix_cache=False,
        )
        try:
            out.append(_run_concurrent(server, requests))
            if window == "auto":
                stats = server.stats()
                # The controller actually drove: observations landed
                # and the gauges are exported.
                assert stats["autotune_updates"] > 0
                assert stats["autotune_window"] in (1, 2, 4, 8)
                assert stats["autotune_t_ms"] >= 0.0
        finally:
            server.close()
    static, auto = out
    assert static == auto
    for i, (prompt, n_new) in enumerate(requests):
        assert auto[i] == reference(params, prompt, n_new), (
            f"request {i} diverged from contiguous generate"
        )


def test_auto_window_sampled_matches_static(params):
    """The positional fold_in(seed, t) key schedule makes sampling
    window-invariant too — auto must not move a sampled token."""
    key = jax.random.fold_in(jax.random.PRNGKey(3), 0)
    sampling = (key, jnp.float32(0.8), jnp.float32(0.9))
    out = []
    for window in (8, "auto"):
        server = PagedGenerationServer(
            params, CFG, slots=2, pages=16, page_size=4,
            window=window, window_max=8, prefix_cache=False,
        )
        try:
            out.append(server.submit([1, 2, 3, 4], n_new=12,
                                     sampling=sampling))
        finally:
            server.close()
    assert out[0] == out[1]
    assert len(out[1]) == 4 + 12


def test_static_window_rejects_unknown_string(params):
    with pytest.raises(ValueError, match="auto"):
        PagedGenerationServer(params, CFG, window="adaptive")


# ---- controller state across poison/revive and reformation ---------------


def test_controller_survives_poison_revive(params):
    """revive() rebuilds pool state but never recreates the controller:
    the learned (R, t) estimates ride through, so the revived pool
    resumes at the learned window instead of re-warming from the cap."""
    server = PagedGenerationServer(
        params, CFG, slots=2, pages=16, page_size=4,
        window="auto", window_max=8, prefix_cache=False,
    )
    prompt = [3, 1, 4, 1, 5]
    try:
        assert server.submit(prompt, n_new=8) == reference(
            params, prompt, 8)
        ctl = server._autotune
        before = ctl.snapshot()
        assert before["updates"] > 0
        cache = server._cache
        real = cache.harvest_window

        def dying(handle):
            raise RuntimeError("injected: harvest died")

        cache.harvest_window = dying
        dying_thread = server._thread
        with pytest.raises(ServingFailure):
            server.submit(prompt, n_new=8)
        dying_thread.join(timeout=30)
        cache.harvest_window = real
        server.revive()
        assert server.degraded is None
        assert server._autotune is ctl  # the same learned instance
        assert ctl.snapshot()["updates"] >= before["updates"]
        assert server.submit(prompt, n_new=6) == reference(
            params, prompt, 6)
        assert ctl.snapshot()["updates"] > before["updates"]
    finally:
        server.close()


def test_controller_survives_slice_reformation(params, mesh):
    """The slice twin: a follower loss kills the op stream, reform()
    replaces it (dropping the device carry and the memoized dispatch
    operands) — and the controller's estimates are untouched, because
    they are host-side plain data owned by the server."""
    cache = SlicePagedKVCache(
        CFG, slots=2, pages=16, page_size=4, mesh=mesh,
        op_budgets=OpBudgets(steady_s=3.0, compile_s=20.0),
    )
    server = PagedGenerationServer(
        params, CFG, cache=cache, window="auto", window_max=4,
        prefix_cache=False,
    )
    prompt = [3, 1, 4, 1, 5]
    wedge = threading.Event()
    try:
        assert server.submit(prompt, n_new=6) == reference(
            params, prompt, 6)
        ctl = server._autotune
        before = ctl.snapshot()
        assert before["updates"] > 0
        with pytest.raises(SliceFollowerLost):
            cache._ops.run(("wedge",), lambda: wedge.wait(60),
                           budget_s=0.2)
        wedge.set()
        assert cache._ops.dead is not None
        cache.reform(budget_s=5.0)
        assert cache._ops.dead is None
        assert server._autotune is ctl
        assert ctl.snapshot() == before  # reformation observed nothing
        assert server.submit(prompt, n_new=6) == reference(
            params, prompt, 6)
        assert ctl.snapshot()["updates"] > before["updates"]
    finally:
        wedge.set()
        server.close()


# ---- runtime-config knobs ------------------------------------------------


AUTO_TOML = """
[runtime]
name = "edge-auto"

[payload]
kind = "transformer-probe"
serving_window = "auto"
serving_window_min = 2
serving_window_max = 128
"""


def test_config_auto_window_round_trip():
    cfg = RuntimeConfig.parse(AUTO_TOML)
    assert cfg.serving_window == "auto"
    assert cfg.serving_window_min == 2
    assert cfg.serving_window_max == 128
    cfg.validate()
    again = RuntimeConfig.parse(cfg.to_toml())
    assert again.serving_window == "auto"
    assert again.serving_window_min == 2
    assert again.serving_window_max == 128


def test_config_static_window_round_trip_unchanged():
    cfg = RuntimeConfig.parse(AUTO_TOML.replace(
        'serving_window = "auto"', "serving_window = 32"))
    assert cfg.serving_window == 32
    cfg.validate()
    assert RuntimeConfig.parse(cfg.to_toml()).serving_window == 32


@pytest.mark.parametrize("old, new, match", [
    ('serving_window = "auto"', 'serving_window = "adaptive"',
     "serving_window"),
    ('serving_window = "auto"', "serving_window = 0",
     "serving_window"),
    ('serving_window = "auto"', "serving_window = 2048",
     "serving_window"),
    ("serving_window_min = 2", "serving_window_min = 0",
     "serving_window_min"),
    ("serving_window_max = 128", "serving_window_max = 2048",
     "serving_window_max"),
])
def test_config_window_validation_rejects(old, new, match):
    with pytest.raises(RuntimeConfigError, match=match):
        RuntimeConfig.parse(AUTO_TOML.replace(old, new)).validate()


def test_config_window_bounds_must_be_ordered():
    text = AUTO_TOML.replace("serving_window_min = 2",
                             "serving_window_min = 256").replace(
        "serving_window_max = 128", "serving_window_max = 8")
    with pytest.raises(RuntimeConfigError, match="min"):
        RuntimeConfig.parse(text).validate()
