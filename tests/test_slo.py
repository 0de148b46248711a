"""SLO engine + flight recorder (SERVING.md rung 25).

The contract under test, end to end: the rolling SLO engine computes
multi-window SLIs and error-budget burn rates from DELTAS of the
cumulative histograms the serving path already keeps; the burn-rate
alert is the classic fast/slow multi-window rule and (knob-gated,
default off) feeds the scheduler's shed decision; device time splits
out of the dispatch->harvest window; the occupancy timeline ring
exports as ``serve_occupancy_*`` gauges and Chrome counter tracks; and
``flight_bundle()`` assembles a schema-complete post-mortem whose SLO
state and page books agree with the live ``stats()`` snapshot. The
whole observability stack ON is token-BIT-IDENTICAL to off. All
fixed-seed and fast: these run in the tier-1 gate.
"""

import dataclasses
import json
import re
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kvedge_tpu.config.runtime_config import (
    RuntimeConfig,
    RuntimeConfigError,
)
from kvedge_tpu.models import TransformerConfig, generate, init_params
from kvedge_tpu.models.serving import (
    PagedGenerationServer,
    ServerOverloaded,
)
from kvedge_tpu.runtime.failures import ServingFailure
from kvedge_tpu.runtime.slo import (
    BURN_FAST_ALERT,
    BURN_SLOW_ALERT,
    OccupancyRing,
    SloEngine,
    SloObjectives,
    hist_delta,
    hist_frac_over,
    hist_quantile,
)
from kvedge_tpu.runtime.status import StatusServer, render_metrics
from kvedge_tpu.runtime.tracing import Tracer
from tests.test_tracing import _check_chrome, _get, check_prometheus_text

pytestmark = pytest.mark.slo

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


# ---- objectives + histogram-delta math -----------------------------------


def test_objectives_validate():
    SloObjectives().validate()
    for bad in (
        dict(target=0.0), dict(target=1.0), dict(ttft_ms=0.0),
        dict(itl_ms=-1.0), dict(queue_ms=0.0),
        dict(fast_window_s=0.0), dict(fast_window_s=700.0),
    ):
        with pytest.raises(ValueError):
            SloObjectives(**bad).validate()


def _hist(edges, counts):
    s = sum(c * (edges[min(i, len(edges) - 1)])
            for i, c in enumerate(counts))
    return {"edges": list(edges), "counts": list(counts),
            "sum": float(s), "count": sum(counts)}


def test_hist_delta_and_reset_detection():
    a = _hist([10.0, 100.0], [1, 2, 0])
    b = _hist([10.0, 100.0], [3, 5, 1])
    d = hist_delta(b, a)
    assert d["counts"] == [2, 3, 1] and d["count"] == 6
    # Backwards counts / shape changes are resets, not deltas.
    assert hist_delta(a, b) is None
    assert hist_delta(_hist([10.0], [1, 0]), a) is None
    assert hist_delta({}, a) is None


def test_hist_quantile_interpolation():
    snap = _hist([10.0, 100.0], [5, 5, 0])
    assert hist_quantile(snap, 0.5) == pytest.approx(10.0)
    assert hist_quantile(snap, 0.99) == pytest.approx(98.2)
    # A quantile landing in +Inf clamps to the top finite edge.
    assert hist_quantile(_hist([10.0, 100.0], [0, 0, 10]), 0.99) == 100.0
    assert hist_quantile(_hist([10.0, 100.0], [0, 0, 0]), 0.99) is None


def test_hist_frac_over():
    snap = _hist([10.0, 100.0], [5, 5, 0])
    assert hist_frac_over(snap, 55.0) == pytest.approx(0.25)
    assert hist_frac_over(snap, 5.0) == pytest.approx(0.75)
    assert hist_frac_over(snap, 200.0) == 0.0
    # +Inf bucket counts wholly over (conservative — alerts early).
    assert hist_frac_over(_hist([10.0, 100.0], [0, 0, 4]),
                          150.0) == 1.0
    assert hist_frac_over(_hist([10.0], [0, 0]), 1.0) is None


# ---- the rolling engine ---------------------------------------------------

_OBJ = SloObjectives(target=0.95, ttft_ms=50.0, itl_ms=50.0,
                     queue_ms=50.0, fast_window_s=10.0,
                     slow_window_s=100.0)


def _snap(bad=0, good=0, tokens=0, done=0, shed=0):
    """A cumulative serving snapshot: ``bad`` latency observations in
    the +Inf bucket (over every objective), ``good`` under them."""
    h = _hist([10.0, 100.0], [good, 0, bad])
    return {"ttft_ms": h, "itl_ms": h, "queue_ms": h,
            "tokens_total": tokens, "done_total": done,
            "shed_total": shed}


def test_engine_slis_burn_and_multiwindow_alert():
    eng = SloEngine(_OBJ)
    assert eng.slis(10.0) == {}          # empty window
    assert eng.burn(10.0) is None
    assert not eng.alert()               # no data never pages
    assert eng.observe(0.0, _snap())
    assert eng.observe(200.0, _snap(bad=10, tokens=40, done=10))
    s = eng.slis(_OBJ.fast_window_s)
    assert s["window_s"] == pytest.approx(200.0)
    assert s["ttft_p99_ms"] == 100.0     # all in +Inf, clamped
    assert s["ttft_frac_over"] == 1.0
    assert s["goodput_tps"] == pytest.approx(40 / 200.0)
    assert s["shed_rate"] == 0.0
    # frac 1.0 / budget 0.05 = burn 20: both windows hot -> alert.
    assert eng.burn(_OBJ.fast_window_s) == pytest.approx(20.0)
    assert eng.burn(_OBJ.slow_window_s) == pytest.approx(20.0)
    assert 20.0 >= BURN_FAST_ALERT and 20.0 >= BURN_SLOW_ALERT
    assert eng.alert()
    # Recovery: a fresh fast window full of good events clears the
    # alert while the slow window still remembers the burn.
    assert eng.observe(210.0, _snap(bad=10, good=400, tokens=90,
                                    done=100))
    assert eng.burn(_OBJ.fast_window_s) == 0.0
    assert eng.burn(_OBJ.slow_window_s) == pytest.approx(20.0 / 41,
                                                         rel=0.1)
    assert not eng.alert()
    doc = eng.doc()
    assert doc["objectives"]["target"] == 0.95
    assert doc["windows"]["fast"]["burn"] == 0.0
    assert doc["alert"] is False
    json.dumps(doc)
    m = eng.metrics()
    assert m["slo_alert"] == 0 and m["slo_snapshots_total"] == 3
    assert set(m) == {
        "slo_ttft_p99_ms", "slo_itl_p99_ms", "slo_queue_p99_ms",
        "slo_goodput_tps", "slo_shed_rate", "slo_burn_fast",
        "slo_burn_slow", "slo_alert", "slo_snapshots_total",
        "slo_resets_total",
    }


def test_engine_shed_rate_feeds_burn():
    eng = SloEngine(_OBJ)
    eng.observe(0.0, _snap())
    # All latency good, but 1 of 4 offered requests shed -> the shed
    # rate is the worst offender and burns the budget.
    eng.observe(200.0, _snap(good=30, tokens=30, done=3, shed=1))
    s = eng.slis(_OBJ.fast_window_s)
    assert s["shed_rate"] == pytest.approx(0.25)
    assert eng.burn(_OBJ.fast_window_s) == pytest.approx(0.25 / 0.05)


def test_engine_throttles_boundary_spam():
    eng = SloEngine(_OBJ)
    # min interval = fast/32 = 0.3125 s.
    assert eng.observe(0.0, _snap())
    assert not eng.observe(0.1, _snap(good=1))
    assert eng.observe(0.5, _snap(good=1))
    assert eng.snapshots_total == 2


def test_engine_counter_reset_rebases_not_revive():
    eng = SloEngine(_OBJ)
    eng.observe(0.0, _snap(good=5, tokens=10, done=2))
    # revive() preserves counters: a same-or-growing snapshot is NOT a
    # reset and the window rides straight through the heal.
    eng.observe(20.0, _snap(good=5, tokens=10, done=2))
    assert eng.resets_total == 0 and len(eng) == 2
    # A replaced pool (counters went backwards) rebases the ring: no
    # delta is ever computed across the reset.
    eng.observe(40.0, _snap(good=1, tokens=3, done=1))
    assert eng.resets_total == 1 and len(eng) == 1
    assert eng.slis(_OBJ.fast_window_s) == {}
    assert eng.burn(_OBJ.fast_window_s) is None
    assert not eng.alert()
    assert eng.metrics()["slo_resets_total"] == 1


# ---- occupancy ring -------------------------------------------------------


def test_occupancy_ring_bounded_tail_and_chrome_counters():
    ring = OccupancyRing(3)
    for i in range(5):
        ring.sample(float(i), {"pages_live": i, "bucket": 2})
    assert len(ring) == 3 and ring.samples_total == 5
    assert ring.last() == {"pages_live": 4, "bucket": 2}
    tail = ring.tail(2)
    assert [t["t"] for t in tail] == [3.0, 4.0]  # oldest first
    assert tail[-1]["pages_live"] == 4
    counters = ring.chrome_counters(epoch=2.0)
    assert len(counters) == 3
    for ev in counters:
        assert ev["ph"] == "C" and ev["name"] == "occupancy"
        assert ev["ts"] >= 0 and ev["pid"] == 1
    # Merged into a tracer export, the counters pass the Chrome check.
    tr = Tracer(sample=1.0)
    tr.span("prefill", "serve", tr.now(), rid="req-1")
    # Synthetic ring stamps (0..4) vs the tracer's real perf_counter
    # epoch: anchor at 0 so the exported ts stay non-negative.
    tr.counter_source = lambda epoch: ring.chrome_counters(0.0)
    events = _check_chrome(tr.export_chrome())
    assert sum(1 for e in events if e["ph"] == "C") == 3
    with pytest.raises(ValueError):
        OccupancyRing(0)


# ---- /metrics conformance -------------------------------------------------


def _synthetic_serving() -> dict:
    h = _hist([10.0, 100.0], [3, 2, 1])
    eng = SloEngine(_OBJ)
    eng.observe(0.0, _snap())
    eng.observe(200.0, _snap(bad=2, good=8, tokens=20, done=5))
    doc = {
        "in_flight": 1, "requests_done_total": 5,
        "tokens_done_total": 20,
        "window_device_ms": h, "window_host_ms": h,
        "window_dispatch_harvest_ms": h, "itl_ms": h,
        "ttft_ms": h, "queue_ms": h, "decode_ms": h,
        "slice_op_ms": {"3": [7, 1.25], "14": [2, 0.5]},
        "occupancy_samples_total": 4,
        "occupancy_pages_total": 16, "occupancy_pages_live": 3,
        "occupancy_pages_free": 13, "occupancy_hbm_bytes_used": 4096,
        "occupancy_bucket": 2, "occupancy_slots_admitted": 1,
        "occupancy_slots_active": 1, "occupancy_reserved_pages": 4,
        "occupancy_prefix_entries": 0,
        "occupancy_prefix_host_bytes": 0,
        "occupancy_journal_bytes": 0, "occupancy_queue_depth": 0,
    }
    doc.update(eng.metrics())
    return doc


def test_new_series_pass_prometheus_conformance():
    text = render_metrics({"ok": True, "serving": _synthetic_serving()})
    families = check_prometheus_text(text)
    for family in ("kvedge_serve_device_ms_window", "kvedge_serve_itl_ms"):
        assert families[family] == "histogram"
    for family in (
        "kvedge_serve_slo_snapshots_total",
        "kvedge_serve_slo_resets_total",
        "kvedge_serve_occupancy_samples_total",
        "kvedge_serve_requests_done_total",
        "kvedge_serve_tokens_done_total",
        "kvedge_serve_device_broadcast_frames_total",
        "kvedge_serve_device_ms_broadcast_total",
    ):
        assert families[family] == "counter"
    for family in (
        "kvedge_serve_slo_ttft_p99_ms", "kvedge_serve_slo_itl_p99_ms",
        "kvedge_serve_slo_queue_p99_ms", "kvedge_serve_slo_goodput_tps",
        "kvedge_serve_slo_shed_rate", "kvedge_serve_slo_burn_fast",
        "kvedge_serve_slo_burn_slow", "kvedge_serve_slo_alert",
        "kvedge_serve_occupancy_pages_live",
        "kvedge_serve_occupancy_hbm_bytes_used",
        "kvedge_serve_occupancy_queue_depth",
    ):
        assert families[family] == "gauge"
    # Per-op labels render one sample per op kind, sorted.
    assert re.search(
        r'kvedge_serve_device_broadcast_frames_total\{op="14"\} 2',
        text)
    assert re.search(
        r'kvedge_serve_device_ms_broadcast_total\{op="3"\} 1\.250',
        text)


# ---- routes ---------------------------------------------------------------


def test_slo_and_bundle_routes_404_when_off():
    srv = StatusServer("127.0.0.1", 0, snapshot=lambda: {"ok": True})
    srv.start()
    try:
        base = f"http://127.0.0.1:{srv.port}"
        code, doc, _ = _get(f"{base}/slo")
        assert code == 404 and "serving_slo" in doc["error"]
        code, doc, _ = _get(f"{base}/debug/bundle")
        assert code == 404 and "serving_bundle" in doc["error"]
    finally:
        srv.shutdown()


def test_slo_and_bundle_routes_serve_docs_when_wired():
    eng = SloEngine(_OBJ)
    eng.observe(0.0, _snap())
    eng.observe(200.0, _snap(good=4, tokens=8, done=2))
    srv = StatusServer(
        "127.0.0.1", 0, snapshot=lambda: {"ok": True},
        slo_doc=eng.doc,
        bundle_doc=lambda: {"bundle_version": 1, "reason": None},
    )
    srv.start()
    try:
        base = f"http://127.0.0.1:{srv.port}"
        code, doc, _ = _get(f"{base}/slo")
        assert code == 200
        assert doc["windows"]["fast"]["goodput_tps"] > 0
        code, doc, _ = _get(f"{base}/debug/bundle")
        assert code == 200 and doc["bundle_version"] == 1
    finally:
        srv.shutdown()


# ---- the serving path -----------------------------------------------------

_OBS = dict(slo=SloObjectives(fast_window_s=1.0), occupancy_ring=32)


def _decode_pair(params, server, label):
    greedy = server.submit([5, 9, 2, 7], n_new=9,
                           request_id=f"req-greedy-{label}")
    key = jax.random.fold_in(jax.random.PRNGKey(3), 0)
    sampled = server.submit(
        [1, 2, 3, 4], n_new=12,
        sampling=(key, jnp.float32(0.8), jnp.float32(0.9)),
        request_id=f"req-sampled-{label}",
    )
    return greedy, sampled


@pytest.mark.parametrize("block, shape", [
    ("", dict(window=1)),
    ("", dict()),
    ("window-block", dict(window=4, prefix_cache=False)),
], ids=["one-step", "overlap", "window-block"])
def test_observability_on_is_token_bit_identical(params, probe_blocks,
                                                 block, shape):
    """The acceptance bar: SLO engine + occupancy ring + full-sample
    tracing all ON change no served token — greedy and sampled, at
    one-step windows and the default, and on a window block
    (``probe_blocks``), whose occupancy counts a second pool."""
    cfg = CFG
    if block:
        cfg, params = probe_blocks[block]
    off_server = PagedGenerationServer(params, cfg, slots=2, pages=32,
                                       **shape)
    try:
        off = _decode_pair(params, off_server, "off")
    finally:
        off_server.close()
    on_server = PagedGenerationServer(
        params, cfg, slots=2, pages=32, tracer=Tracer(sample=1.0),
        **_OBS, **shape,
    )
    try:
        on = _decode_pair(params, on_server, "on")
        stats = on_server.stats()
    finally:
        on_server.close()
    assert off == on, f"observability changed tokens ({shape})"
    assert stats["slo_snapshots_total"] >= 1
    assert stats["occupancy_samples_total"] >= 1
    if not block:
        assert off[0] == reference(params, [5, 9, 2, 7], 9)


def test_device_time_itl_and_occupancy_fill(params):
    server = PagedGenerationServer(params, CFG, slots=2, pages=16,
                                   **_OBS)
    try:
        server.submit([5, 9, 2], n_new=6)
        stats = server.stats()
    finally:
        server.close()
    # Device-time attribution: the device slice of every window.
    dev = stats["window_device_ms"]
    assert dev["count"] >= 1 and dev["sum"] > 0
    # ITL observed once per normal finish (n_new > 1).
    assert stats["itl_ms"]["count"] == 1
    assert stats["requests_done_total"] == 1
    assert stats["tokens_done_total"] == 6
    # Occupancy gauges flatten the latest boundary sample.
    assert stats["occupancy_pages_total"] == 16
    assert stats["occupancy_queue_depth"] == 0
    assert stats["occupancy_samples_total"] >= 1
    # SLO gauges exist the moment the engine is on.
    assert "slo_burn_fast" in stats and "slo_alert" in stats


def test_slice_op_broadcast_ms_surfaces_in_stats(params):
    server = PagedGenerationServer(params, CFG, slots=2, pages=16)
    try:
        # The slice transport exposes op_broadcast_ms; a single-host
        # cache does not. stats() picks it up by duck type.
        assert "slice_op_ms" not in server.stats()
        server._cache.op_broadcast_ms = {"3": [4, 2.5]}
        stats = server.stats()
        assert stats["slice_op_ms"] == {"3": [4, 2.5]}
    finally:
        server.close()
    text = render_metrics({"ok": True, "serving": stats})
    check_prometheus_text(text)
    assert 'kvedge_serve_device_broadcast_frames_total{op="3"} 4' in text


def test_burn_gated_shed_protects_top_class(params):
    server = PagedGenerationServer(params, CFG, slots=2, pages=16,
                                   slo=SloObjectives(), slo_shed=True)
    try:
        # The gate is installed and quiet: no alert, nothing sheds.
        assert server._sched.burn_input is not None
        assert server.submit([5, 9, 2], n_new=2, priority="batch")
        # Force the alert hot: batch sheds at the door with the burn
        # reason; the top class never burn-sheds.
        server._sched.burn_input = lambda: True
        with pytest.raises(ServerOverloaded, match="burn-rate"):
            server.submit([5, 9, 2], n_new=2, priority="batch")
        assert server.submit([5, 9, 2], n_new=2,
                             priority="interactive")
        assert server.stats()["sched_shed_total"] == 1
    finally:
        server.close()


def test_slo_shed_requires_objectives(params):
    # Knob-off default: no gate installed at all.
    server = PagedGenerationServer(params, CFG, slots=2, pages=16)
    try:
        assert server._sched.burn_input is None
    finally:
        server.close()
    with pytest.raises(ValueError, match="slo_shed"):
        PagedGenerationServer(params, CFG, slots=2, pages=16,
                              slo_shed=True)


# ---- flight bundle --------------------------------------------------------


def test_flight_bundle_complete_and_consistent_after_poison(params):
    tr = Tracer(sample=1.0)
    server = PagedGenerationServer(params, CFG, slots=2, pages=24,
                                   tracer=tr, **_OBS)
    try:
        server.submit([3, 1, 4, 1, 5], n_new=4, request_id="req-a")
        cache = server._cache
        real = cache.harvest_window

        def dying(handle):
            raise RuntimeError("injected: harvest died mid-overlap")

        cache.harvest_window = dying
        with pytest.raises(ServingFailure):
            server.submit([3, 1, 4], n_new=20, request_id="req-b")
        server._thread.join(timeout=30)
        cache.harvest_window = real

        bundle = server.flight_bundle()
        json.dumps(bundle)  # JSON-complete, no numpy leakage
        assert bundle["bundle_version"] == 1
        assert bundle["degraded"] == 1 and bundle["reason"]
        assert bundle["slo"] is not None
        assert bundle["occupancy_tail"]
        assert len(bundle["config_fingerprint"]) == 12
        assert bundle["config"]["slots"] == 2
        assert bundle["config"]["slo"]["target"] == 0.99
        books = bundle["page_accounting"]
        assert books["free"] + books["live"] == books["pages_total"]
        assert {"name", "cat", "t_ms"} <= set(bundle["trace_tail"][0])
        assert "poison" in {e["name"] for e in bundle["trace_tail"]}
        # The bundle IS the server's final state: its SLO gauges agree
        # with a fresh stats() snapshot on the quiescent pool.
        stats = server.stats()
        for key in stats:
            if key.startswith("slo_"):
                assert bundle["metrics"][key] == stats[key], key
        # Same config -> same fingerprint; a changed config diverges.
        again = server.flight_bundle()
        assert again["config_fingerprint"] == \
            bundle["config_fingerprint"]
    finally:
        server.close()


def test_bundle_persists_next_to_last_failure(tmp_path):
    """Workload wiring: on poison, flight-bundle.json lands on the
    state volume beside last-failure.json (serving_bundle on)."""
    import time

    from kvedge_tpu.runtime import heartbeat
    from kvedge_tpu.runtime.status import GenerateUnavailable
    from kvedge_tpu.runtime.workload import run_serve_payload

    cfg = _cfg(tmp_path, payload_serving="paged", serving_trace="on",
               serving_slo=True, serving_bundle=True,
               serving_occupancy_ring=64,
               serving_recovery_attempts=0)
    check, serve_fn = run_serve_payload(cfg)
    assert check.ok, check.error
    try:
        server = None
        for cell in serve_fn.close.__closure__:
            try:
                if isinstance(cell.cell_contents, PagedGenerationServer):
                    server = cell.cell_contents
            except ValueError:
                continue
        assert server is not None

        def die(*a, **k):
            raise RuntimeError("injected: decode seam died")

        for seam in ("dispatch_window", "harvest_window"):
            setattr(server._cache, seam, die)
        with pytest.raises((ServingFailure, GenerateUnavailable)):
            serve_fn({"tokens": [[1, 2, 3]], "n_new": 8})
        bundle = None
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline:
            bundle = heartbeat.read_flight_bundle(cfg.state_dir)
            if bundle is not None:
                break
            time.sleep(0.05)
        assert bundle is not None, "no flight bundle persisted"
        assert bundle["bundle_version"] == 1
        assert bundle["degraded"] == 1
        assert bundle["boot_count"] >= 0 and bundle["ts"] > 0
        assert heartbeat.read_failure_record(cfg.state_dir) is not None
    finally:
        serve_fn.close()


def _cfg(tmp_path, **overrides):
    base = dict(
        name="slo-test",
        state_dir=str(tmp_path / "state"),
        expected_platform="cpu",
        status_port=0,
        status_bind="127.0.0.1",
        payload="serve",
        train_seq=16,
    )
    base.update(overrides)
    return dataclasses.replace(RuntimeConfig(), **base)


# ---- config knobs ---------------------------------------------------------


def test_runtime_config_slo_knobs_roundtrip(tmp_path):
    cfg = _cfg(tmp_path, serving_slo=True, serving_slo_target=0.999,
               serving_slo_ttft_ms=500.0, serving_slo_fast_s=30.0,
               serving_slo_slow_s=300.0, serving_slo_shed=True,
               serving_bundle=True, serving_occupancy_ring=128)
    cfg.validate()
    text = cfg.to_toml()
    assert "serving_slo = true" in text
    assert "serving_slo_target = 0.999" in text
    assert "serving_occupancy_ring = 128" in text
    for bad in (
        dict(serving_slo_target=1.5),
        dict(serving_slo_ttft_ms=0.0),
        dict(serving_slo=True, serving_slo_fast_s=900.0),
        dict(serving_slo_shed=True),               # needs serving_slo
        dict(serving_occupancy_ring=-1),
    ):
        with pytest.raises(RuntimeConfigError):
            _cfg(tmp_path, **bad).validate()


# ---- end to end -----------------------------------------------------------


def test_http_slo_metrics_and_bundle_end_to_end(tmp_path):
    """One booted runtime with the whole stack on: /slo serves the
    burn document, /debug/bundle the post-mortem, /metrics passes
    conformance with the rung-25 families, and /trace carries the
    occupancy counter track."""
    from kvedge_tpu.runtime.boot import start_runtime

    handle = start_runtime(_cfg(
        tmp_path, payload_serving="paged", serving_trace="on",
        serving_slots=2, serving_slo=True, serving_slo_fast_s=1.0,
        serving_slo_slow_s=10.0, serving_bundle=True,
        serving_occupancy_ring=64,
    ))
    base = f"http://127.0.0.1:{handle.status_port}"
    try:
        code, doc, _ = _get(f"{base}/slo")
        assert code == 200
        assert doc["objectives"]["fast_window_s"] == 1.0
        assert doc["burn_alert_thresholds"]["fast"] == BURN_FAST_ALERT
        assert doc["burn_alert_thresholds"]["slow"] == BURN_SLOW_ALERT

        code, bundle, _ = _get(f"{base}/debug/bundle")
        assert code == 200
        assert bundle["bundle_version"] == 1 and bundle["degraded"] == 0
        assert not bundle["page_accounting"]["free_dup"]

        with urllib.request.urlopen(f"{base}/metrics", timeout=30) as r:
            text = r.read().decode()
        families = check_prometheus_text(text)
        assert families["kvedge_serve_slo_burn_fast"] == "gauge"
        assert families["kvedge_serve_device_ms_window"] == "histogram"
        assert families["kvedge_serve_occupancy_pages_total"] == "gauge"
        assert families["kvedge_serve_requests_done_total"] == "counter"

        code, trace, _ = _get(f"{base}/trace")
        assert code == 200
        events = _check_chrome(trace)
        assert any(e["ph"] == "C" for e in events)
    finally:
        handle.shutdown()
