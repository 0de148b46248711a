"""Request-scoped tracing (SERVING.md rung 18): the flight recorder.

The tracing contract under test, end to end: a lock-cheap bounded ring
records span timelines keyed by request IDs minted at ingress; tracing
on is token-BIT-IDENTICAL to off (greedy and sampled, overlap on/off);
``GET /trace`` exports valid Chrome trace-event JSON; on pool poison
the recorder's tail embeds in ``last-failure.json``; the ``/metrics``
exposition — including the new per-stage ``serve_ttft_ms`` split —
passes a strict Prometheus text-format conformance check. All
fixed-seed and fast: these run in the tier-1 gate.
"""

import dataclasses
import json
import re
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kvedge_tpu.config.runtime_config import RuntimeConfig
from kvedge_tpu.models import TransformerConfig, generate, init_params
from kvedge_tpu.models.serving import PagedGenerationServer
from kvedge_tpu.runtime.failures import ServingFailure
from kvedge_tpu.runtime.status import StatusServer, render_metrics
from kvedge_tpu.runtime.tracing import (
    ADMIT_PHASES,
    LOOP_PHASES,
    POSTMORTEM_EVENTS,
    Tracer,
    clean_request_id,
    new_request_id,
)

pytestmark = pytest.mark.trace

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


# ---- recorder unit behavior ----------------------------------------------


def test_ring_is_bounded_and_counts_drops():
    tr = Tracer(sample=1.0, capacity=8)
    for i in range(20):
        tr.event(f"e{i}", "test")
    assert len(tr) == 8
    assert tr.dropped == 12
    stats = tr.stats()
    assert stats["trace_events"] == 8
    assert stats["trace_events_total"] == 20
    assert stats["trace_dropped_total"] == 12
    assert stats["trace_sample"] == 1.0
    # The ring kept the NEWEST events (flight-recorder semantics).
    assert [d["name"] for d in tr.last_events(3)] == ["e17", "e18", "e19"]


def test_request_id_mint_and_hygiene():
    rid = new_request_id()
    assert rid.startswith("req-") and len(rid) == 4 + 16
    assert new_request_id() != rid  # random, not sequential
    assert clean_request_id(rid) == rid
    assert clean_request_id("abc-DEF_1.2:3") == "abc-DEF_1.2:3"
    # Hostile or unusable values sanitize to "" (caller mints instead).
    assert clean_request_id("bad id!") == ""
    assert clean_request_id("x\ny") == ""
    assert clean_request_id("") == ""
    assert clean_request_id(None) == ""
    assert clean_request_id(123) == ""
    # Over-long IDs truncate to the cap, then validate.
    assert clean_request_id("a" * 200) == "a" * 64


def test_from_knob():
    assert Tracer.from_knob("off") is None
    assert Tracer.from_knob("") is None
    assert Tracer.from_knob(None) is None
    assert Tracer.from_knob(False) is None
    on = Tracer.from_knob("on")
    assert on is not None and on.sample == 1.0
    rate = Tracer.from_knob(0.25)
    assert rate is not None and rate.sample == 0.25
    assert Tracer.from_knob(0.0) is None  # sample-nothing == off
    for bad in (-0.5, 1.5):
        with pytest.raises(ValueError):
            Tracer.from_knob(bad)


def test_sampling_is_deterministic_and_fate_shared():
    a, b = Tracer(sample=0.5), Tracer(sample=0.5)
    rids = [f"req-{i}" for i in range(200)]
    # Same decision on every tracer instance (= every pod) per rid.
    assert [a.sampled(r) for r in rids] == [b.sampled(r) for r in rids]
    picked = sum(a.sampled(r) for r in rids)
    assert 0 < picked < 200  # a real split, not all-or-nothing
    assert all(Tracer(sample=1.0).sampled(r) for r in rids)


def test_last_events_tail_oldest_first():
    tr = Tracer(sample=1.0, capacity=256)
    t0 = tr.now()
    tr.span("prefill", "serve", t0, t0 + 0.002, rid="req-x",
            args={"prompt": 3})
    tr.event("poison", "failure", args={"type": "RuntimeError"})
    docs = tr.last_events()
    assert len(docs) == 2
    assert docs[0]["name"] == "prefill" and docs[1]["name"] == "poison"
    assert docs[0]["rid"] == "req-x"
    assert docs[0]["dur_ms"] == pytest.approx(2.0, abs=0.5)
    assert "dur_ms" not in docs[1]  # instants carry no duration
    json.dumps(docs)  # JSON-safe by construction
    assert len(Tracer(sample=1.0).last_events()) == 0
    assert POSTMORTEM_EVENTS > 0


# ---- Chrome trace-event export -------------------------------------------


def _check_chrome(doc: dict) -> list:
    """Schema-check a Chrome/Perfetto trace-event document; returns the
    non-metadata events."""
    json.dumps(doc)  # must be pure JSON
    assert doc["displayTimeUnit"] in ("ms", "ns")
    events = doc["traceEvents"]
    assert isinstance(events, list)
    named_tracks = {}
    payload = []
    counters = []
    for ev in events:
        assert isinstance(ev["name"], str) and ev["name"]
        assert ev["ph"] in ("X", "i", "M", "C")
        assert ev["pid"] == 1
        assert isinstance(ev["tid"], int)
        if ev["ph"] == "M":
            assert ev["name"] == "thread_name"
            named_tracks[ev["tid"]] = ev["args"]["name"]
            continue
        assert isinstance(ev["cat"], str) and ev["cat"]
        assert isinstance(ev["ts"], (int, float)) and ev["ts"] >= 0
        if ev["ph"] == "C":
            # Counter track (rung-25 occupancy timeline): numeric args
            # only; counters draw their own track, so no thread_name
            # metadata requirement applies.
            assert isinstance(ev["args"], dict) and ev["args"]
            for v in ev["args"].values():
                assert isinstance(v, (int, float))
            counters.append(ev)
            continue
        if ev["ph"] == "X":
            assert ev["dur"] >= 0
        else:
            assert ev["s"] == "t"
        payload.append(ev)
    for ev in payload:  # every span rides a named track
        assert ev["tid"] in named_tracks
        assert named_tracks[ev["tid"]] == ev["cat"]
    return payload + counters


def test_export_chrome_is_valid_trace_event_json():
    tr = Tracer(sample=1.0)
    t0 = tr.now()
    tr.span("prefill", "serve", t0, rid="req-1", args={"prompt": 4})
    tr.span("queue", "sched", t0, rid="req-1")
    tr.event("poison", "failure")
    doc = tr.export_chrome()
    events = _check_chrome(doc)
    assert len(events) == 3
    assert {e["cat"] for e in events} == {"serve", "sched", "failure"}
    by_name = {e["name"]: e for e in events}
    assert by_name["prefill"]["args"] == {"prompt": 4, "rid": "req-1"}
    assert doc["otherData"]["dropped"] == 0
    assert doc["otherData"]["sample"] == 1.0


# ---- bit-identity: tracing on == tracing off -----------------------------


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


def test_tracing_is_token_bit_identical(params):
    """The acceptance bar: greedy AND sampled streams, one-step
    windows AND the default — the traced run's tokens equal the
    untraced run's bit for bit, and the traced run actually recorded
    its spans."""
    for window in (1, 64):
        off_server = PagedGenerationServer(params, CFG, slots=2,
                                           pages=16, window=window)
        try:
            off = _decode_pair(params, off_server, "off")
        finally:
            off_server.close()
        tr = Tracer(sample=1.0)
        on_server = PagedGenerationServer(params, CFG, slots=2,
                                          pages=16, window=window,
                                          tracer=tr)
        try:
            on = _decode_pair(params, on_server, "on")
        finally:
            on_server.close()
        assert off == on, f"tracing changed tokens (window={window})"
        names = {rec[3] for rec in tr._snapshot()}
        assert {"prefill", "decode", "queue"} <= names
        # The new sites: every phase of the loop and of the submit
        # path is a span of the ring, and the loop keeps its
        # dispatch-to-harvest "window" span (it spans phases).
        # (whether the loop ever parks for work is up to the race
        # between its start and the first admission)
        assert (set(LOOP_PHASES) - {"loop/wait_work"}
                | set(ADMIT_PHASES)) <= names
        assert "window" in names
        # The two ledgers' spans (ISSUE 38) are on the same pin: a
        # sampled request's root span, the states that had no span
        # before, and its holds of the work lock.
        assert {"request", "admit", "join_wait", "lock/admit/start",
                "lock/admit/prefill_chunk",
                "lock/admit/first_pick"} <= names
    assert off[0] == reference(params, [5, 9, 2, 7], 9)


def test_request_spans_attribute_by_rid(params):
    tr = Tracer(sample=1.0)
    server = PagedGenerationServer(params, CFG, slots=2, pages=16,
                                   tracer=tr)
    try:
        server.submit([5, 9, 2], n_new=4, request_id="req-abc")
    finally:
        server.close()
    mine = [rec for rec in tr._snapshot() if rec[5] == "req-abc"]
    names = {rec[3] for rec in mine}
    assert {"enqueue", "queue", "prefill", "decode"} <= names
    # Per-stage histograms fed from the same boundaries, always on.
    # (Server is closed; the snapshots were taken while it served.)


def test_unsampled_request_keeps_fabric_spans_only(params):
    tr = Tracer(sample=0.0001)
    rid = next(f"req-{i}" for i in range(1000)
               if not tr.sampled(f"req-{i}"))
    server = PagedGenerationServer(params, CFG, slots=2, pages=16,
                                   tracer=tr)
    try:
        traced = server.submit([5, 9, 2], n_new=4, request_id=rid)
    finally:
        server.close()
    assert traced == reference(params, [5, 9, 2], 4)
    assert len(tr) > 0  # window/step fabric recorded regardless
    assert not [rec for rec in tr._snapshot() if rec[5] == rid]


def test_stage_histograms_always_on(params):
    """serve_ttft_ms and the queue/decode split exist and fill WITHOUT
    a tracer — the /metrics story must not depend on serving_trace."""
    server = PagedGenerationServer(params, CFG, slots=2, pages=16)
    try:
        server.submit([5, 9, 2], n_new=4)
        stats = server.stats()
    finally:
        server.close()
    for key in ("ttft_ms", "queue_ms", "decode_ms"):
        hist = stats[key]
        assert len(hist["counts"]) == len(hist["edges"]) + 1
        assert hist["count"] == sum(hist["counts"]) >= 1
    assert "trace_events" not in stats  # no tracer, no trace gauges


def test_the_prefill_span_is_the_parent_of_its_chunk_phases(params):
    """One request's ring spans, by time: queue, then ``prefill`` from
    the admission to the pick of the first token, holding the request's
    chunk phases (lock wait, chunk, lock wait, chunk, ...) and its
    ``admit/first_pick``, each with the request's id; the loop's phases
    carry none."""
    tr = Tracer(sample=1.0)
    server = PagedGenerationServer(params, CFG, slots=2, pages=16,
                                   prefill_chunk=2, tracer=tr)
    try:
        server.submit([5, 9, 2, 7, 1], n_new=4, request_id="req-abc")
    finally:
        server.close()
    mine = sorted((rec for rec in tr._snapshot() if rec[5] == "req-abc"
                   and rec[0] == "X"), key=lambda rec: rec[1])
    (prefill,) = [rec for rec in mine if rec[3] == "prefill"]
    (queue,) = [rec for rec in mine if rec[3] == "queue"]
    inside = [rec for rec in mine if rec[3].startswith("admit/")]
    assert [rec[3] for rec in inside] == [
        "admit/lock_wait", "admit/prefill_chunk"] * 3 + ["admit/first_pick"]
    lo, hi = prefill[1], prefill[1] + prefill[2]
    assert all(lo <= rec[1] and rec[1] + rec[2] <= hi + 1e-9
               for rec in inside)
    assert queue[1] + queue[2] <= lo + 1e-9
    assert hi == pytest.approx(inside[-1][1] + inside[-1][2])
    chunk_args = [rec[6] for rec in inside if rec[3] == "admit/prefill_chunk"]
    assert chunk_args == [{"off": 0, "n": 2}, {"off": 2, "n": 2},
                          {"off": 4, "n": 1}]
    loop = [rec for rec in tr._snapshot() if rec[3] in LOOP_PHASES]
    assert loop and all(rec[5] == "" for rec in loop)
    _check_chrome(tr.export_chrome())


def test_an_unsampled_requests_phases_stay_out_of_the_ring(params):
    tr = Tracer(sample=0.0001)
    rid = next(f"req-{i}" for i in range(1000)
               if not tr.sampled(f"req-{i}"))
    server = PagedGenerationServer(params, CFG, slots=2, pages=16,
                                   tracer=tr)
    try:
        server.submit([5, 9, 2], n_new=4, request_id=rid)
        stats = server.stats()
    finally:
        server.close()
    names = {rec[3] for rec in tr._snapshot()}
    assert set(LOOP_PHASES) - {"loop/wait_work"} <= names
    assert not names & set(ADMIT_PHASES)
    # the other two sinks do not ask the sampler
    assert stats["prefill_chunk_ms"]["count"] == 1
    assert stats["phase_ms"]["admit/first_pick"][0] == 1


@pytest.mark.parametrize("key", ["prefill_lock_wait_ms", "prefill_chunk_ms",
                                 "first_emit_ms"])
def test_metrics_render_the_phase_histograms(params, key):
    """The three new histograms reach /metrics the way serve_ttft_ms
    does, with no tracer; the counters and phase_ms stay in stats()."""
    server = PagedGenerationServer(params, CFG, slots=2, pages=16)
    try:
        server.submit([5, 9, 2], n_new=4)
        stats = server.stats()
    finally:
        server.close()
    text = render_metrics({"ok": True, "boot_count": 1, "uptime_s": 2.5,
                           "heartbeat_seq": 3, "heartbeat_age_s": 0.1,
                           "serving": stats})
    families = check_prometheus_text(text)
    assert families[f"kvedge_serve_{key}"] == "histogram"
    m = re.search(rf"^kvedge_serve_{key}_count (\d+)$", text, re.M)
    assert m and int(m.group(1)) == stats[key]["count"] == 1
    assert "phase_ms" not in text and "clock_s" not in text
    assert stats["clock_s"] > 0 and stats["tokens_emitted_total"] == 4


def test_tracer_survives_poison_and_revive(params):
    """The recorder is plain host state: it must ride through a pool
    poison and revive() unchanged, with the poison and revive visible
    in the same timeline as the spans they interrupt."""
    tr = Tracer(sample=1.0)
    server = PagedGenerationServer(params, CFG, slots=2, pages=24,
                                   tracer=tr)
    prompt = [3, 1, 4, 1, 5]
    try:
        baseline = server.submit(prompt, n_new=4, request_id="req-a")
        cache = server._cache
        real = cache.harvest_window
        calls = []

        def dying(handle):
            calls.append(1)
            if len(calls) == 2:
                raise RuntimeError("injected: harvest died mid-overlap")
            return real(handle)

        cache.harvest_window = dying
        with pytest.raises(ServingFailure):
            server.submit(prompt, n_new=40, request_id="req-b")
        server._thread.join(timeout=30)
        cache.harvest_window = real
        server.revive()
        assert server.tracer is tr  # same recorder, same ring
        again = server.submit(prompt, n_new=4, request_id="req-c")
        assert again == baseline
        names = {rec[3] for rec in tr._snapshot()}
        assert {"poison", "revive"} <= names
        assert "req-c" in {rec[5] for rec in tr._snapshot()}
    finally:
        server.close()


# ---- /metrics conformance ------------------------------------------------

_SAMPLE_RE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{[^}]*\})? (\S+)$"
)
_LE_RE = re.compile(r'^\{le="([^"]+)"\}$')


def check_prometheus_text(text: str) -> dict:
    """Strict text-format conformance over a whole exposition: unique
    HELP/TYPE per family, every sample under a declared family,
    counters end in _total, histogram ``le`` buckets cumulative and
    +Inf-terminated with a matching _count. Returns {family: type}."""
    helps: dict = {}
    types: dict = {}
    samples: dict = {}
    for ln, line in enumerate(text.splitlines(), 1):
        if not line.strip():
            continue
        if line.startswith("# HELP "):
            name, _, help_text = line[len("# HELP "):].partition(" ")
            assert name not in helps, f"duplicate HELP for {name}"
            assert help_text.strip(), f"empty HELP for {name}"
            helps[name] = help_text
            continue
        if line.startswith("# TYPE "):
            name, _, mtype = line[len("# TYPE "):].partition(" ")
            assert name not in types, f"duplicate TYPE for {name}"
            assert name in helps, f"TYPE before HELP for {name}"
            assert mtype in ("gauge", "counter", "histogram"), line
            types[name] = mtype
            continue
        assert not line.startswith("#"), f"line {ln}: bad comment {line}"
        m = _SAMPLE_RE.match(line)
        assert m, f"line {ln}: unparseable sample {line!r}"
        name, labels, value = m.groups()
        float(value)  # every sample value must parse
        family = name
        for suffix in ("_bucket", "_sum", "_count"):
            base = name[: -len(suffix)] if name.endswith(suffix) else ""
            if base and types.get(base) == "histogram":
                family = base
        assert family in types, f"sample {name} has no TYPE declaration"
        samples.setdefault(family, []).append((name, labels, float(value)))
    for name, mtype in types.items():
        assert samples.get(name), f"declared family {name} has no samples"
        if mtype == "counter":
            assert name.endswith("_total"), (
                f"counter {name} must end in _total"
            )
        if mtype != "histogram":
            continue
        rows = samples[name]
        buckets = [(lbl, v) for n, lbl, v in rows
                   if n == name + "_bucket"]
        assert buckets, f"histogram {name} has no buckets"
        les, counts = [], []
        for lbl, v in buckets:
            m = _LE_RE.match(lbl or "")
            assert m, f"histogram {name} bucket without le label: {lbl}"
            les.append(float("inf") if m.group(1) == "+Inf"
                       else float(m.group(1)))
            counts.append(v)
        assert les[-1] == float("inf"), f"{name} missing +Inf bucket"
        assert les == sorted(les), f"{name} le edges not increasing"
        assert counts == sorted(counts), (
            f"{name} bucket counts not cumulative"
        )
        count_samples = [v for n, _, v in rows if n == name + "_count"]
        assert count_samples == [counts[-1]], (
            f"{name}_count disagrees with the +Inf bucket"
        )
        assert [n for n, _, _ in rows if n == name + "_sum"], (
            f"histogram {name} has no _sum"
        )
    return types


def test_conformance_checker_catches_violations():
    # The checker itself must have teeth: each canned violation trips.
    good = ("# HELP kvedge_x_total things\n"
            "# TYPE kvedge_x_total counter\nkvedge_x_total 1\n")
    check_prometheus_text(good)
    bad_cases = (
        good + good,  # duplicate HELP/TYPE
        "# HELP kvedge_y things\n# TYPE kvedge_y counter\nkvedge_y 1\n",
        "kvedge_orphan 1\n",  # sample without TYPE
        ("# HELP kvedge_h ms\n# TYPE kvedge_h histogram\n"
         'kvedge_h_bucket{le="1"} 5\nkvedge_h_bucket{le="+Inf"} 3\n'
         "kvedge_h_sum 1\nkvedge_h_count 3\n"),  # non-cumulative
        ("# HELP kvedge_h ms\n# TYPE kvedge_h histogram\n"
         'kvedge_h_bucket{le="1"} 1\n'
         "kvedge_h_sum 1\nkvedge_h_count 1\n"),  # no +Inf bucket
    )
    for text in bad_cases:
        with pytest.raises(AssertionError):
            check_prometheus_text(text)


# ---- the serve payload end to end ----------------------------------------


def _cfg(tmp_path, **overrides):
    base = dict(
        name="trace-test",
        state_dir=str(tmp_path / "state"),
        expected_platform="cpu",
        status_port=0,
        status_bind="127.0.0.1",
        payload="serve",
        train_seq=16,
    )
    base.update(overrides)
    return dataclasses.replace(RuntimeConfig(), **base)


def _find_server(serve_fn) -> PagedGenerationServer:
    """The paged server behind a workload serve_fn, via the close
    closure (test-only introspection; the public API deliberately does
    not expose the server object)."""
    for cell in serve_fn.close.__closure__:
        try:
            if isinstance(cell.cell_contents, PagedGenerationServer):
                return cell.cell_contents
        except ValueError:
            continue
    raise AssertionError("no PagedGenerationServer behind serve_fn")


def _get(url):
    try:
        with urllib.request.urlopen(url, timeout=30) as resp:
            return resp.status, json.loads(resp.read()), dict(resp.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read()), dict(e.headers)


def _post(url, doc, headers=None):
    req = urllib.request.Request(
        url, data=json.dumps(doc).encode(),
        headers={"Content-Type": "application/json", **(headers or {})},
        method="POST",
    )
    try:
        with urllib.request.urlopen(req, timeout=120) as resp:
            return resp.status, json.loads(resp.read()), dict(resp.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read()), dict(e.headers)


def test_serve_payload_threads_the_knob_and_echoes_ids(tmp_path):
    from kvedge_tpu.runtime.workload import run_serve_payload

    # Default: tracing off, IDs still minted and echoed.
    check, serve_fn = run_serve_payload(_cfg(tmp_path))
    assert check.ok, check.error
    try:
        assert serve_fn.tracer is None
        out = serve_fn({"tokens": [[1, 2, 3]], "n_new": 2})
        assert out["request_id"].startswith("req-")
        echo = serve_fn({"tokens": [[1, 2, 3]], "n_new": 2,
                         "_request_id": "caller-1"})
        assert echo["request_id"] == "caller-1"
        assert "trace_events" not in serve_fn.stats()
    finally:
        serve_fn.close()


def test_poison_embeds_flight_recorder_in_last_failure(tmp_path):
    """The post-mortem acceptance bar: a seeded poison lands the flight
    recorder's tail inside last-failure.json on the state volume."""
    import time

    from kvedge_tpu.runtime import heartbeat
    from kvedge_tpu.runtime.status import GenerateUnavailable
    from kvedge_tpu.runtime.workload import run_serve_payload

    cfg = _cfg(tmp_path, payload_serving="paged", serving_trace="on",
               serving_recovery_attempts=0)
    check, serve_fn = run_serve_payload(cfg)
    assert check.ok, check.error
    try:
        assert serve_fn.tracer is not None
        server = _find_server(serve_fn)

        def die(*a, **k):
            raise RuntimeError("injected: decode seam died")

        for seam in ("dispatch_window", "harvest_window"):
            setattr(server._cache, seam, die)
        with pytest.raises((ServingFailure, GenerateUnavailable)):
            serve_fn({"tokens": [[1, 2, 3]], "n_new": 8})
        record = None
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline:
            record = heartbeat.read_failure_record(cfg.state_dir)
            if record is not None:
                break
            time.sleep(0.05)
        assert record is not None, "no failure record persisted"
        trace = record["trace"]
        assert isinstance(trace, list) and trace
        assert len(trace) <= POSTMORTEM_EVENTS
        assert all({"name", "cat", "t_ms"} <= set(ev) for ev in trace)
        assert "poison" in {ev["name"] for ev in trace}
    finally:
        serve_fn.close()


def test_http_trace_metrics_and_request_ids_end_to_end(tmp_path):
    """One booted runtime: X-Request-Id in -> echoed out (header and
    body), GET /trace exports the request's spans as valid Chrome JSON,
    /metrics passes strict conformance with the new per-stage
    histograms, and /profile/traces lists on-disk captures."""
    from kvedge_tpu.runtime.boot import start_runtime

    handle = start_runtime(_cfg(
        tmp_path, payload_serving="paged", serving_trace="on",
        serving_slots=2,
    ))
    base = f"http://127.0.0.1:{handle.status_port}"
    try:
        code, doc, headers = _post(
            f"{base}/generate", {"tokens": [[1, 2, 3]], "n_new": 4},
            headers={"X-Request-Id": "cli-42"},
        )
        assert code == 200
        assert doc["request_id"] == "cli-42"
        assert headers["X-Request-Id"] == "cli-42"
        # A hostile header is sanitized away; the pod mints instead.
        code, doc, headers = _post(
            f"{base}/generate", {"tokens": [[1, 2, 3]], "n_new": 4},
            headers={"X-Request-Id": "bad id!"},
        )
        assert code == 200
        assert doc["request_id"].startswith("req-")
        assert headers["X-Request-Id"] == doc["request_id"]

        code, trace, _ = _get(f"{base}/trace")
        assert code == 200
        events = _check_chrome(trace)
        rids = {e.get("args", {}).get("rid") for e in events}
        assert "cli-42" in rids

        with urllib.request.urlopen(f"{base}/metrics", timeout=30) as r:
            text = r.read().decode()
        families = check_prometheus_text(text)
        for family in ("kvedge_serve_ttft_ms", "kvedge_serve_queue_ms",
                       "kvedge_serve_decode_ms"):
            assert families[family] == "histogram"
        assert families["kvedge_serve_latency_ms_total"] == "counter"
        assert "kvedge_serve_latency_ms_sum" not in families
        assert families["kvedge_serve_trace_events_total"] == "counter"
        # Both HTTP requests observed a first token (the boot probe may
        # add one more — it shares the server's histograms).
        m = re.search(r"^kvedge_serve_ttft_ms_count (\d+)$", text, re.M)
        assert m and int(m.group(1)) >= 2

        code, listing, _ = _get(f"{base}/profile/traces")
        assert code == 200 and listing["traces"] == []
        code, _doc, _ = _post(f"{base}/profile?seconds=0.2", {})
        assert code == 200
        code, listing, _ = _get(f"{base}/profile/traces")
        assert code == 200 and len(listing["traces"]) == 1
        entry = listing["traces"][0]
        assert entry["name"].startswith("trace-")
        assert entry["seq"] == 1
        assert entry["bytes"] > 0 and entry["age_s"] >= 0
    finally:
        handle.shutdown()


def test_trace_route_404_when_off_and_profile_traces_503_unwired():
    srv = StatusServer("127.0.0.1", 0, snapshot=lambda: {"ok": True})
    srv.start()
    try:
        code, doc, _ = _get(f"http://127.0.0.1:{srv.port}/trace")
        assert code == 404 and "serving_trace" in doc["error"]
        code, doc, _ = _get(
            f"http://127.0.0.1:{srv.port}/profile/traces"
        )
        assert code == 503
    finally:
        srv.shutdown()


def test_render_metrics_without_serving_is_conformant():
    text = render_metrics({"ok": True, "boot_count": 1, "uptime_s": 2.5,
                           "heartbeat_seq": 3, "heartbeat_age_s": 0.1})
    families = check_prometheus_text(text)
    assert "kvedge_up" in families
