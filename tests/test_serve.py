"""The ``serve`` payload: POST /generate against the checkpointed model.

Closes the state-volume loop the runtime exists for: ``train`` writes
checkpoints through the volume, a later ``serve`` pod restores the latest
one and serves greedy decode over HTTP. Correctness anchor: the endpoint's
output must equal the teacher-forced argmax of the restored parameters —
the same cross-check discipline as the inference probe.
"""

import dataclasses
import json
import urllib.error
import urllib.request

import numpy as np
import pytest

from kvedge_tpu.config.runtime_config import RuntimeConfig
from kvedge_tpu.runtime.boot import start_runtime
from kvedge_tpu.runtime.workload import (
    run_serve_payload,
    run_train_payload,
    train_model_config,
)


def _cfg(tmp_path, **overrides):
    base = dict(
        name="serve-test",
        state_dir=str(tmp_path / "state"),
        expected_platform="cpu",
        status_port=0,
        status_bind="127.0.0.1",
        payload="serve",
        train_seq=16,
    )
    base.update(overrides)
    return dataclasses.replace(RuntimeConfig(), **base)


def _post(url, doc, token=None):
    headers = {"Content-Type": "application/json"}
    if token:
        headers["Authorization"] = f"Bearer {token}"
    req = urllib.request.Request(
        url, data=json.dumps(doc).encode(), headers=headers, method="POST"
    )
    try:
        with urllib.request.urlopen(req, timeout=120) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def test_serve_payload_fresh_volume(tmp_path):
    check, serve_fn = run_serve_payload(_cfg(tmp_path))
    assert check.ok, check.error
    out = serve_fn({"tokens": [[1, 2, 3]], "n_new": 3})
    assert out["restored_step"] is None  # nothing trained yet
    assert len(out["tokens"][0]) == 6
    assert all(isinstance(t, int) for t in out["tokens"][0])


def test_serve_matches_teacher_forcing(tmp_path):
    import jax
    import jax.numpy as jnp

    from kvedge_tpu.models import forward, init_params

    cfg = _cfg(tmp_path)
    _, serve_fn = run_serve_payload(cfg)
    tcfg, _ = train_model_config(cfg)
    params = init_params(jax.random.PRNGKey(0), tcfg)  # the served init

    prompt = [[5, 9, 2, 7], [1, 1, 4, 3]]
    out = serve_fn({"tokens": prompt, "n_new": 4})["tokens"]
    so_far = jnp.asarray(prompt, jnp.int32)
    for _ in range(4):
        nxt = jnp.argmax(forward(params, so_far, tcfg)[:, -1], axis=-1)
        so_far = jnp.concatenate(
            [so_far, nxt[:, None].astype(jnp.int32)], axis=1
        )
    np.testing.assert_array_equal(np.asarray(out), np.asarray(so_far))


def test_serve_request_validation(tmp_path):
    _, serve_fn = run_serve_payload(_cfg(tmp_path))
    for bad in (
        {},                                      # no tokens
        {"tokens": []},                          # empty
        {"tokens": [[1], []]},                   # empty row
        {"tokens": [[1, 2], [3]]},               # ragged
        {"tokens": [[1, 2]], "n_new": 0},        # n_new < 1
        {"tokens": [[1, 2]], "n_new": 10_000},   # n_new > max_seq
        {"tokens": [[1] * 15], "n_new": 4},      # prompt + n_new > max_seq
        {"tokens": [["a", "b"]]},                # non-integers
        {"tokens": [[1.9, 2.2]]},                # floats must NOT truncate
        {"tokens": [[True, False]]},             # bools are not token ids
    ):
        with pytest.raises(ValueError):
            serve_fn(bad)


def test_serve_small_train_seq_still_boots(tmp_path):
    # A legal train_seq smaller than the default probe shapes must not
    # fail the payload; the self-check sizes itself from the model.
    check, serve_fn = run_serve_payload(_cfg(tmp_path, train_seq=4))
    assert check.ok, check.error
    out = serve_fn({"tokens": [[1, 2]], "n_new": 2})
    assert len(out["tokens"][0]) == 4


def test_train_then_serve_restores_trained_params(tmp_path):
    """The whole story: train a few steps, then serve from the SAME state
    volume — the endpoint must decode with the TRAINED weights, not the
    init (proven by matching teacher forcing on the restored tree)."""
    import jax.numpy as jnp
    import numpy as np

    from kvedge_tpu.data import write_corpus
    from kvedge_tpu.models import forward

    corpus = tmp_path / "corpus.kvfeed"
    rng = np.random.default_rng(11)
    write_corpus(corpus, rng.integers(0, 512, size=3000, dtype=np.int32))

    train_cfg = _cfg(
        tmp_path, payload="train", train_corpus=str(corpus),
        train_steps=4, train_batch=8, train_checkpoint_every=2,
    )
    result = run_train_payload(train_cfg)
    assert result.ok, result.error

    serve_cfg = _cfg(tmp_path)
    check, serve_fn = run_serve_payload(serve_cfg)
    assert check.ok, check.error
    out = serve_fn({"tokens": [[3, 1, 4]], "n_new": 2})
    assert out["restored_step"] == 4

    # Teacher-forced argmax with the restored (trained) params.
    from kvedge_tpu.runtime.checkpoint import StateCheckpointer

    tcfg, _ = train_model_config(serve_cfg)
    with StateCheckpointer(serve_cfg.state_dir) as ckpt:
        _, tree = ckpt.restore_latest()
    params = tree["params"]
    so_far = jnp.asarray([[3, 1, 4]], jnp.int32)
    for _ in range(2):
        nxt = jnp.argmax(forward(params, so_far, tcfg)[:, -1], axis=-1)
        so_far = jnp.concatenate(
            [so_far, nxt[:, None].astype(jnp.int32)], axis=1
        )
    np.testing.assert_array_equal(np.asarray(out["tokens"]),
                                  np.asarray(so_far))


def test_tp_mesh_checkpoint_serves_sharded(tmp_path):
    """VERDICT r2 #1 done-bar: a {data:2, model:4}-trained checkpoint
    serves through the mesh-aware path with tokens IDENTICAL to the
    unsharded single-device decode of the same params — and the served
    params really are sharded over the model axis (not replicated)."""
    import jax
    import numpy as np

    from kvedge_tpu.config.runtime_config import MeshSpec
    from kvedge_tpu.data import write_corpus
    from kvedge_tpu.models import generate
    from kvedge_tpu.runtime.checkpoint import StateCheckpointer
    from kvedge_tpu.runtime.workload import _restore_latest_params

    corpus = tmp_path / "corpus.kvfeed"
    rng = np.random.default_rng(23)
    write_corpus(corpus, rng.integers(0, 512, size=3000, dtype=np.int32))
    mesh_spec = MeshSpec(axes=(("data", 2), ("model", 4)))

    result = run_train_payload(_cfg(
        tmp_path, payload="train", train_corpus=str(corpus),
        train_steps=3, train_batch=8, train_checkpoint_every=3,
        mesh=mesh_spec,
    ))
    assert result.ok, result.error

    serve_cfg = _cfg(tmp_path, mesh=mesh_spec)
    tcfg, mesh = train_model_config(serve_cfg)
    check, serve_fn = run_serve_payload(serve_cfg)
    assert check.ok, check.error
    try:
        out = serve_fn({"tokens": [[3, 1, 4], [2, 7, 2]], "n_new": 4})
        assert out["restored_step"] == 3

        # The restore is genuinely placement-aware: qkv shards its output
        # features over the 4-way model axis.
        _, sharded = _restore_latest_params(serve_cfg, tcfg, mesh=mesh)
        spec = sharded["w_qkv"].sharding.spec
        assert "model" in jax.tree_util.tree_leaves(list(spec))

        # Unsharded single-device decode of the SAME checkpoint must
        # produce identical tokens.
        with StateCheckpointer(serve_cfg.state_dir) as ckpt:
            _, tree = ckpt.restore_latest()
        import jax.numpy as jnp

        want = generate(
            tree["params"],
            jnp.asarray([[3, 1, 4], [2, 7, 2]], jnp.int32), tcfg, n_new=4,
        )
        np.testing.assert_array_equal(
            np.asarray(out["tokens"]), np.asarray(want)
        )
    finally:
        serve_fn.close()


@pytest.mark.parametrize("axes,label", [
    ((("data", 2), ("seq", 4)), "seq-ring"),
    ((("data", 2), ("expert", 4)), "expert"),
    ((("data", 2), ("stage", 4)), "stage"),
    ((("data", 2), ("model", 2), ("seq", 2)), "tp-x-seq"),
])
def test_serve_payload_runs_on_all_mesh_families(tmp_path, axes, label):
    """Serving is mesh-aware for every family training supports: the
    deterministic init restores sharded on each mesh and decodes tokens
    identical to the unsharded single-device decode."""
    import jax
    import jax.numpy as jnp

    from kvedge_tpu.config.runtime_config import MeshSpec
    from kvedge_tpu.models import generate, init_params

    serve_cfg = _cfg(tmp_path, mesh=MeshSpec(axes=axes))
    check, serve_fn = run_serve_payload(serve_cfg)
    assert check.ok, f"{label}: {check.error}"
    try:
        out = serve_fn({"tokens": [[3, 1, 4]], "n_new": 3})
        tcfg, _ = train_model_config(serve_cfg)
        want = generate(
            init_params(jax.random.PRNGKey(0), tcfg),
            jnp.asarray([[3, 1, 4]], jnp.int32), tcfg, n_new=3,
        )
        np.testing.assert_array_equal(
            np.asarray(out["tokens"]), np.asarray(want),
            err_msg=label,
        )
    finally:
        serve_fn.close()


def test_multihost_serve_refuses_unshared_checkpoints(
        tmp_path, monkeypatch):
    """Multi-host serve is leader-serves (contiguous) or the cross-host
    paged scheduler (round 4 — the real 2-process proofs live in
    test_distributed.py); either way every process must restore the
    SAME params, so a missing shared checkpoint_dir refuses loudly."""
    import jax

    monkeypatch.setattr(jax, "process_count", lambda: 2)
    check, serve_fn = run_serve_payload(_cfg(tmp_path))
    assert serve_fn is None
    assert not check.ok
    assert "checkpoint_dir" in check.error and "shared" in check.error


# ---- HTTP surface --------------------------------------------------------


@pytest.fixture
def served(tmp_path):
    handle = start_runtime(_cfg(tmp_path, status_token="serve-tok"))
    assert handle.check.ok, handle.check.error
    yield f"http://127.0.0.1:{handle.status_port}"
    handle.shutdown()


def test_http_generate_round_trip(served):
    code, doc = _post(f"{served}/generate",
                      {"tokens": [[1, 2, 3]], "n_new": 2},
                      token="serve-tok")
    assert code == 200
    assert len(doc["tokens"][0]) == 5


def test_http_generate_requires_token(served):
    code, doc = _post(f"{served}/generate", {"tokens": [[1]]})
    assert code == 401
    code, _ = _post(f"{served}/generate", {"tokens": [[1]]}, token="wrong")
    assert code == 401


def test_http_generate_bad_requests(served):
    code, doc = _post(f"{served}/generate", {"tokens": []},
                      token="serve-tok")
    assert code == 400
    # Non-JSON body
    req = urllib.request.Request(
        f"{served}/generate", data=b"not json",
        headers={"Authorization": "Bearer serve-tok"}, method="POST",
    )
    try:
        urllib.request.urlopen(req, timeout=30)
        code = 200
    except urllib.error.HTTPError as e:
        code = e.code
    assert code == 400


def test_metrics_expose_serving_gauges_under_load(tmp_path):
    """VERDICT r2 #4 done-bar: /metrics carries kvedge_serve_* request
    counters, and the paged pool's occupancy gauges are visible WHILE a
    request decodes (in_flight >= 1, a slot consumed, pages reserved)."""
    import threading
    import time

    handle = start_runtime(_cfg(
        tmp_path, payload_serving="paged", status_token="serve-tok",
        serving_slots=2,
    ))
    base = f"http://127.0.0.1:{handle.status_port}"

    def scrape():
        with urllib.request.urlopen(f"{base}/metrics", timeout=30) as r:
            text = r.read().decode()
        out = {}
        for line in text.splitlines():
            if line.startswith("#"):
                continue
            name, _, value = line.partition(" ")
            out[name] = float(value)
        return out

    try:
        m = scrape()
        assert m["kvedge_serve_free_slots"] == 2.0  # slots knob is live
        # The boot self-check is not operator traffic.
        assert m["kvedge_serve_requests_total"] == 0.0

        done = threading.Event()
        result = {}

        def fire():
            result["resp"] = _post(
                f"{base}/generate", {"tokens": [[1, 2, 3]], "n_new": 12},
                token="serve-tok",
            )
            done.set()

        worker = threading.Thread(target=fire)
        worker.start()
        saw_in_flight = False
        deadline = time.monotonic() + 120
        while not done.is_set() and time.monotonic() < deadline:
            m = scrape()
            if m["kvedge_serve_in_flight"] >= 1.0:
                saw_in_flight = True
                assert m["kvedge_serve_free_slots"] <= 1.0
                assert m["kvedge_serve_reserved_pages"] >= 1.0
                break
            time.sleep(0.01)
        worker.join(timeout=120)
        assert saw_in_flight, "request never observed in flight"
        code, _doc = result["resp"]
        assert code == 200

        m = scrape()
        assert m["kvedge_serve_requests_total"] == 1.0
        assert m["kvedge_serve_completed_total"] == 1.0
        assert m["kvedge_serve_tokens_generated_total"] == 12.0
        assert m["kvedge_serve_in_flight"] == 0.0
        assert m["kvedge_serve_free_slots"] == 2.0
        assert m["kvedge_serve_last_latency_ms"] > 0.0
        assert m["kvedge_serve_rejected_total"] == 0.0

        # A 400-class rejection lands in its own bucket.
        code, _doc = _post(f"{base}/generate", {"tokens": []},
                           token="serve-tok")
        assert code == 400
        m = scrape()
        assert m["kvedge_serve_rejected_total"] == 1.0
        assert m["kvedge_serve_completed_total"] == 1.0
    finally:
        handle.shutdown()


def test_http_generate_503_without_serve_payload(tmp_path):
    handle = start_runtime(_cfg(tmp_path, payload="devicecheck"))
    try:
        code, doc = _post(
            f"http://127.0.0.1:{handle.status_port}/generate",
            {"tokens": [[1]]},
        )
        assert code == 503
        assert "serve" in doc["error"]
    finally:
        handle.shutdown()


def test_expert_mesh_train_serve_agree_without_warning(tmp_path):
    """The derived MoE config must be provably drop-free: train on an
    expert mesh, serve from the checkpoint, and the endpoint must match
    teacher forcing with NO divergence warning."""
    import warnings

    import jax.numpy as jnp
    import numpy as np

    from kvedge_tpu.config.runtime_config import MeshSpec
    from kvedge_tpu.data import write_corpus
    from kvedge_tpu.models import forward
    from kvedge_tpu.runtime.checkpoint import StateCheckpointer

    corpus = tmp_path / "corpus.kvfeed"
    rng = np.random.default_rng(13)
    write_corpus(corpus, rng.integers(0, 512, size=3000, dtype=np.int32))
    mesh_spec = MeshSpec(axes=(("data", 2), ("expert", 4)))

    result = run_train_payload(_cfg(
        tmp_path, payload="train", train_corpus=str(corpus),
        train_steps=2, train_batch=8, train_checkpoint_every=2,
        mesh=mesh_spec,
    ))
    assert result.ok, result.error

    serve_cfg = _cfg(tmp_path, mesh=mesh_spec)
    tcfg, _ = train_model_config(serve_cfg)
    assert tcfg.expert_capacity_factor * tcfg.expert_top_k >= tcfg.n_experts

    with warnings.catch_warnings():
        warnings.simplefilter("error", RuntimeWarning)
        check, serve_fn = run_serve_payload(serve_cfg)
        assert check.ok, check.error
        out = serve_fn({"tokens": [[3, 1, 4]], "n_new": 2})
    assert out["restored_step"] == 2

    with StateCheckpointer(serve_cfg.state_dir) as ckpt:
        _, tree = ckpt.restore_latest()
    so_far = jnp.asarray([[3, 1, 4]], jnp.int32)
    for _ in range(2):
        nxt = jnp.argmax(
            forward(tree["params"], so_far, tcfg)[:, -1], axis=-1
        )
        so_far = jnp.concatenate(
            [so_far, nxt[:, None].astype(jnp.int32)], axis=1
        )
    np.testing.assert_array_equal(np.asarray(out["tokens"]),
                                  np.asarray(so_far))


# ---- eval payload --------------------------------------------------------


def _eval_cfg(tmp_path, corpus, **overrides):
    base = dict(payload="eval", train_corpus=str(corpus),
                train_steps=3, train_batch=8)
    base.update(overrides)
    return _cfg(tmp_path, **base)


def _make_corpus(tmp_path, seed=17):
    from kvedge_tpu.data import write_corpus

    corpus = tmp_path / "corpus.kvfeed"
    rng = np.random.default_rng(seed)
    write_corpus(corpus, rng.integers(0, 512, size=3000, dtype=np.int32))
    return corpus


def test_eval_payload_fresh_volume_near_ln_vocab(tmp_path):
    import math

    from kvedge_tpu.runtime.workload import run_eval_payload

    corpus = _make_corpus(tmp_path)
    result = run_eval_payload(_eval_cfg(tmp_path, corpus))
    assert result.ok, result.error
    # Untrained model on random tokens: loss ~ ln(512).
    assert abs(result.probe_checksum - math.log(512)) < 0.5 * math.log(512)


def test_eval_after_training_improves(tmp_path):
    """Train on the corpus, then eval the checkpoint on the SAME corpus:
    the restored loss must beat the fresh-init loss — proving eval reads
    the trained weights, not the init."""
    from kvedge_tpu.runtime.workload import run_eval_payload

    corpus = _make_corpus(tmp_path)
    fresh = run_eval_payload(_eval_cfg(tmp_path, corpus))
    assert fresh.ok, fresh.error

    train = run_train_payload(_cfg(
        tmp_path, payload="train", train_corpus=str(corpus),
        train_steps=6, train_batch=8, train_checkpoint_every=3,
    ))
    assert train.ok, train.error

    trained = run_eval_payload(_eval_cfg(tmp_path, corpus))
    assert trained.ok, trained.error
    assert trained.probe_checksum < fresh.probe_checksum


def test_eval_warns_on_training_corpus_and_not_on_holdout(tmp_path, capsys):
    """VERDICT r2 #8 done-bar: eval on a fresh held-out split reports
    WITHOUT the training-loss warning; the fallback warns loudly."""
    from kvedge_tpu.runtime.workload import run_eval_payload

    corpus = _make_corpus(tmp_path)
    heldout_dir = tmp_path / "h"
    heldout_dir.mkdir()
    heldout = _make_corpus(heldout_dir, seed=99)

    result = run_eval_payload(_eval_cfg(tmp_path, corpus))
    assert result.ok, result.error
    out = capsys.readouterr().out
    assert "WARNING" in out and "TRAINING corpus" in out
    assert "held_out=False" in out

    result = run_eval_payload(_eval_cfg(
        tmp_path, corpus, eval_corpus=str(heldout)
    ))
    assert result.ok, result.error
    out = capsys.readouterr().out
    assert "WARNING" not in out
    assert "held_out=True" in out


def test_eval_accepts_eval_corpus_only(tmp_path):
    from kvedge_tpu.config.runtime_config import RuntimeConfig

    cfg = RuntimeConfig.parse(
        "[payload]\nkind = \"eval\"\neval_corpus = \"/x.kvfeed\"\n"
    )
    assert cfg.eval_corpus == "/x.kvfeed"
    assert RuntimeConfig.parse(cfg.to_toml()) == cfg


def test_eval_requires_corpus():
    from kvedge_tpu.config.runtime_config import (
        RuntimeConfig,
        RuntimeConfigError,
    )

    with pytest.raises(RuntimeConfigError, match="corpus"):
        RuntimeConfig.parse('[payload]\nkind = "eval"\n')


def test_eval_multihost_requires_shared_checkpoint_dir(tmp_path, monkeypatch):
    import jax

    from kvedge_tpu.runtime.workload import run_eval_payload

    corpus = _make_corpus(tmp_path)
    monkeypatch.setattr(jax, "process_count", lambda: 2)
    result = run_eval_payload(_eval_cfg(tmp_path, corpus))
    assert not result.ok
    assert "checkpoint_dir" in result.error and "shared storage" in result.error


def test_eval_reports_clear_error_for_indivisible_batch(tmp_path):
    from kvedge_tpu.config.runtime_config import MeshSpec
    from kvedge_tpu.runtime.workload import run_eval_payload

    corpus = _make_corpus(tmp_path)
    result = run_eval_payload(_eval_cfg(
        tmp_path, corpus, train_batch=7,
        mesh=MeshSpec(axes=(("data", 8),)),
    ))
    assert not result.ok
    assert "must divide" in result.error


def test_paged_serving_matches_contiguous(tmp_path):
    """[payload] serving = 'paged' routes /generate through the
    continuous-batching server; outputs must equal the contiguous path."""
    contiguous_check, contiguous_fn = run_serve_payload(_cfg(tmp_path))
    assert contiguous_check.ok, contiguous_check.error

    paged_check, paged_fn = run_serve_payload(
        _cfg(tmp_path, payload_serving="paged")
    )
    assert paged_check.ok, paged_check.error

    try:
        req = {"tokens": [[5, 9, 2, 7], [1, 1, 4, 3]], "n_new": 5}
        got = paged_fn(req)
        want = contiguous_fn(req)
        assert got["tokens"] == want["tokens"]
        assert got["restored_step"] == want["restored_step"]
    finally:
        paged_fn.close()
        contiguous_fn.close()


def test_http_generate_streams_ndjson(tmp_path):
    """End-to-end streaming: one JSON document per token over the wire,
    final document carries the full result; tokens equal the
    non-streamed greedy decode."""
    handle = start_runtime(_cfg(
        tmp_path, payload_serving="paged", status_token="serve-tok"
    ))
    try:
        base = f"http://127.0.0.1:{handle.status_port}"
        _, want = _post(f"{base}/generate",
                        {"tokens": [[5, 9, 2]], "n_new": 4},
                        token="serve-tok")
        req = urllib.request.Request(
            f"{base}/generate",
            data=json.dumps({"tokens": [[5, 9, 2]], "n_new": 4,
                             "stream": True}).encode(),
            headers={"Authorization": "Bearer serve-tok"}, method="POST",
        )
        with urllib.request.urlopen(req, timeout=120) as resp:
            assert resp.status == 200
            assert resp.headers["Content-Type"] == "application/x-ndjson"
            lines = [json.loads(ln) for ln in resp.read().splitlines()]
        token_lines = [ln for ln in lines if "token" in ln]
        (final,) = [ln for ln in lines if ln.get("done")]
        assert len(token_lines) == 4
        assert final["tokens"] == want["tokens"]
        assert [ln["token"] for ln in token_lines] == want["tokens"][0][3:]
        # The handler stamped the flush of the streamed request's first
        # line (the buffered request has no such line): one first_write,
        # from that token's put on the stream, never negative.
        wrote = handle.serve_fn.stats()["request_ms"]["first_write"]
        assert wrote[0] == 1 and wrote[1] >= 0.0
    finally:
        handle.shutdown()


def test_http_generate_stream_rejected_on_contiguous_backend(tmp_path):
    check, serve_fn = run_serve_payload(_cfg(tmp_path))
    assert check.ok
    try:
        with pytest.raises(ValueError, match="paged"):
            serve_fn({"tokens": [[1, 2]], "n_new": 4, "stream": True})
        with pytest.raises(ValueError, match="boolean"):
            serve_fn({"tokens": [[1, 2]], "n_new": 4, "stream": 1})
    finally:
        serve_fn.close()


def test_wide_row_burst_bounded_threads_and_row_cap(tmp_path):
    """VERDICT r3 #6: rows ride a shared pool sized from serving_slots
    — a wide request must not spawn a thread per row — and rows beyond
    the 4x-slots ceiling are rejected up front (400), not queued."""
    import threading

    check, serve_fn = run_serve_payload(_cfg(
        tmp_path, payload_serving="paged", serving_slots=2,
    ))
    assert check.ok, check.error
    try:
        with pytest.raises(ValueError, match="ceiling"):
            serve_fn({"tokens": [[1, 2]] * 9, "n_new": 2})  # 9 > 4*2

        before = threading.active_count()
        out = serve_fn({"tokens": [[i + 1, 2] for i in range(8)],
                        "n_new": 3})
        # The widest legal burst adds at most the pool's 2*slots workers
        # (plus nothing per-row); a thread-per-row regression would add
        # 8 here and fail.
        assert threading.active_count() - before <= 2 * 2
        assert len(out["tokens"]) == 8
        assert all(len(row) == 5 for row in out["tokens"])
        # (Row-vs-contiguous token equality under concurrency is pinned
        # by test_paged_serving_matches_contiguous and the streaming
        # merge test; this test is about the thread budget.)
    finally:
        serve_fn.close()


def test_stream_consumer_disconnect_frees_serving_capacity(tmp_path):
    """VERDICT r3 #5a at the payload layer: closing the response stream
    (what status.py does on BrokenPipeError) cancels every row, so the
    slots and pages free long before the reserved budgets run out and a
    follow-up request admits immediately."""
    import time

    check, serve_fn = run_serve_payload(_cfg(
        tmp_path, payload_serving="paged", serving_slots=2,
        train_seq=128,
    ))
    assert check.ok, check.error
    try:
        out = serve_fn({"tokens": [[5, 9, 2], [1, 1, 4]], "n_new": 100,
                        "stream": True})
        stream = out["_stream"]
        for _ in range(3):
            next(stream)  # both rows are decoding
        stream.close()  # the HTTP layer's disconnect hook
        deadline = time.monotonic() + 30
        stats = serve_fn.stats()
        while stats["in_flight"] and time.monotonic() < deadline:
            time.sleep(0.01)
            stats = serve_fn.stats()
        assert stats["in_flight"] == 0
        assert stats["reserved_pages"] == 0
        # Capacity is usable right away — and the abandoned request
        # recorded no completion (matching what the client observed).
        got = serve_fn({"tokens": [[4, 4]], "n_new": 2})
        assert len(got["tokens"][0]) == 4
        assert stats["completed_total"] == 0
    finally:
        serve_fn.close()


def test_stream_multiple_rows_merge_with_attribution(tmp_path):
    """Multi-row streaming: rows decode concurrently, merged into one
    ndjson sequence with per-row attribution; regrouping by row must
    reproduce the non-streamed result exactly, and each row's tokens
    arrive in generation order."""
    check, serve_fn = run_serve_payload(
        _cfg(tmp_path, payload_serving="paged")
    )
    assert check.ok
    try:
        req = {"tokens": [[5, 9, 2], [1, 1, 4]], "n_new": 5}
        want = serve_fn(req)
        out = serve_fn({**req, "stream": True})
        docs = list(out["_stream"])
        token_docs = [d for d in docs if "token" in d]
        (final,) = [d for d in docs if d.get("done")]
        assert len(token_docs) == 2 * 5
        by_row = {0: [], 1: []}
        for d in token_docs:
            by_row[d["row"]].append(d["token"])
        for i in (0, 1):
            assert req["tokens"][i] + by_row[i] == want["tokens"][i]
        assert final["tokens"] == want["tokens"]
    finally:
        serve_fn.close()


def test_prefix_cache_persists_across_serve_restarts(tmp_path):
    """The pod-reschedule story for warm prefixes: a serve runtime's
    registry dumps to the state volume at shutdown and the next serve
    runtime re-pins it at boot — the first request after the 'restart'
    is a prefix hit with tokens identical to the cold decode."""
    cfg = _cfg(tmp_path, payload_serving="paged", serving_page_size=4)
    prompt = [7, 3, 9, 1, 5, 5, 2, 8]  # two full pages at page_size 4

    check, serve_fn = run_serve_payload(cfg)
    assert check.ok, check.error
    try:
        cold = serve_fn({"tokens": [prompt], "n_new": 4})["tokens"]
    finally:
        serve_fn.close()  # dumps <state_dir>/prefix-cache.npz
    import os

    assert os.path.exists(os.path.join(cfg.state_dir,
                                       "prefix-cache.npz"))

    check, revived_fn = run_serve_payload(cfg)
    assert check.ok, check.error
    try:
        # 3 = the prompt's 1- and 2-page prefixes + the boot probe's
        # one full page (the probe registered live in run 1, so its
        # entry persisted too; in run 2 it re-registers onto the loaded
        # node — a no-op).
        stats = revived_fn.stats()
        assert stats["prefix_entries"] == 3, stats
        # The boot probe of run 2 already hit its own loaded page (the
        # radix cache shares down to one token, copy-on-write), so the
        # request's hit is counted from here.
        hits = stats["prefix_hits"]
        warm = revived_fn({"tokens": [prompt], "n_new": 4})["tokens"]
        assert warm == cold
        assert revived_fn.stats()["prefix_hits"] == hits + 1
    finally:
        revived_fn.close()

    # Persistence off: the file is not read — only the live probe
    # entry exists.
    check, off_fn = run_serve_payload(
        _cfg(tmp_path, payload_serving="paged", serving_page_size=4,
             serving_prefix_persist=False)
    )
    assert check.ok, check.error
    try:
        assert off_fn.stats()["prefix_entries"] == 1
    finally:
        off_fn.close()
