"""The [model] section: operator-sized payload models through the
product path.

Round 3's verdict: the entire train -> checkpoint -> serve loop could
only ever run the hard-coded probe shape, while the flagship model
lived exclusively in a benchmark script. These tests pin
the fix: `derive_model_config` resolves [model] (preset + overrides)
against the mesh — preset-derived values adapt, explicitly-set values
are authoritative and refuse impossible meshes loudly — and the
flagship preset trains, checkpoints, and serves through the same payload
path as everything else.
"""

import dataclasses

import numpy as np
import pytest

from kvedge_tpu.config.runtime_config import (
    MeshSpec,
    ModelSpec,
    RuntimeConfig,
)
from kvedge_tpu.models import PRESETS
from kvedge_tpu.runtime.workload import (
    MeshConfigError,
    derive_model_config,
    run_serve_payload,
    run_train_payload,
)


def _cfg(axes=(("data", 0),), model=None, **overrides):
    base = dict(
        expected_platform="cpu",
        mesh=MeshSpec(axes=axes),
        model=ModelSpec(**(model or {})),
    )
    base.update(overrides)
    return dataclasses.replace(RuntimeConfig(), **base)


def test_default_is_probe_preset():
    tcfg, _ = derive_model_config(_cfg(), seq=64)
    probe = PRESETS["probe"]
    assert (tcfg.vocab, tcfg.d_model, tcfg.n_layers, tcfg.d_ff) == (
        probe["vocab"], probe["d_model"], probe["n_layers"], probe["d_ff"]
    )
    assert tcfg.n_heads == probe["n_heads"]
    assert tcfg.max_seq == 64


def test_flagship_preset_resolves():
    tcfg, _ = derive_model_config(
        _cfg(model={"preset": "flagship"}), seq=128
    )
    flag = PRESETS["flagship"]
    assert (tcfg.vocab, tcfg.d_model, tcfg.n_heads, tcfg.n_layers,
            tcfg.d_ff) == (flag["vocab"], flag["d_model"], flag["n_heads"],
                           flag["n_layers"], flag["d_ff"])
    # 41.6M parameters: the bench model, through the product path.
    assert tcfg.param_count == 41_558_528


def test_flagship_is_the_bench_model():
    """One definition: the [model] preset must be exactly the shape
    __graft_entry__ reports numbers for."""
    from __graft_entry__ import FLAGSHIP

    tcfg, _ = derive_model_config(
        _cfg(model={"preset": "flagship"}), seq=FLAGSHIP.max_seq
    )
    for field in ("vocab", "d_model", "n_heads", "n_kv_heads", "n_layers",
                  "d_ff", "max_seq"):
        assert getattr(tcfg, field) == getattr(FLAGSHIP, field), field


def test_explicit_fields_override_preset():
    tcfg, _ = derive_model_config(
        _cfg(model={"preset": "flagship", "n_kv_heads": 2,
                    "n_layers": 4, "vocab": 1024}),
        seq=64,
    )
    assert tcfg.n_kv_heads == 2
    assert tcfg.n_layers == 4
    assert tcfg.vocab == 1024
    assert tcfg.d_model == PRESETS["flagship"]["d_model"]  # kept


def test_preset_heads_adapt_to_model_axis():
    tcfg, _ = derive_model_config(
        _cfg(axes=(("data", 1), ("model", 8))), seq=64
    )
    assert tcfg.n_heads == 8  # probe's 4 lifted to the axis size


def test_preset_layers_round_up_to_stage_multiple():
    tcfg, _ = derive_model_config(
        _cfg(axes=(("data", 2), ("stage", 4)),
             model={"preset": "flagship", "n_layers": 0}),
        seq=64,
    )
    assert tcfg.n_layers == 8  # 8 % 4 == 0: unchanged
    tcfg, _ = derive_model_config(
        _cfg(axes=(("data", 2), ("stage", 4))), seq=64
    )
    assert tcfg.n_layers == 4  # probe's 2 rounded up to one multiple


def test_explicit_layers_refuse_indivisible_stages():
    with pytest.raises(MeshConfigError, match="n_layers"):
        derive_model_config(
            _cfg(axes=(("data", 2), ("stage", 4)),
                 model={"n_layers": 6}),
            seq=64,
        )


def test_explicit_heads_refuse_ulysses_mismatch():
    with pytest.raises(MeshConfigError, match="n_heads"):
        derive_model_config(
            _cfg(axes=(("data", 2), ("seq", 4)), model={"n_heads": 6},
                 payload_attention="ulysses"),
            seq=64,
        )
    # Preset-derived heads still round up instead.
    tcfg, _ = derive_model_config(
        _cfg(axes=(("data", 2), ("seq", 4)),
             payload_attention="ulysses"),
        seq=64,
    )
    assert tcfg.n_heads % 4 == 0


def test_explicit_experts_refuse_indivisible_axis():
    with pytest.raises(MeshConfigError, match="experts"):
        derive_model_config(
            _cfg(axes=(("data", 4), ("expert", 2)),
                 model={"experts": 3}),
            seq=64,
        )
    tcfg, _ = derive_model_config(
        _cfg(axes=(("data", 4), ("expert", 2)), model={"experts": 4}),
        seq=64,
    )
    assert tcfg.n_experts == 4  # 2 experts per axis shard


def test_experts_without_axis_replicate():
    """MoE on a dense mesh is legal — expert weights replicate (the
    sharding rules prune axes the mesh lacks, parallel/sharding.py)."""
    tcfg, _ = derive_model_config(_cfg(model={"experts": 2}), seq=64)
    assert tcfg.n_experts == 2
    # Drop-free default capacity: factor * top_k >= E.
    assert tcfg.expert_capacity_factor * tcfg.expert_top_k >= 2


def test_capacity_factor_override_and_top2_default():
    tcfg, _ = derive_model_config(
        _cfg(model={"experts": 4, "expert_top_k": 2}), seq=64
    )
    assert tcfg.expert_top_k == 2
    assert tcfg.expert_capacity_factor * 2 >= 4  # still drop-free
    tcfg, _ = derive_model_config(
        _cfg(model={"experts": 4, "expert_capacity_factor": 1.25}),
        seq=64,
    )
    assert tcfg.expert_capacity_factor == 1.25  # operator's choice kept


def test_moe_knobs_on_dense_model_refused():
    """Silently-dead config is the failure mode the whole section is
    designed against: MoE knobs without an MoE model must refuse."""
    for knobs in ({"expert_top_k": 2}, {"expert_capacity_factor": 1.5}):
        with pytest.raises(MeshConfigError, match="dense"):
            derive_model_config(_cfg(model=knobs), seq=64)


def test_invalid_architecture_is_a_config_refusal():
    # d_model % n_heads: a clear MeshConfigError, not a traceback.
    with pytest.raises(MeshConfigError, match="invalid"):
        derive_model_config(
            _cfg(model={"d_model": 100, "n_heads": 3}), seq=64
        )
    with pytest.raises(MeshConfigError, match="invalid"):
        derive_model_config(
            _cfg(model={"n_heads": 8, "n_kv_heads": 3}), seq=64
        )


def test_flagship_trains_checkpoints_and_serves(tmp_path):
    """The r3 gap, closed end to end: the FLAGSHIP shape trains steps
    through the real train payload, checkpoints, and a serve pod
    restores it and answers /generate — same volume, same [model]
    section, greedy tokens from the TRAINED weights."""
    from kvedge_tpu.data import write_corpus

    corpus = tmp_path / "corpus.kvfeed"
    rng = np.random.default_rng(7)
    write_corpus(corpus, rng.integers(0, 32000, size=2000, dtype=np.int32))

    common = dict(
        state_dir=str(tmp_path / "state"),
        status_port=0,
        model={"preset": "flagship"},
        train_seq=16,
        train_batch=8,
    )
    train_cfg = _cfg(
        payload="train", train_corpus=str(corpus), train_steps=2,
        train_checkpoint_every=2, **common,
    )
    result = run_train_payload(train_cfg)
    assert result.ok, result.error

    serve_cfg = _cfg(payload="serve", **common)
    check, serve_fn = run_serve_payload(serve_cfg)
    assert check.ok, check.error
    out = serve_fn({"tokens": [[31999, 17, 4]], "n_new": 3})
    assert out["restored_step"] == 2
    assert len(out["tokens"][0]) == 6
    assert all(0 <= t < 32000 for t in out["tokens"][0])

    # The serve-side model is the flagship architecture, not the probe.
    from kvedge_tpu.runtime.workload import train_model_config

    tcfg, _ = train_model_config(serve_cfg)
    assert tcfg.d_model == 512 and tcfg.vocab == 32000


def test_model_mismatch_between_train_and_serve_fails_loudly(tmp_path):
    """A serve pod whose [model] disagrees with the checkpoint it
    restores must error (orbax tree/shape mismatch surfaces as a failed
    payload), not silently decode a different architecture."""
    from kvedge_tpu.data import write_corpus

    corpus = tmp_path / "corpus.kvfeed"
    rng = np.random.default_rng(3)
    write_corpus(corpus, rng.integers(0, 512, size=2000, dtype=np.int32))

    common = dict(state_dir=str(tmp_path / "state"), status_port=0,
                  train_seq=16, train_batch=8)
    result = run_train_payload(_cfg(
        payload="train", train_corpus=str(corpus), train_steps=2,
        train_checkpoint_every=2, **common,
    ))
    assert result.ok, result.error
    check, _ = run_serve_payload(_cfg(
        payload="serve", model={"preset": "flagship"}, **common,
    ))
    assert not check.ok
    assert "serve payload failed" in check.error
