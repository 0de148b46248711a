"""Runtime-config TOML: parse, validate, round-trip, apply."""

import pytest

from kvedge_tpu.config.runtime_config import (
    MeshSpec,
    RuntimeConfig,
    RuntimeConfigError,
)

SAMPLE = """
[runtime]
name = "edge-tpu-a"
state_dir = "/var/lib/kvedge/state"
heartbeat_interval_s = 5.0

[tpu]
platform = "tpu"
expected_chips = 8

[mesh]
axes = { data = 2, model = 4 }

[status]
port = 9000

[payload]
kind = "transformer-probe"
"""


def test_parse_sample():
    cfg = RuntimeConfig.parse(SAMPLE)
    assert cfg.name == "edge-tpu-a"
    assert cfg.expected_chips == 8
    assert cfg.mesh.axes == (("data", 2), ("model", 4))
    assert cfg.status_port == 9000
    assert cfg.payload == "transformer-probe"


def test_defaults_from_empty_doc():
    cfg = RuntimeConfig.parse("")
    assert cfg.payload == "devicecheck"
    assert cfg.mesh.axis_names() == ("data", "model")
    assert cfg.expected_chips == 0


def test_invalid_toml_and_values():
    with pytest.raises(RuntimeConfigError):
        RuntimeConfig.parse("not [valid toml")
    with pytest.raises(RuntimeConfigError):
        RuntimeConfig.parse("[payload]\nkind = 'mine-bitcoin'\n")
    with pytest.raises(RuntimeConfigError):
        RuntimeConfig.parse("[status]\nport = 99999\n")
    with pytest.raises(RuntimeConfigError):
        RuntimeConfig.parse("[runtime]\nheartbeat_interval_s = 0\n")
    with pytest.raises(RuntimeConfigError):
        RuntimeConfig.parse("[payload]\nattention = 'quadratic'\n")


def test_payload_attention_round_trips():
    cfg = RuntimeConfig.parse("[payload]\nattention = 'ulysses'\n")
    assert cfg.payload_attention == "ulysses"
    assert RuntimeConfig.parse(cfg.to_toml()) == cfg
    assert RuntimeConfig.parse("").payload_attention == ""  # auto


def test_serving_pool_knobs_round_trip_and_validate():
    cfg = RuntimeConfig.parse(
        "[payload]\nserving = 'paged'\nserving_slots = 8\n"
        "serving_page_size = 32\nserving_pages = 96\n"
    )
    assert (cfg.serving_slots, cfg.serving_page_size, cfg.serving_pages) \
        == (8, 32, 96)
    assert RuntimeConfig.parse(cfg.to_toml()) == cfg
    # Defaults: 4 slots, 16-token pages, auto-sized pool.
    default = RuntimeConfig.parse("")
    assert (default.serving_slots, default.serving_page_size,
            default.serving_pages) == (4, 16, 0)
    for bad in ("serving_slots = 0", "serving_page_size = 0",
                "serving_pages = -1"):
        with pytest.raises(RuntimeConfigError):
            RuntimeConfig.parse(f"[payload]\n{bad}\n")


def test_model_section_parses_and_round_trips():
    cfg = RuntimeConfig.parse(
        "[model]\npreset = \"flagship\"\nn_kv_heads = 2\nexperts = 4\n"
        "expert_top_k = 2\nexpert_capacity_factor = 1.5\n"
    )
    assert cfg.model.preset == "flagship"
    assert cfg.model.n_kv_heads == 2
    assert cfg.model.experts == 4
    assert cfg.model.expert_top_k == 2
    assert cfg.model.expert_capacity_factor == 1.5
    assert cfg.model.vocab == 0  # unset = from the preset
    again = RuntimeConfig.parse(cfg.to_toml())
    assert again.model == cfg.model


def test_model_section_defaults_empty():
    cfg = RuntimeConfig.parse("")
    assert cfg.model.preset == ""
    assert cfg.model.d_model == 0


def test_model_section_validation():
    for bad in (
        "[model]\npreset = 'gpt5'\n",
        "[model]\nd_model = -1\n",
        "[model]\nn_heads = \"many\"\n",
        "[model]\nexpert_top_k = 3\n",
        "[model]\nexpert_capacity_factor = -0.5\n",
    ):
        with pytest.raises(RuntimeConfigError):
            RuntimeConfig.parse(bad)


def test_mesh_resolution():
    spec = MeshSpec(axes=(("data", 0), ("model", 4)))
    assert spec.resolved_shape(8) == (2, 4)
    with pytest.raises(RuntimeConfigError):
        spec.resolved_shape(6)  # 6 % 4 != 0
    fixed = MeshSpec(axes=(("data", 2), ("model", 4)))
    assert fixed.resolved_shape(8) == (2, 4)
    with pytest.raises(RuntimeConfigError):
        fixed.resolved_shape(16)
    with pytest.raises(RuntimeConfigError):
        MeshSpec(axes=(("a", 0), ("b", 0))).resolved_shape(8)


def test_round_trip_and_apply(tmp_path):
    cfg = RuntimeConfig.parse(SAMPLE)
    # to_toml -> parse is the identity on the validated form.
    assert RuntimeConfig.parse(cfg.to_toml()) == cfg
    target = tmp_path / "etc" / "config.toml"
    state = tmp_path / "state"
    cfg2 = RuntimeConfig.parse(
        cfg.to_toml().replace("/var/lib/kvedge/state", str(state))
    )
    written = cfg2.apply(config_path=str(target))
    assert written == str(target)
    assert state.is_dir()
    assert RuntimeConfig.parse(target.read_text()) == cfg2


def test_to_toml_escapes_strings():
    # Quotes and backslashes in values must survive apply -> re-parse
    # (the applied config is what the next boot reads).
    cfg = RuntimeConfig(name='a"b\\c', state_dir="C:\\kvedge state")
    assert RuntimeConfig.parse(cfg.to_toml()) == cfg


def test_validate_catches_programmatic_bad_mesh():
    with pytest.raises(RuntimeConfigError):
        RuntimeConfig(mesh=MeshSpec(axes=())).validate()
    with pytest.raises(RuntimeConfigError):
        RuntimeConfig(mesh=MeshSpec(axes=(("a", -1),))).validate()
    with pytest.raises(RuntimeConfigError):
        RuntimeConfig(mesh=MeshSpec(axes=(("a", 1), ("a", 2)))).validate()


def test_two_zero_axes_rejected_at_parse():
    with pytest.raises(RuntimeConfigError):
        RuntimeConfig.parse("[mesh]\naxes = { data = 0, model = 0 }\n")


def test_wrongly_typed_values_raise_config_error():
    with pytest.raises(RuntimeConfigError):
        RuntimeConfig.parse('[status]\nport = "abc"\n')
    with pytest.raises(RuntimeConfigError):
        RuntimeConfig.parse('[runtime]\nheartbeat_interval_s = "fast"\n')


def test_serving_window_round_trips():
    cfg = RuntimeConfig.parse(
        "[payload]\nserving = 'paged'\nserving_window = 128\n"
    )
    assert cfg.serving_window == 128
    assert RuntimeConfig.parse(cfg.to_toml()) == cfg
    assert RuntimeConfig.parse("").serving_window == 64
    for bad in ("serving_window = 0", "serving_window = 2048"):
        with pytest.raises(RuntimeConfigError):
            RuntimeConfig.parse(f"[payload]\n{bad}\n")


# The paged server does not speculate since PR 48: the three keys are
# refused by name at any value but the default an older to_toml wrote.
RETIRED = [("serving_speculative", "3", "0"),
           ("serving_spec_window", "4", "0"),
           ("serving_spec_sampled_window", "false", "true")]


@pytest.mark.parametrize("key, value, default", RETIRED)
def test_a_retired_speculation_key_is_refused_by_name(key, value, default):
    with pytest.raises(RuntimeConfigError, match=key) as refused:
        RuntimeConfig.parse(f"[payload]\nserving = 'paged'\n{key} = {value}\n")
    assert "does not speculate since PR 48" in str(refused.value)
    if key == "serving_speculative":
        with pytest.raises(RuntimeConfigError, match=key):
            RuntimeConfig.parse(f"[payload]\n{key} = 'auto'\n")


@pytest.mark.parametrize("key, value, default", RETIRED)
def test_a_retired_speculation_key_parses_at_its_old_default(key, value,
                                                             default):
    """A document an older ``to_toml`` wrote still parses, to what a
    document without the key parses to; ``to_toml`` no longer writes
    it, and the round trip holds."""
    cfg = RuntimeConfig.parse(f"[payload]\n{key} = {default}\n")
    assert cfg == RuntimeConfig.parse("")
    assert not hasattr(cfg, key)
    assert key not in cfg.to_toml()
    assert RuntimeConfig.parse(cfg.to_toml()) == cfg


def test_serving_trace_knob_round_trips_and_validates():
    cfg = RuntimeConfig.parse(
        "[payload]\nserving = 'paged'\nserving_trace = 'on'\n"
    )
    assert cfg.serving_trace == "on"
    assert RuntimeConfig.parse(cfg.to_toml()) == cfg
    assert RuntimeConfig.parse("").serving_trace == "off"
    sampled = RuntimeConfig.parse("[payload]\nserving_trace = 0.25\n")
    assert sampled.serving_trace == 0.25
    assert RuntimeConfig.parse(sampled.to_toml()) == sampled
    # An integer 1 is a valid rate (TOML writers vary on 1 vs 1.0).
    assert RuntimeConfig.parse(
        "[payload]\nserving_trace = 1\n"
    ).serving_trace == 1.0
    for bad in ("serving_trace = 'sometimes'", "serving_trace = 0.0",
                "serving_trace = 1.5", "serving_trace = -0.5",
                "serving_trace = true"):
        with pytest.raises(RuntimeConfigError):
            RuntimeConfig.parse(f"[payload]\n{bad}\n")


def test_paged_attention_knob_round_trips_and_threads():
    cfg = RuntimeConfig.parse(
        "[payload]\nserving = 'paged'\npaged_attention = 'gather'\n"
    )
    assert cfg.payload_paged_attention == "gather"
    assert RuntimeConfig.parse(cfg.to_toml()) == cfg
    with pytest.raises(RuntimeConfigError):
        RuntimeConfig.parse("[payload]\npaged_attention = 'fast'\n")
    # Threads into the derived model config (the deployment-level
    # escape hatch for the kernel's auto policy).
    from kvedge_tpu.runtime.workload import derive_model_config

    tcfg, _ = derive_model_config(cfg, seq=32)
    assert tcfg.paged_attention == "gather"
    tcfg, _ = derive_model_config(RuntimeConfig.parse(""), seq=32)
    assert tcfg.paged_attention == "auto"


def test_serving_kv_dtype_round_trips_and_validates():
    cfg = RuntimeConfig.parse(
        "[payload]\nserving = 'paged'\nserving_kv_dtype = 'int8'\n"
    )
    assert cfg.serving_kv_dtype == "int8"
    assert RuntimeConfig.parse(cfg.to_toml()) == cfg
    assert RuntimeConfig.parse("").serving_kv_dtype == ""
    with pytest.raises(RuntimeConfigError):
        RuntimeConfig.parse("[payload]\nserving_kv_dtype = 'fp8'\n")


def test_serving_checkpoint_knobs_round_trip_and_validate():
    """Rung 22 knobs: checkpoint cadence (0 = off, today's
    fail-and-retry semantics) and the page-conservation audit."""
    cfg = RuntimeConfig.parse(
        "[payload]\nserving = 'paged'\nserving_checkpoint_every = 16\n"
        "serving_debug_pages = true\n"
    )
    assert cfg.serving_checkpoint_every == 16
    assert cfg.serving_debug_pages is True
    assert RuntimeConfig.parse(cfg.to_toml()) == cfg
    default = RuntimeConfig.parse("")
    assert default.serving_checkpoint_every == 0
    assert default.serving_debug_pages is False
    for bad in ("serving_checkpoint_every = -1",
                "serving_debug_pages = 'yes'"):
        with pytest.raises(RuntimeConfigError):
            RuntimeConfig.parse(f"[payload]\n{bad}\n")
