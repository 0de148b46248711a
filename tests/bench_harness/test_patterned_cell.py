"""The patterned block's configuration, cell and readers in the harness
(``granite-4.0-h-small``, ``references/granite_moe_hybrid.py``): the
committed file against the source's keys, the block's counts against
the same arithmetic by hand, and one whole CPU run of the block at a
probe size through ``run.measure``, its three readers in the line.
A file of its own: a ``model_config`` PR adds files beside the
harness's and edits none of them."""

import json
import os

import pytest

from benchmark import cellspec, metrics

from test_benchmark_harness import (
    BENCH, REPO, _in_the_layout, _measure, _same, probe_tree,
)

NAME = "granite-4.0-h-small"
CELL = NAME + ".batchgen"

# The catalog row's ``config`` for ``granite-4.0-h-small`` (the
# ``model-configs`` guide's ``architectures.jsonl``; source
# https://huggingface.co/ibm-granite/granite-4.0-h-small/blob/main/config.json).
PUBLISHED = {
    "attention_bias": False, "attention_multiplier": 0.0078125,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 4096,
    "intermediate_size": 768,
    "layer_types": (["mamba"] * 5 + ["attention"] + ["mamba"] * 4) * 4,
    "logits_scaling": 16, "mamba_chunk_size": 256, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 128,
    "mamba_proj_bias": False, "max_position_embeddings": 131072,
    "model_type": "granitemoehybrid",
    "normalization_function": "rmsnorm", "num_attention_heads": 32,
    "num_experts_per_tok": 10, "num_hidden_layers": 40,
    "num_key_value_heads": 8, "num_local_experts": 72,
    "position_embedding_type": "nope", "residual_multiplier": 0.22,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "shared_intermediate_size": 1536, "tie_word_embeddings": True,
    "vocab_size": 100352,
}
REDUCED = {"num_hidden_layers": 10,
           "layer_types": PUBLISHED["layer_types"][:10],
           "num_local_experts": 36, "vocab_size": 50176}


def test_the_granite_configuration_holds_the_source_s_keys():
    """Every key of the catalog row's ``config`` at the top level under
    the same name and at the published value, but the four that are the
    chip's share of the stated deployment, which stand as run with
    ``published`` beside them; no width is among them, and the file
    says which two chips share a layer."""
    conf = next(c for c in BENCH["configs"] if c["name"] == NAME)
    config = _in_the_layout(conf, REPO)
    assert conf["reduced"] == list(REDUCED)
    for key, value in PUBLISHED.items():
        assert key in config, key
        assert _same(config[key], REDUCED.get(key, value)), key
    assert _same(config["published"], {k: PUBLISHED[k] for k in REDUCED})
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.isfile(catalog):  # the literal above is the row's config
        with open(catalog) as fh:
            row = next(r for r in map(json.loads, fh) if r["name"] == NAME)
        assert _same(row["config"], PUBLISHED)
        assert conf["source"] == row["source_url"]
    # a width is a hidden, intermediate, state or head size, a key that
    # ends in _dim or _rank, an expansion factor, the experts a token
    assert not [k for k in REDUCED if k != "vocab_size" and k.endswith(
        ("_size", "_dim", "_rank", "_head", "_state", "_expand",
         "_per_tok"))]
    for said in ("2 chips", "four pipeline stages", "all-reduce",
                 "experts 0 to 35"):
        assert said in config["deployment"], said
    assert config["first_local_expert"] == 0
    assert len(config["assumed"]) >= 3
    assert {"mamba_chunk_size", "rope_theta", "max_position_embeddings",
            "routed_sum", "serving_prefix_cache"} <= set(
                config["departures"])


def test_the_granite_cell_is_what_the_issue_sized():
    """What the program is told (the router's published width, the
    share, the pattern) and the load: 64 closed-loop clients on chains
    of 16 over ``batchgen``, 64 slots, 1,536 pages, no prefix cache."""
    cell = cellspec.load_cell(CELL)
    model = cell.config["model"]
    assert (model["experts"], model["experts_held"], model["expert_first"],
            model["expert_top_k"]) == (72, 36, 0, 10)
    assert model["layer_pattern"] == REDUCED["layer_types"]
    assert (model["n_layers"], model["vocab"]) == (10, 50176)
    assert model["rotary"] is False and model["ffn_gated"] is True
    payload = cell.config["payload"]
    assert (payload["serving_slots"], payload["serving_pages"],
            payload["serving_page_size"], payload["seq"]) == (
                64, 1536, 128, 3072)
    assert payload["serving_prefix_cache"] is False
    assert (cell.load["loop"], cell.load["clients"],
            cell.load["requests_per_client"]) == ("closed", 64, 16)
    entry = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (entry["chips"], entry["traffic"]) == (1, "batchgen")
    # it reports everything the cell beside it does, and three of its own
    names = {m["name"] for m in cell.per_layer}
    other = {m["name"] for m in
             cellspec.load_cell("starcoder2-3b.batchgen").per_layer}
    assert names - other == {"expert_held_pick_pct.closed",
                             "expert_imbalance.closed",
                             "state_reset_ms.closed"}
    assert other <= names
    # the document the server starts from parses, and refuses what the
    # block cannot run with
    from kvedge_tpu.config.runtime_config import (
        RuntimeConfig, RuntimeConfigError,
    )

    document = cellspec.runtime_document(cell, "<dir>", "tpu")
    parsed = RuntimeConfig.from_mapping(document)
    assert parsed.model.layer_pattern == tuple(REDUCED["layer_types"])
    assert parsed.model.experts_held == 36 and parsed.model.ssm_state == 128
    with pytest.raises(RuntimeConfigError, match="serving_prefix_cache"):
        RuntimeConfig.from_mapping(cellspec.runtime_document(
            cell, "<dir>", "tpu", {"serving_prefix_cache": True}))


def test_every_named_thing_has_its_file_where_cells_share_a_mix():
    """Two cells on one traffic mix: a mix's file is named once."""
    found = metrics.readers()
    for m in BENCH["per_layer"]:
        assert m["name"] in found, m["name"]
        for cell in m.get("workloads", ()):
            assert cell in {w["name"] for w in BENCH["workloads"]}
    read = {m["name"].removesuffix(".closed") for m in BENCH["per_layer"]}
    assert {name.removesuffix(".closed") for name in found} == read
    for kind, names in (
            ("cells", {w["name"] for w in BENCH["workloads"]}),
            ("traffic", {w["traffic"] for w in BENCH["workloads"]}),
            ("configs", {c["name"] for c in BENCH["configs"]})):
        files = os.listdir(os.path.join(REPO, "benchmark", kind))
        assert sorted(f.rsplit(".", 1)[0] for f in files) == sorted(names)


def test_the_patterned_block_s_counts_are_a_lower_bound_from_shapes():
    """``decode_step`` at the committed cut, against the same arithmetic
    by hand: the held matrices once in bf16, the router in float32, the
    rows' recurrent state once in and once out, live keys and values
    once."""
    cell = cellspec.load_cell(CELL)
    model, block = cell.config["model"], cell.reference
    d, inner, n = 4096, 8192, 128
    mamba = d * (2 * inner + 2 * n + 128) + inner * d
    attention = d * (32 + 16) * 128 + 4096 * d
    expert, shared = 3 * d * 768, 3 * d * 1536
    assert block.mamba_params(model) == mamba == 102_236_160
    assert block.attention_params(model) == attention == 41_943_040
    assert block.expert_params(model) == expert == 9_437_184
    state = 9 * (4 * inner * n + 2 * 3 * (inner + 2 * n))
    assert block.state_bytes_per_row(model) == state == 38_204_928
    assert block.kv_bytes_per_token(model) == 4096
    step = block.decode_step(model, 64.0, 64 * 1500.0)
    always = 9 * mamba + attention + 10 * shared + 50176 * d
    reached = 36 * (1 - (1 - 10 / 72) ** 64)
    assert 35.9 < reached < 36
    want = (2 * (always + 10 * reached * expert) + 4 * 10 * d * 72
            + 2 * 64 * state + 4096 * 64 * 1501)
    assert step["bytes"] == pytest.approx(want)
    # 9.5 GB of matrices, 4.9 GB of state, 0.4 GB of keys and values
    assert 14.5e9 < step["bytes"] < 15.2e9
    # one row reaches ten experts at most, and reads its own state alone
    one = block.decode_step(model, 1.0, 1500.0)
    assert one["bytes"] < 2 * (always + 10 * 10 * expert) + 3 * state
    assert step["flops"] < 0.02 * 197e12  # bound by bytes, not operations


# The patterned block at a probe size: two periods of m m a m, 4 of 8
# gated experts held, 3 a token, a shared expert, every multiplier other
# than 1, no rotary. The server refuses it with the prefix cache on.
PROBE3_CONFIG = {
    "reference": "granite_moe_hybrid",
    "source": "none: a probe size for the CPU tests",
    "reduced": ["num_local_experts"],
    "published": {"num_local_experts": 8},
    "deployment": "2 chips share each layer's experts, and this is one of "
                  "them: experts 0 to 3 of 8, the router at its published "
                  "width, every other part of a layer whole",
    "attention_bias": False, "attention_multiplier": 0.2,
    "embedding_multiplier": 3.0, "hidden_act": "silu", "hidden_size": 32,
    "intermediate_size": 16,
    "layer_types": ["mamba", "mamba", "attention", "mamba"] * 2,
    "logits_scaling": 2.0, "mamba_chunk_size": 16, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 8, "mamba_d_state": 16,
    "mamba_expand": 1, "mamba_n_groups": 1, "mamba_n_heads": 4,
    "mamba_proj_bias": False, "normalization_function": "rmsnorm",
    "num_attention_heads": 4, "num_experts_per_tok": 3,
    "num_hidden_layers": 8, "num_key_value_heads": 2,
    "num_local_experts": 4, "position_embedding_type": "nope",
    "residual_multiplier": 0.5, "rms_norm_eps": 1e-5,
    "shared_intermediate_size": 24, "tie_word_embeddings": True,
    "vocab_size": 256,
    "mesh": {"axes": {"data": 1}},
    "payload": {"seq": 256, "serving_slots": 4, "serving_page_size": 16,
                "serving_pages": 96, "serving_window": 8,
                "serving_prefix_cache": False,
                "serving_prefix_persist": False},
}


@pytest.fixture(scope="module")
def probe3(tmp_path_factory):
    """The harness tests' probe checkout with one more configuration and
    cell, added as files and entries: the patterned block under the
    closed-loop probe mix."""
    root = probe_tree(str(tmp_path_factory.mktemp("checkout")))
    bench = os.path.join(root, "benchmark")
    with open(os.path.join(bench, "configs", "probe3.json"), "w") as fh:
        json.dump(PROBE3_CONFIG, fh, indent=1)
    with open(os.path.join(bench, "cells", "probe.tinyclosed.json")) as fh:
        load = json.load(fh)
    with open(os.path.join(bench, "cells", "probe3.tinyclosed.json"),
              "w") as fh:
        json.dump(load, fh)
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        doc = json.load(fh)
    doc["configs"].append({
        "name": "probe3", "source": PROBE3_CONFIG["source"],
        "file": "benchmark/configs/probe3.json",
        "reduced": PROBE3_CONFIG["reduced"],
        "why": "probe of the patterned block, a chip's share of it"})
    doc["workloads"].append({"name": "probe3.tinyclosed", "config": "probe3",
                             "traffic": "tinyclosed", "chips": 1,
                             "why": "probe"})
    for metric in doc["per_layer"]:  # what the patterned block's cells read
        if CELL in metric.get("workloads", ()):
            metric["workloads"].append("probe3.tinyclosed")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as fh:
        json.dump(doc, fh)
    return root


def test_the_patterned_block_runs_whole_on_the_cpu(probe3, tmp_path):
    """The server starts from ``model_of``'s ``[model]`` (layer pattern,
    SSM sizes, the share of the experts), serves a closed loop, and is
    correct by the committed reference's float32 pass; the three readers
    this block brought find their counters, the others read as in any
    cell."""
    cell, line, said = _measure(probe3, 33, name="probe3.tinyclosed",
                                layers=True, out_dir=str(tmp_path))
    assert cell.reference.__file__.endswith("granite_moe_hybrid.py")
    model = cell.config["model"]
    assert model["layer_pattern"] == ["mamba", "mamba", "attention", "mamba"]
    assert (model["experts"], model["experts_held"]) == (8, 4)
    assert line["failed"] == 0 and line["attempted"] >= 3
    assert any("token_gap_mean" in s and s.endswith("ok") for s in said)
    got = line["metrics"]
    # 3 picks of 8 experts a token and layer, 4 of them held: about half
    assert 25.0 < got["expert_held_pick_pct.closed"]["value"] < 75.0
    assert 1.0 <= got["expert_imbalance.closed"]["value"] < 4.0
    assert got["state_reset_ms.closed"]["value"] > 0.0
    assert 0.0 < got["pool_live_pct.closed"]["value"] <= 100.0
    assert got["decode_bucket_fill_pct.closed"]["value"] > 0.0
    # the cells of the other block do not report the newcomers
    other = cellspec.load_cell("probe.tinyclosed", repo=probe3)
    assert "expert_imbalance.closed" not in {m["name"]
                                             for m in other.per_layer}


def test_the_new_readers_find_nothing_on_a_program_without_the_counters():
    """On the parent, which has no such phase or counter, each reader
    returns nothing and does not raise: the line leaves the metric out."""
    readers = metrics.readers()
    bare = {"stats_start": {"clock_s": 0.0, "phase_ms": {}},
            "stats_end": {"clock_s": 1.0, "phase_ms": {}}}
    for name in ("expert_held_pick_pct.closed", "expert_imbalance.closed",
                 "state_reset_ms.closed"):
        assert readers[name](bare) is None
        assert readers[name]({"stats_start": {}, "stats_end": {}}) is None
    held = readers["expert_held_pick_pct.closed"]({
        "stats_start": {"expert_picks_total": 100,
                        "expert_picks_held_total": 40},
        "stats_end": {"expert_picks_total": 300,
                      "expert_picks_held_total": 140}})
    assert held == pytest.approx(50.0)
    worst = readers["expert_imbalance.closed"]({
        "stats_start": {"expert_picks_by_expert": [0, 10, 0, 0]},
        "stats_end": {"expert_picks_by_expert": [10, 40, 10, 10]}})
    assert worst == pytest.approx(30 * 4 / 60)
    reset = readers["state_reset_ms.closed"]({
        "stats_start": {"phase_ms": {"admit/state_reset": [2, 1.0]}},
        "stats_end": {"phase_ms": {"admit/state_reset": [6, 3.0]}}})
    assert reset == pytest.approx(0.5)
