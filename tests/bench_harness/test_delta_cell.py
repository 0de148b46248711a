"""The delta-rule block's configuration, cell and reader in the harness
(``solar-open2-250b``, ``references/solar_open2.py``): the committed
file against the catalog row key by key, the block's counts against the
same arithmetic by hand, and one whole CPU run of the block at a probe
size through ``run.measure``, its reader in the line. A file of its
own: a ``model_config`` PR adds files beside the harness's and edits
none of them."""

import copy
import json
import os

import pytest

from benchmark import cellspec, metrics

from test_benchmark_harness import (
    BENCH, REPO, _in_the_layout, _measure, _same, probe_tree,
)

NAME = "solar-open2-250b"
CELL = NAME + ".batchgen"
TOUCHED = "expert_touched_pct.closed"

# The catalog row's ``config`` for ``Solar-Open2-250B`` (the
# ``model-configs`` guide's ``architectures.jsonl``; source
# https://huggingface.co/upstage/Solar-Open2-250B/blob/main/config.json).
PUBLISHED = {
    "model_type": "solar_open2", "partial_rotary_factor": 1,
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 128,
                           "num_heads": 64, "num_kv_heads": None},
    "hidden_size": 4096, "num_hidden_layers": 48,
    "num_attention_heads": 64, "head_dim": 128, "num_key_value_heads": 8,
    "vocab_size": 196608, "intermediate_size": 10240,
    "moe_intermediate_size": 1280, "rms_norm_eps": 1e-05,
    "rope_theta": 10000, "tie_word_embeddings": False,
    "max_position_embeddings": 1048576, "first_k_dense_replace": 0,
    "use_rope": False, "gqa_interval": 3,
    "gqa_layers": [0, 4, 8, 12, 16, 20, 24, 28, 32, 36, 40, 44],
    "use_gqa_gate": True, "kda_use_full_proj": False,
    "kda_allow_neg_eigval": True, "n_routed_experts": 320,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "routed_scaling_factor": 1, "num_experts_per_tok": 8,
}
REDUCED = {"num_hidden_layers": 4, "gqa_layers": [0],
           "n_routed_experts": 40, "vocab_size": 24576}


def test_the_solar_configuration_holds_the_source_s_keys():
    """Every key of the catalog row's ``config`` at the top level under
    the same name and at the published value (``linear_attn_config``
    whole), but the four that are the chip's share of the stated
    deployment, which stand as run with ``published`` beside them; no
    width is among them, and the file says which eight chips share a
    layer and what it assumed."""
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
            row = next(r for r in map(json.loads, fh)
                       if r["name"] == "Solar-Open2-250B")
        assert _same(row["config"], PUBLISHED)
        assert conf["source"] == row["source_url"]
    # a width is a hidden, intermediate, state or head size, a key that
    # ends in _dim or _rank, an expansion factor, the experts a token
    assert not [k for k in REDUCED if k != "vocab_size" and k.endswith(
        ("_size", "_dim", "_rank", "_head", "_state", "_expand",
         "_per_tok"))]
    # the floors: a whole period and four layers, 8 experts or more, an
    # eighth of the vocabulary
    assert config["num_hidden_layers"] == config["gqa_interval"] + 1 >= 4
    assert config["n_routed_experts"] >= 8
    assert 8 * config["vocab_size"] >= PUBLISHED["vocab_size"]
    for said in ("8 chips", "12 pipeline stages", "all-reduce",
                 "experts 0 to 39", "rows 0 to 24,575"):
        assert said in config["deployment"], said
    assert config["first_routed_expert"] == 0
    assumed = " ".join(config["assumed"])
    for said in ("rank 128", "dt_bias", "L2-normalised", "W_gate",
                 "softmax over all 320", "moe_intermediate_size x",
                 "float32", "0.02"):
        assert said in assumed, said
    assert {"intermediate_size", "rope_theta", "partial_rotary_factor",
            "max_position_embeddings", "routed_sum",
            "serving_prefix_cache"} <= set(config["departures"])


def test_the_solar_cell_is_what_the_issue_sized():
    """What the program is told (the router's published width, the
    share, the pattern, the new keys) and the load: 64 closed-loop
    clients on chains of 16 over ``batchgen``, 64 slots, 1,536 pages,
    no prefix cache, a window of 32."""
    cell = cellspec.load_cell(CELL)
    model = cell.config["model"]
    assert (model["experts"], model["experts_held"], model["expert_first"],
            model["expert_top_k"]) == (320, 40, 0, 8)
    assert model["layer_pattern"] == ["attention", "delta", "delta", "delta"]
    assert (model["n_layers"], model["vocab"]) == (4, 24576)
    assert (model["n_heads"], model["n_kv_heads"], model["head_dim"]) == (
        64, 8, 128)
    assert (model["ssm_heads"], model["ssm_head_dim"], model["ssm_state"],
            model["ssm_conv"], model["ssm_gate_rank"]) == (
                64, 128, 128, 4, 128)
    assert (model["d_ff"], model["shared_ff"]) == (1280, 1280)
    assert model["rotary"] is False and model["ffn_gated"] is True
    assert model["attention_gate"] is True and model["untied_head"] is True
    payload = cell.config["payload"]
    assert (payload["serving_slots"], payload["serving_pages"],
            payload["serving_page_size"], payload["seq"]) == (
                64, 1536, 128, 3072)
    assert payload["serving_prefix_cache"] is False
    assert payload["serving_window"] == cell.load["decode_window"] == 32
    assert (cell.load["loop"], cell.load["clients"],
            cell.load["requests_per_client"], cell.load["ramp_s"],
            cell.load["drain_s"], cell.load["check"]["requests"]) == (
                "closed", 64, 16, 24.0, 4.0, 4)
    assert cell.load["programs"] == cellspec.load_cell(
        "granite-4.0-h-small.batchgen").load["programs"]
    entry = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (entry["chips"], entry["traffic"]) == (1, "batchgen")
    # it reports everything the first cell does, and one of its own
    names = {m["name"] for m in cell.per_layer}
    other = {m["name"] for m in
             cellspec.load_cell("starcoder2-3b.batchgen").per_layer}
    assert names - other == {TOUCHED}
    assert other <= names
    touched = next(m for m in BENCH["per_layer"] if m["name"] == TOUCHED)
    assert touched == {
        "name": TOUCHED, "unit": "%", "better": "lower",
        "source": "program_counter", "layer": "model step",
        "moves": "out_tok_s", "workloads": [CELL]}
    # the document the server starts from parses, and refuses what the
    # block cannot run with
    from kvedge_tpu.config.runtime_config import (
        RuntimeConfig, RuntimeConfigError,
    )

    document = cellspec.runtime_document(cell, "<dir>", "tpu")
    parsed = RuntimeConfig.from_mapping(document)
    assert parsed.model.layer_pattern == ("attention", "delta", "delta",
                                          "delta")
    assert parsed.model.experts_held == 40 and parsed.model.head_dim == 128
    assert parsed.model.attention_gate and parsed.model.untied_head
    assert RuntimeConfig.parse(parsed.to_toml()).model == parsed.model
    with pytest.raises(RuntimeConfigError, match="serving_prefix_cache"):
        RuntimeConfig.from_mapping(cellspec.runtime_document(
            cell, "<dir>", "tpu", {"serving_prefix_cache": True}))


def _sizes_doubled(config: dict) -> dict:
    """The file with every size twice as large: widths, heads, experts,
    vocabulary, the conv, at the top level and inside the nested group;
    the depth and the pattern (``gqa_*``) as they are."""
    keep = {"num_hidden_layers", "gqa_interval", "gqa_layers",
            "first_k_dense_replace", "routed_scaling_factor",
            "partial_rotary_factor", "first_routed_expert",
            "n_shared_experts"}

    def doubled(group: dict) -> dict:
        return {k: 2 * v if type(v) is int and k not in keep else v
                for k, v in group.items()}

    out = doubled(config)
    for key in ("linear_attn_config", "published"):
        out[key] = doubled(config[key])
    return out


def test_a_file_stating_every_size_twice_as_large_runs_them_so():
    """What the file states is what runs: the server's ``model`` is
    ``model_of`` of the file, each of its sizes is a value the file
    states, and a file that stated every size twice as large would run
    every size twice as large (nothing in ``model_of`` is a size of its
    own)."""
    cell = cellspec.load_cell(CELL)
    with open(os.path.join(REPO, "benchmark", "configs",
                           NAME + ".json")) as fh:
        config = json.load(fh)
    model = cell.config.pop("model")
    assert cell.config == config
    assert _same(model, cell.reference.model_of(copy.deepcopy(config)))
    stated = {v for group in (config, config["linear_attn_config"],
                              config["published"])
              for v in group.values() if type(v) is int}
    sizes = {k: v for k, v in model.items() if type(v) is int}
    assert set(sizes) == {
        "vocab", "d_model", "n_heads", "n_kv_heads", "head_dim", "n_layers",
        "ssm_heads", "ssm_head_dim", "ssm_state", "ssm_conv",
        "ssm_gate_rank", "experts", "experts_held", "expert_first",
        "expert_top_k", "d_ff", "shared_ff"}
    assert all(v in stated or k == "expert_first" for k, v in sizes.items())
    twice = cell.reference.model_of(_sizes_doubled(config))
    for key, value in sizes.items():
        assert twice[key] == (value if key in ("n_layers", "expert_first")
                              else 2 * value), key
    assert twice["layer_pattern"] == model["layer_pattern"]


@pytest.mark.parametrize("key, value", [
    ("use_rope", True), ("use_gqa_gate", False),
    ("kda_use_full_proj", True), ("tie_word_embeddings", True),
    ("first_k_dense_replace", 1), ("gqa_layers", [1]),
])
def test_a_key_the_block_is_not_written_for_is_refused(key, value):
    with open(os.path.join(REPO, "benchmark", "configs",
                           NAME + ".json")) as fh:
        config = json.load(fh)
    block = cellspec.load_cell(CELL).reference
    with pytest.raises(SystemExit, match=key):
        block.model_of({**config, key: value})


def test_the_delta_block_s_counts_are_a_lower_bound_from_shapes():
    """``decode_step`` at the committed cut, against ISSUE 36's
    arithmetic by hand: every held matrix once in bf16, the router in
    float32, the rows' recurrent state once in and once out, live keys
    and values once: about 8.4 GB at 64 rows, three fifths of it the
    held experts and a fifth the state."""
    cell = cellspec.load_cell(CELL)
    model, block = cell.config["model"], cell.reference
    d, keys = 4096, 8192
    delta = (d * 3 * keys + d * (2 * 128 + 64) + 128 * 2 * keys
             + 4 * 3 * keys + keys * d)
    attention = d * (64 + 16) * 128 + 2 * keys * d
    expert = shared = 3 * d * 1280
    assert block.delta_params(model) == delta == 137_723_904
    assert block.attention_params(model) == attention == 109_051_904
    assert block.expert_params(model) == expert == 15_728_640
    assert block.shared_params(model) == shared
    state = 3 * (4 * keys * 128 + 2 * 3 * 3 * keys)
    assert block.state_bytes_per_row(model) == state == 13_025_280
    assert block.kv_bytes_per_token(model) == 4096
    # the tree: 3.308 B parameters with the embedding's slice beside the
    # head's, the norms' gains and the routers
    tree = (3 * delta + attention + 4 * (shared + 40 * expert + d * 320)
            + 2 * 24576 * d)
    assert 3.30e9 < tree < 3.32e9
    step = block.decode_step(model, 64.0, 77000.0)
    always = 3 * delta + attention + 4 * shared + 24576 * d
    want = (2 * (always + 4 * 40 * expert) + 4 * 4 * d * 320
            + 2 * 64 * state + 4096 * (77000 + 64))
    assert step["bytes"] == pytest.approx(want)
    assert 8.3e9 < step["bytes"] < 8.5e9
    assert 0.58 < 2 * 4 * 40 * expert / step["bytes"] < 0.62
    assert 0.18 < 2 * 64 * state / step["bytes"] < 0.21
    # one row reads every held expert all the same, and its own state
    one = block.decode_step(model, 1.0, 1500.0)
    assert one["bytes"] > 2 * 4 * 40 * expert
    assert one["bytes"] < (2 * (always + 4 * 40 * expert) + 4 * 4 * d * 320
                           + 3 * state)
    assert step["flops"] < 0.02 * 197e12  # bound by bytes, not operations


# The delta-rule block at a probe size: two periods of a d d d, 2 of 16
# gated experts held (one of eight shares), 3 a token, a shared expert,
# heads that are not the hidden size divided up, a gate on the attention
# layer and a head of its own. The server refuses it with the prefix
# cache on.
PROBE4_CONFIG = {
    "reference": "solar_open2",
    "source": "none: a probe size for the CPU tests",
    "reduced": ["n_routed_experts"],
    "published": {"n_routed_experts": 16},
    "deployment": "8 chips share each layer's experts, and this is one of "
                  "them: experts 0 and 1 of 16, the router at its "
                  "published width, every other part of a layer whole",
    "model_type": "solar_open2", "partial_rotary_factor": 1,
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 8,
                           "num_heads": 4, "num_kv_heads": None},
    "hidden_size": 32, "num_hidden_layers": 8, "num_attention_heads": 4,
    "head_dim": 16, "num_key_value_heads": 2, "vocab_size": 256,
    "intermediate_size": 64, "moe_intermediate_size": 16,
    "rms_norm_eps": 1e-5, "rope_theta": 10000, "tie_word_embeddings": False,
    "max_position_embeddings": 4096, "first_k_dense_replace": 0,
    "use_rope": False, "gqa_interval": 3, "gqa_layers": [0, 4],
    "use_gqa_gate": True, "kda_use_full_proj": False,
    "kda_allow_neg_eigval": True, "n_routed_experts": 2,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "routed_scaling_factor": 1, "num_experts_per_tok": 3,
    "mesh": {"axes": {"data": 1}},
    "payload": {"seq": 256, "serving_slots": 4, "serving_page_size": 16,
                "serving_pages": 96, "serving_window": 8,
                "serving_prefix_cache": False,
                "serving_prefix_persist": False},
}


@pytest.fixture(scope="module")
def probe4(tmp_path_factory):
    """The harness tests' probe checkout with one more configuration and
    cell, added as files and entries: the delta-rule block under the
    closed-loop probe mix."""
    root = probe_tree(str(tmp_path_factory.mktemp("checkout")))
    bench = os.path.join(root, "benchmark")
    with open(os.path.join(bench, "configs", "probe4.json"), "w") as fh:
        json.dump(PROBE4_CONFIG, fh, indent=1)
    with open(os.path.join(bench, "cells", "probe.tinyclosed.json")) as fh:
        load = json.load(fh)
    # The program computes in bf16 here as on the chip, and a delta layer
    # of 8 key channels carries a rounding of its input on two to three
    # times as large (its keys are far from orthogonal, so the rule's
    # corrections are large): logits of size 0.4 come out 0.04 from the
    # float32 reference's and the mean gap reads 0.027, where the other
    # probes' logits of size 0.07 read under 0.01.
    load["check"]["limits"] = {"token_gap_max": 1.0, "token_gap_mean": 0.06}
    with open(os.path.join(bench, "cells", "probe4.tinyclosed.json"),
              "w") as fh:
        json.dump(load, fh)
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        doc = json.load(fh)
    doc["configs"].append({
        "name": "probe4", "source": PROBE4_CONFIG["source"],
        "file": "benchmark/configs/probe4.json",
        "reduced": PROBE4_CONFIG["reduced"],
        "why": "probe of the delta-rule block, a chip's share of it"})
    doc["workloads"].append({"name": "probe4.tinyclosed", "config": "probe4",
                             "traffic": "tinyclosed", "chips": 1,
                             "why": "probe"})
    for metric in doc["per_layer"]:  # what the delta block's cell reads
        if CELL in metric.get("workloads", ()):
            metric["workloads"].append("probe4.tinyclosed")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as fh:
        json.dump(doc, fh)
    return root


def test_the_delta_block_runs_whole_on_the_cpu(probe4, tmp_path):
    """The server starts from ``model_of``'s ``[model]`` (the pattern
    with delta layers, the heads' own size, the gate, the head, the
    share of the experts), serves a closed loop, and is correct by the
    committed reference's float32 pass; the reader this block brought
    finds its counters, the others read as in any cell."""
    cell, line, said = _measure(probe4, 36, name="probe4.tinyclosed",
                                layers=True, out_dir=str(tmp_path))
    assert cell.reference.__file__.endswith("solar_open2.py")
    model = cell.config["model"]
    assert model["layer_pattern"] == ["attention", "delta", "delta", "delta"]
    assert (model["experts"], model["experts_held"]) == (16, 2)
    assert line["failed"] == 0 and line["attempted"] >= 3
    assert any("token_gap_mean" in s and s.endswith("ok") for s in said)
    got = line["metrics"]
    # 3 picks of 16 experts a token and layer, a few rows: each of the
    # 2 held experts is touched in some steps and not in others
    assert 5.0 < got[TOUCHED]["value"] < 95.0
    assert 0.0 < got["pool_live_pct.closed"]["value"] <= 100.0
    assert got["decode_bucket_fill_pct.closed"]["value"] > 0.0
    assert got["decode_step_wall_ms.closed"]["value"] > 0.0
    # the cells of the other blocks do not report the newcomer
    other = cellspec.load_cell("probe.tinyclosed", repo=probe4)
    assert TOUCHED not in {m["name"] for m in other.per_layer}


def test_the_new_reader_finds_nothing_on_a_program_without_the_counter():
    """On the parent, which has no such counter, the reader returns
    nothing and does not raise: the line leaves the metric out."""
    read = metrics.readers()[TOUCHED]
    assert read({"stats_start": {"clock_s": 0.0, "decode_steps_total": 0},
                 "stats_end": {"clock_s": 1.0,
                               "decode_steps_total": 64}}) is None
    assert read({"stats_start": {}, "stats_end": {}}) is None
    # 160 (layer, held expert) matrices a step, 100 steps, 12,800 of the
    # 16,000 touched
    got = read({
        "stats_start": {"decode_steps_total": 50,
                        "expert_touched_total": 1000,
                        "expert_reads_per_step": 160},
        "stats_end": {"decode_steps_total": 150,
                      "expert_touched_total": 13800,
                      "expert_reads_per_step": 160}})
    assert got == pytest.approx(80.0)
    # no step in the window: nothing to divide by
    assert read({
        "stats_start": {"decode_steps_total": 50, "expert_touched_total": 9,
                        "expert_reads_per_step": 160},
        "stats_end": {"decode_steps_total": 50, "expert_touched_total": 9,
                      "expert_reads_per_step": 160}}) is None
