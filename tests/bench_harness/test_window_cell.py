"""The window block's configuration, cell and readers in the harness
(``smallthinker-21ba3b``, ``references/smallthinker.py``): the
committed file against the catalog row key by key, the cell as ISSUE 40
sized it, the block's counts against the same arithmetic by hand, one
whole CPU run of the block at a probe size through ``run.measure`` with
its readers in the line, and each reader on a program without its keys.
A file of its own: a ``model_config`` PR adds files beside the harness's
and edits none of them."""

import copy
import json
import os

import pytest

from benchmark import cellspec, metrics, schedule

from test_benchmark_harness import (
    BENCH, REPO, _in_the_layout, _measure, _same, probe_tree,
)

NAME = "smallthinker-21ba3b"
CELL = NAME + ".longmix"
NEW = ("window_dropped_pct.closed", "window_pool_live_pct.closed",
       "window_release_ms.closed", "paged_attention_roofline_pct.closed",
       "expert_pick_imbalance.closed")

# The catalog row's ``config`` for ``SmallThinker-21BA3B-Instruct`` (the
# ``model-configs`` guide's ``architectures.jsonl``; source
# https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct/blob/main/config.json).
LAYOUT = [0, 1, 1, 1] * 13
PUBLISHED = {
    "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
    "model_name": "smallthinker_21b_instruct", "moe_ffn_hidden_size": 768,
    "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "num_attention_heads": 28, "num_hidden_layers": 52,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_layout": LAYOUT, "rope_scaling": None, "rope_theta": 1500000,
    "sliding_window_layout": LAYOUT, "sliding_window_size": 4096,
    "tie_word_embeddings": False, "vocab_size": 151936,
}
REDUCED = {"num_hidden_layers": 8, "rope_layout": LAYOUT[:8],
           "sliding_window_layout": LAYOUT[:8]}


def test_the_window_configuration_holds_the_source_s_keys():
    """Every key of the catalog row's ``config`` at the top level under
    the same name and at the published value, but the depth and the two
    layouts cut to it, which stand as run with ``published`` beside
    them: no width, no expert and no row of the vocabulary is cut."""
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
                       if r["name"] == "SmallThinker-21BA3B-Instruct")
        assert _same(row["config"], PUBLISHED)
        assert conf["source"] == row["source_url"]
    assert not [k for k in REDUCED if k.endswith(
        ("_size", "_dim", "_rank", "_head", "_state", "_expand",
         "_per_tok", "_experts"))]
    # the floors: two whole periods, every expert, the whole vocabulary
    assert config["num_hidden_layers"] == 8
    assert config["moe_num_primary_experts"] == 64
    assert config["vocab_size"] == PUBLISHED["vocab_size"]
    for said in ("pipeline stages", "all 64 experts", "8 of 52 layers",
                 "embedding", "head"):
        assert said in config["deployment"], said
    assumed = " ".join(config["assumed"])
    for said in ("router placed before attention", "ReGLU",
                 "counts the query's own position", "no bias",
                 "only primary experts", "rotate-half", "0.02"):
        assert said in assumed, said
    assert {"max_position_embeddings", "serving_prefix_cache",
            "weights"} <= set(config["departures"])


def test_the_window_cell_is_what_the_issue_sized():
    """What the program is told (the pattern with its window kind, the
    base, the router's place, the gate) and the load: 64 closed-loop
    clients on chains of 16 over ``longmix``, 64 slots, two pools,
    chunks of 256, a window of 32."""
    cell = cellspec.load_cell(CELL)
    model = cell.config["model"]
    assert model["layer_pattern"] == ["attention", "window", "window",
                                      "window"]
    assert (model["n_layers"], model["vocab"], model["d_model"]) == (
        8, 151936, 2560)
    assert (model["n_heads"], model["n_kv_heads"], model["head_dim"]) == (
        28, 4, 128)
    assert (model["experts"], model["expert_top_k"], model["d_ff"]) == (
        64, 6, 768)
    assert "experts_held" not in model and "shared_ff" not in model
    assert model["attention_window"] == 4096
    assert model["rope_theta"] == 1.5e6 and model["rotary"] is False
    assert model["router_before_mixer"] is True
    assert (model["ffn_gated"], model["ffn_activation"]) == (True, "relu")
    assert model["untied_head"] is True
    payload = cell.config["payload"]
    assert (payload["seq"], payload["serving_slots"],
            payload["serving_page_size"], payload["serving_pages"]) == (
                8192, 64, 128, 2816)
    # the window layers' pool has no key: slots x a row's cap
    assert "serving_window_pages" not in payload
    assert payload["serving_prefix_cache"] is False
    assert payload["serving_prefill_chunk"] == cell.load["prefill_chunk"] \
        == 256
    assert payload["serving_window"] == cell.load["decode_window"] == 32
    assert (cell.load["loop"], cell.load["clients"],
            cell.load["requests_per_client"], cell.load["ramp_s"],
            cell.load["drain_s"]) == ("closed", 64, 16, 24.0, 4.0)
    # 16 requests a check and not ISSUE 40's 4: over 4 the mean gap read
    # 0.022 to 0.041 on four seeds and over 8 0.017 to 0.045 on eight
    # (now and then a request reads several times the others), too wide
    # to hold a limit under the int8 control's 0.082 (PERF.md section 6,
    # PR 40); the extreme's limit is a guard at twice the largest
    # reading or more (test_check_limits.py holds it to the file's own)
    assert cell.load["check"]["requests"] == 16
    limits = cell.load["check"]["limits"]
    assert limits["token_gap_mean"] == 0.058
    assert limits["token_gap_max"] >= 2 * 2.05
    assert cell.traffic["prompt"] == {"dist": "uniform", "min": 512,
                                      "max": 5120, "multiple": 256}
    assert cell.traffic["output"] == {"dist": "uniform", "min": 1024,
                                      "max": 3008, "multiple": 1}
    entry = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (entry["chips"], entry["traffic"]) == (1, "longmix")
    # final lengths to 8,128: past the window, within the served context
    plan = schedule.build(cell.traffic, cell.load, 1, 48.0, model["vocab"])
    totals = [r["prompt"] + r["n_new"] for r in plan["requests"]]
    assert 2 * 4096 - 128 < max(totals) <= payload["seq"]
    assert sum(t > 4096 for t in totals) > len(totals) // 2
    # one prefill tail: every warm-up and every window prompt is whole
    # chunks
    assert all(r["prompt"] % 256 == 0 for r in plan["requests"])
    # it reports everything the first cell does, and five of its own,
    # each listed for this cell alone
    names = {m["name"] for m in cell.per_layer}
    other = {m["name"] for m in
             cellspec.load_cell("starcoder2-3b.batchgen").per_layer}
    assert names - other == set(NEW)
    assert other <= names
    for name in NEW:
        metric = next(m for m in BENCH["per_layer"] if m["name"] == name)
        assert metric["workloads"] == [CELL]
        assert metric["moves"] == "out_tok_s"
    # the document the server starts from parses, and refuses what the
    # block cannot run with
    from kvedge_tpu.config.runtime_config import (
        RuntimeConfig, RuntimeConfigError,
    )

    document = cellspec.runtime_document(cell, "<dir>", "tpu")
    parsed = RuntimeConfig.from_mapping(document)
    assert parsed.model.layer_pattern == ("attention", "window", "window",
                                          "window")
    assert parsed.model.attention_window == 4096
    assert parsed.model.rope_theta == 1.5e6
    assert parsed.model.router_before_mixer
    assert parsed.model.ffn_activation == "relu"
    assert RuntimeConfig.parse(parsed.to_toml()) == parsed
    for key, value in (("serving_prefix_cache", True),
                       ("serving_speculative", 3),
                       ("serving_kv_dtype", "int8")):
        with pytest.raises(RuntimeConfigError, match=key):
            RuntimeConfig.from_mapping(cellspec.runtime_document(
                cell, "<dir>", "tpu", {key: value}))


def _sizes_doubled(config: dict) -> dict:
    """The file with every size twice as large: widths, heads, experts,
    vocabulary, the window; the depth, the layouts and the flags as
    they are."""
    keep = {"num_hidden_layers"}
    out = {k: 2 * v if type(v) is int and k not in keep else v
           for k, v in config.items()}
    out["payload"] = {**config["payload"], "seq": 2 * config["payload"]["seq"]}
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
    stated = {v for group in (config, config["payload"])
              for v in group.values() if type(v) is int}
    sizes = {k: v for k, v in model.items() if type(v) is int}
    assert set(sizes) == {
        "vocab", "d_model", "n_heads", "n_kv_heads", "head_dim", "n_layers",
        "attention_window", "experts", "expert_top_k", "d_ff", "seq"}
    assert all(v in stated for v in sizes.values())
    twice = cell.reference.model_of(_sizes_doubled(config))
    for key, value in sizes.items():
        assert twice[key] == (value if key == "n_layers" else 2 * value), key
    assert twice["rope_theta"] == 2 * model["rope_theta"]
    assert twice["layer_pattern"] == model["layer_pattern"]


@pytest.mark.parametrize("key, value", [
    ("moe_primary_router_apply_softmax", False), ("norm_topk_prob", False),
    ("tie_word_embeddings", True), ("rope_scaling", {"type": "yarn"}),
    ("rope_layout", [1] * 8), ("sliding_window_layout", [0, 1] * 3),
])
def test_a_key_the_block_is_not_written_for_is_refused(key, value):
    with open(os.path.join(REPO, "benchmark", "configs",
                           NAME + ".json")) as fh:
        config = json.load(fh)
    block = cellspec.load_cell(CELL).reference
    with pytest.raises(SystemExit, match=key.split("_layout")[0]):
        block.model_of({**config, key: value})


def test_the_window_block_s_counts_never_pass_a_count_from_the_shapes():
    """``decode_step`` at the committed cut, against ISSUE 40's
    arithmetic by hand: every matrix once in bf16, the router in
    float32, the full layers' live keys and values once and the window
    layers' at the least the rows' windows can hold; and for every
    split of the live positions over the rows, no more than what the
    rows' shapes give."""
    cell = cellspec.load_cell(CELL)
    model, block = cell.config["model"], cell.reference
    d = 2560
    attention = d * (28 + 8) * 128 + 28 * 128 * d
    expert = 3 * d * 768
    assert block.attention_params(model) == attention == 20_971_520
    assert block.expert_params(model) == expert == 5_898_240
    assert block.attention_layers(model) == (2, 6)
    assert block.kv_bytes_per_token(model) == 2048
    assert block.page_bytes(model, 128) == 262_144
    layer = attention + 64 * expert + d * 64
    assert 398.5e6 < layer < 398.7e6
    tree = 8 * layer + 2 * 151936 * d
    assert 3.96e9 < tree < 3.97e9  # 7.93 GB in bf16
    rows, live = 62.0, 62 * 4300.0
    step = block.decode_step(model, rows, live)
    weights = (2 * (8 * attention + 151936 * d + 8 * 64 * expert)
               + 4 * 8 * d * 64)
    held = live * 4096 / 8192
    want = weights + 2048 * (2 * (live + rows) + 6 * (held + rows))
    assert step["bytes"] == pytest.approx(want)
    assert 0.53 < 2 * 8 * 64 * expert / step["bytes"] < 0.62
    assert step["flops"] < 0.05 * 197e12  # bound by bytes
    # any split of the same positions over the rows holds as much or
    # more in its windows than the count assumes
    for split in ([4300.0] * 62, [8192.0] * 32 + [150.0] * 30,
                  [1536.0] * 31 + [7064.0] * 31):
        assert sum(split) == pytest.approx(live, rel=0.02)
        by_shape = weights + 2048 * (
            2 * (sum(split) + rows)
            + 6 * (sum(min(n, 4096.0) for n in split) + rows))
        got = block.decode_step(model, rows, sum(split))["bytes"]
        assert got <= by_shape * (1 + 1e-9)
    # contexts within the window: nothing is dropped, nothing understated
    short = dict(model, seq=4096)
    assert block.window_tokens(short, 4.0, 9000.0) == 9000.0
    assert block.window_tokens(model, 4.0, 9000.0) == 4500.0


# The window block at a probe size: two periods of f w w w, all 8 ReLU-
# gated experts held, 3 a token, routed before the mixer, a window of 32
# under contexts to 184, a head of its own. The server refuses it with
# the prefix cache on.
PROBE5_CONFIG = {
    "reference": "smallthinker",
    "source": "none: a probe size for the CPU tests",
    "reduced": [], "published": {},
    "deployment": "one virtual CPU device holds every layer whole",
    "head_dim": 16, "hidden_size": 32, "max_position_embeddings": 512,
    "model_name": "probe5", "moe_ffn_hidden_size": 16,
    "moe_num_active_primary_experts": 3, "moe_num_primary_experts": 8,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "num_attention_heads": 4, "num_hidden_layers": 8,
    "num_key_value_heads": 2, "rms_norm_eps": 1e-6,
    "rope_layout": [0, 1, 1, 1, 0, 1, 1, 1], "rope_scaling": None,
    "rope_theta": 1500000,
    "sliding_window_layout": [0, 1, 1, 1, 0, 1, 1, 1],
    "sliding_window_size": 32, "tie_word_embeddings": False,
    "vocab_size": 256,
    "mesh": {"axes": {"data": 1}},
    "payload": {"seq": 256, "serving_slots": 4, "serving_page_size": 16,
                "serving_pages": 96, "serving_window": 8,
                "serving_prefill_chunk": 32,
                "serving_prefix_cache": False,
                "serving_prefix_persist": False},
}


@pytest.fixture(scope="module")
def probe5(tmp_path_factory):
    """The harness tests' probe checkout with one more configuration and
    cell, added as files and entries: the window block under the
    closed-loop probe mix."""
    root = probe_tree(str(tmp_path_factory.mktemp("checkout")))
    bench = os.path.join(root, "benchmark")
    with open(os.path.join(bench, "configs", "probe5.json"), "w") as fh:
        json.dump(PROBE5_CONFIG, fh, indent=1)
    with open(os.path.join(bench, "cells", "probe.tinyclosed.json")) as fh:
        load = json.load(fh)
    load["prefill_chunk"] = 32
    # the program computes in bf16 here as on the chip, against float32
    load["check"]["limits"] = {"token_gap_max": 1.0, "token_gap_mean": 0.06}
    with open(os.path.join(bench, "cells", "probe5.tinyclosed.json"),
              "w") as fh:
        json.dump(load, fh)
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        doc = json.load(fh)
    doc["configs"].append({
        "name": "probe5", "source": PROBE5_CONFIG["source"],
        "file": "benchmark/configs/probe5.json",
        "reduced": [], "why": "probe of the window block"})
    doc["workloads"].append({"name": "probe5.tinyclosed", "config": "probe5",
                             "traffic": "tinyclosed", "chips": 1,
                             "why": "probe"})
    for metric in doc["per_layer"]:  # what the window block's cell reads
        if CELL in metric.get("workloads", ()):
            metric["workloads"].append("probe5.tinyclosed")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as fh:
        json.dump(doc, fh)
    return root


def test_the_window_block_runs_whole_on_the_cpu(probe5, tmp_path):
    """The server starts from ``model_of``'s ``[model]`` (the pattern
    with its window kind, the base, the router's place, the gate),
    serves a closed loop whose contexts pass the window several times
    over, and is correct by the committed reference's float32 pass; the
    readers this block brought find their counters (the kernel's share
    needs a trace and is left out), the others read as in any cell."""
    cell, line, said = _measure(probe5, 40, name="probe5.tinyclosed",
                                layers=True, out_dir=str(tmp_path))
    assert cell.reference.__file__.endswith("smallthinker.py")
    model = cell.config["model"]
    assert model["layer_pattern"] == ["attention", "window", "window",
                                      "window"]
    assert line["failed"] == 0 and line["attempted"] >= 3
    assert line["correct"], said
    assert any("token_gap_mean" in s and s.endswith("ok") for s in said)
    got = line["metrics"]
    # contexts of 72 to 184 positions under a window of 32: the window
    # layers hold 3 to 4 pages of 16 where the context spans 5 to 12
    assert 20.0 < got["window_dropped_pct.closed"]["value"] < 80.0
    # 4 slots of a cap of 5 pages: 20 pages, most of them held
    assert 20.0 < got["window_pool_live_pct.closed"]["value"] <= 100.0
    assert got["window_release_ms.closed"]["value"] > 0.0
    # 8 experts, 3 a token: never under 1, never over the 8 one expert
    # with every pick would read
    assert 1.0 <= got["expert_pick_imbalance.closed"]["value"] < 8.0
    assert "paged_attention_roofline_pct.closed" not in got
    assert 0.0 < got["pool_live_pct.closed"]["value"] <= 100.0
    assert got["lock_unnamed_pct.closed"]["value"] == pytest.approx(
        0.0, abs=0.5)
    # the cells of the other blocks do not report the newcomers
    other = cellspec.load_cell("probe.tinyclosed", repo=probe5)
    assert not set(NEW) & {m["name"] for m in other.per_layer}


@pytest.mark.parametrize("name", NEW)
def test_a_new_reader_finds_nothing_on_a_program_without_its_keys(name):
    """On the parent, which has no such counter or phase, each reader
    returns nothing and does not raise: the line leaves the metric
    out."""
    read = metrics.readers()[name]
    cell = cellspec.load_cell("starcoder2-3b.batchgen")
    base = {"cell": cell, "events": None, "records": [],
            "peak": {"hbm_bytes_per_s": 819e9}}
    old = {"clock_s": 0.0, "decode_steps_total": 0,
           "pages_live_steps_total": 0, "pages_total": 640,
           "phase_ms": {"loop/emit": [0, 0.0]}}
    new = {"clock_s": 1.0, "decode_steps_total": 64,
           "pages_live_steps_total": 6400, "pages_total": 640,
           "phase_ms": {"loop/emit": [2, 1.0]}}
    assert read({**base, "stats_start": old, "stats_end": new}) is None
    assert read({**base, "stats_start": {}, "stats_end": {}}) is None


def test_the_new_readers_read_what_the_server_counts():
    """The three counter readers on two snapshots by hand: 64 steps in
    which the live rows' contexts spanned 100 pages and their window
    tables held 60 of a pool of 120, and 10 givings-back of 0.5 ms."""
    found = metrics.readers()
    old = {"decode_steps_total": 100, "pages_live_steps_total": 1000,
           "window_pages_live_steps_total": 500, "window_pages_total": 120,
           "phase_ms": {"loop/window_release": [4, 1.0],
                        "admit/window_release": [1, 1.0]}}
    new = {"decode_steps_total": 164, "pages_live_steps_total": 7400,
           "window_pages_live_steps_total": 4340, "window_pages_total": 120,
           "phase_ms": {"loop/window_release": [10, 3.5],
                        "admit/window_release": [5, 3.5]}}
    ctx = {"stats_start": old, "stats_end": new}
    assert found["window_dropped_pct.closed"](ctx) == pytest.approx(40.0)
    assert found["window_pool_live_pct.closed"](ctx) == pytest.approx(50.0)
    assert found["window_release_ms.closed"](ctx) == pytest.approx(0.5)
    # the fifth reads the pick counters as ``expert_imbalance.closed``
    # does in its cell: 4 experts' picks grew by 30, 10, 10, 10
    ctx = {"stats_start": {"expert_picks_by_expert": [5, 5, 5, 5]},
           "stats_end": {"expert_picks_by_expert": [35, 15, 15, 15]}}
    assert found["expert_pick_imbalance.closed"](ctx) == pytest.approx(2.0)
    assert found["expert_pick_imbalance.closed"](ctx) \
        == found["expert_imbalance.closed"](ctx)
    ctx = {"stats_start": old, "stats_end": new}
    # no context past the window: the tables hold what the contexts span
    new["window_pages_live_steps_total"] = 500 + 6400
    assert found["window_dropped_pct.closed"](ctx) == pytest.approx(0.0)
