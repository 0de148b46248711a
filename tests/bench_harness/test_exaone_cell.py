"""The K-EXAONE block's configuration, cell and reader in the harness
(``k-exaone-236b-a23b``, ``references/exaone_moe.py``): the committed
file against the catalog row key by key, the cell as ISSUE 43 sized it,
the block's counts against the same arithmetic by hand, one whole CPU
run of the block at a probe size through ``run.measure``, and the new
reader on a hand-made capture with and without a blocked call. A file
of its own: a ``model_config`` PR adds files beside the harness's and
edits none of them."""

import copy
import json
import os

import pytest

from benchmark import cellspec, metrics, schedule, trace

from test_benchmark_harness import (
    BENCH, REPO, _in_the_layout, _measure, _same, _stats, probe_tree,
)

NAME = "k-exaone-236b-a23b"
CELL = NAME + ".longmix"
NEW = "blocked_attention_roofline_pct.closed"

# The catalog row's ``config`` for ``K-EXAONE-236B-A23B`` (the
# ``model-configs`` guide's ``architectures.jsonl``; source
# https://huggingface.co/LGAI-EXAONE/K-EXAONE-236B-A23B/blob/main/config.json).
TYPES = ["sliding_attention"] * 3 + ["full_attention"]
PUBLISHED = {
    "first_k_dense_replace": 1, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 6144, "intermediate_size": 18432,
    "layer_types": TYPES * 12, "max_position_embeddings": 262144,
    "mlp_layer_types": ["dense"] + ["sparse"] * 47,
    "model_type": "exaone_moe", "moe_intermediate_size": 2048,
    "mtp_layer_types": ["full_attention"], "mtp_sliding_windows": [0],
    "n_group": 1, "norm_topk_prob": True, "num_attention_heads": 64,
    "num_experts": 128, "num_experts_per_tok": 8, "num_hidden_layers": 48,
    "num_key_value_heads": 8, "num_nextn_predict_layers": 1,
    "num_shared_experts": 1, "rms_norm_eps": 1e-05,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "sliding_window": 128, "sliding_window_pattern": "LLLG",
    "sliding_windows": [128, 128, 128, 0] * 12,
    "tie_word_embeddings": False, "topk_group": 1, "vocab_size": 153600,
}
REDUCED = {"num_hidden_layers": 5, "layer_types": (TYPES * 2)[:5],
           "mlp_layer_types": ["dense"] + ["sparse"] * 4,
           "sliding_windows": [128, 128, 128, 0, 128],
           "num_experts": 16, "vocab_size": 19200}


def _config() -> dict:
    with open(os.path.join(REPO, "benchmark", "configs",
                           NAME + ".json")) as fh:
        return json.load(fh)


def test_the_exaone_configuration_holds_the_source_s_keys():
    """Every key of the catalog row's ``config`` at the top level under
    the same name and at the published value, but the depth and the
    three lists cut to it, the experts held and the vocabulary's slice,
    which stand as run with ``published`` beside them: no width is
    cut."""
    conf = next(c for c in BENCH["configs"] if c["name"] == NAME)
    config = _in_the_layout(conf, REPO)
    assert conf["reduced"] == list(REDUCED) == config["reduced"]
    for key, value in PUBLISHED.items():
        assert key in config, key
        assert _same(config[key], REDUCED.get(key, value)), key
    assert _same(config["published"], {k: PUBLISHED[k] for k in REDUCED})
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.isfile(catalog):  # the literal above is the row's config
        with open(catalog) as fh:
            row = next(r for r in map(json.loads, fh)
                       if r["name"] == "K-EXAONE-236B-A23B")
        assert _same(row["config"], PUBLISHED)
        assert conf["source"] == row["source_url"] == config["source"]
    assert not [k for k in REDUCED if k != "vocab_size" and k.endswith(
        ("_size", "_dim", "_rank", "_head", "_state", "_expand", "_per_tok",
         "_window"))]
    # the floors: the dense layer and a whole period of four after it,
    # 8 or more routed experts, an eighth of the vocabulary
    assert config["num_hidden_layers"] == 1 + 4
    assert config["num_experts"] >= 8
    assert config["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert "model" not in config and config["first_routed_expert"] == 0
    for said in ("8 chips", "experts 0 to 15", "rows 0 to 19,199",
                 "shared expert", "all-reduce", "5 of 48 layers"):
        assert said in config["deployment"], said
    assumed = " ".join(config["assumed"])
    for said in ("EXAONE 4.0", "RMSNorm of their own", "on its OUTPUT",
                 "sliding_attention layers only", "DeepSeek-V3",
                 "never enters a gate", "added ungated",
                 "counts the query's own position", "numbered after 25",
                 "rotate-half"):
        assert said in assumed, said
    assert {"num_nextn_predict_layers", "max_position_embeddings",
            "serving_prefix_cache", "serving_speculative",
            "weights"} <= set(config["departures"])
    assert "drafting head" in config["departures"]["num_nextn_predict_layers"]


def test_the_exaone_cell_is_what_the_issue_sized():
    """What the program is told (a dense layer before the period w w f
    w, the router's score, bias and scale, the two norms) and the load:
    the other longmix cell's, so that the two differ in the block
    alone."""
    cell = cellspec.load_cell(CELL)
    model = cell.config["model"]
    assert model["layer_pattern"] == ["window", "window", "attention",
                                      "window"]
    assert (model["n_layers"], model["dense_layers"], model["dense_ff"]) == (
        5, 1, 18432)
    assert (model["vocab"], model["d_model"]) == (19200, 6144)
    assert (model["n_heads"], model["n_kv_heads"], model["head_dim"]) == (
        64, 8, 128)
    assert (model["experts"], model["experts_held"], model["expert_first"],
            model["expert_top_k"], model["d_ff"], model["shared_ff"]) == (
                128, 16, 0, 8, 2048, 2048)
    assert (model["router_score"], model["router_bias"],
            model["router_scale"]) == ("sigmoid", True, 2.5)
    assert model["qk_norm"] is True and model["norm_after"] is True
    assert model["attention_window"] == 128
    assert model["rope_theta"] == 1e6 and model["rotary"] is False
    assert model["untied_head"] is True and model["norm_eps"] == 1e-5
    payload = cell.config["payload"]
    assert (payload["seq"], payload["serving_slots"],
            payload["serving_page_size"], payload["serving_pages"]) == (
                8192, 64, 128, 2816)
    assert payload["serving_prefix_cache"] is False
    assert payload["serving_prefill_chunk"] == cell.load["prefill_chunk"] \
        == 256
    assert payload["serving_window"] == cell.load["decode_window"] == 32
    other = cellspec.load_cell("smallthinker-21ba3b.longmix")
    assert payload == other.config["payload"]
    assert {k: v for k, v in cell.load.items() if k != "check"} \
        == {k: v for k, v in other.load.items() if k != "check"}
    assert cell.traffic == other.traffic
    assert (cell.load["loop"], cell.load["clients"],
            cell.load["requests_per_client"], cell.load["ramp_s"],
            cell.load["drain_s"]) == ("closed", 64, 16, 24.0, 4.0)
    # the limits, from readings (PERF.md section 6, PR 43): the mean's
    # between the program's largest and the int8 control's smallest; the
    # extreme's at twice the program's largest or more
    assert cell.load["check"]["requests"] == 16
    limits = cell.load["check"]["limits"]
    assert set(limits) == {"token_gap_max", "token_gap_mean"}
    assert limits["token_gap_max"] >= 2 * 1.555
    assert 2 * 0.00539 < limits["token_gap_mean"] < 0.02844 / 2
    entry = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (entry["chips"], entry["traffic"]) == (1, "longmix")
    plan = schedule.build(cell.traffic, cell.load, 1, 48.0, model["vocab"])
    totals = [r["prompt"] + r["n_new"] for r in plan["requests"]]
    assert max(totals) <= payload["seq"]
    assert sum(t > 4096 for t in totals) > len(totals) // 2
    # it reports everything the first cell does and one metric of its
    # own, listed for this cell alone
    names = {m["name"] for m in cell.per_layer}
    first = {m["name"] for m in
             cellspec.load_cell("starcoder2-3b.batchgen").per_layer}
    assert names - first == {NEW} and first <= names
    assert next(m for m in BENCH["per_layer"] if m["name"] == NEW) == {
        "name": NEW, "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "kernels", "moves": "out_tok_s",
        "workloads": [CELL]}
    assert NEW in metrics.readers()
    from kvedge_tpu.config.runtime_config import (
        RuntimeConfig, RuntimeConfigError,
    )

    document = cellspec.runtime_document(cell, "<dir>", "tpu")
    parsed = RuntimeConfig.from_mapping(document)
    assert parsed.model.layer_pattern == ("window", "window", "attention",
                                          "window")
    assert (parsed.model.dense_layers, parsed.model.dense_ff) == (1, 18432)
    assert parsed.model.router_score == "sigmoid"
    assert RuntimeConfig.parse(parsed.to_toml()) == parsed
    for key, value in (("serving_prefix_cache", True),
                       ("serving_speculative", 3),
                       ("serving_kv_dtype", "int8")):
        with pytest.raises(RuntimeConfigError, match=key):
            RuntimeConfig.from_mapping(cellspec.runtime_document(
                cell, "<dir>", "tpu", {key: value}))


def _sizes_doubled(config: dict) -> dict:
    """The file with every size twice as large: widths, heads, experts,
    vocabulary, the window; the depth, the lists' kinds, the counts of
    dense layers, shared experts and groups and the flags as they are."""
    keep = {"num_hidden_layers", "first_k_dense_replace", "n_group",
            "topk_group", "num_shared_experts", "num_nextn_predict_layers",
            "first_routed_expert"}
    out = {k: 2 * v if type(v) in (int, float) and k not in keep else v
           for k, v in config.items()}
    out["sliding_windows"] = [2 * w for w in config["sliding_windows"]]
    out["published"] = {**config["published"],
                        "num_experts": 2 * config["published"]["num_experts"]}
    out["rope_parameters"] = {
        **config["rope_parameters"],
        "rope_theta": 2 * config["rope_parameters"]["rope_theta"]}
    out["payload"] = {**config["payload"], "seq": 2 * config["payload"]["seq"]}
    return out


def test_a_file_stating_every_size_twice_as_large_runs_them_so():
    """What the file states is what runs: the server's ``model`` is
    ``model_of`` of the file, each of its sizes is a value the file
    states, and a file that stated every size twice as large would run
    every size twice as large (nothing in ``model_of`` is a size of its
    own)."""
    cell = cellspec.load_cell(CELL)
    config = _config()
    model = cell.config.pop("model")
    assert cell.config == config
    assert _same(model, cell.reference.model_of(copy.deepcopy(config)))
    stated = {v for group in (config, config["payload"], config["published"])
              for v in group.values() if type(v) is int}
    sizes = {k: v for k, v in model.items() if type(v) is int}
    assert set(sizes) == {
        "vocab", "d_model", "n_heads", "n_kv_heads", "head_dim", "n_layers",
        "dense_layers", "dense_ff", "attention_window", "experts",
        "experts_held", "expert_first", "expert_top_k", "d_ff", "shared_ff",
        "seq"}
    assert all(v in stated for v in sizes.values())
    twice = cell.reference.model_of(_sizes_doubled(config))
    same = {"n_layers", "dense_layers", "expert_first"}
    for key, value in sizes.items():
        assert twice[key] == (value if key in same else 2 * value), key
    assert twice["rope_theta"] == 2 * model["rope_theta"]
    assert twice["router_scale"] == 2 * model["router_scale"]
    assert twice["layer_pattern"] == model["layer_pattern"]


@pytest.mark.parametrize("key, value", [
    ("scoring_func", "softmax"), ("n_group", 8), ("topk_group", 4),
    ("norm_topk_prob", False), ("tie_word_embeddings", True),
    ("hidden_act", "gelu"), ("num_shared_experts", 2),
    ("rope_parameters", {"rope_theta": 1000000, "rope_type": "yarn"}),
    ("mlp_layer_types", ["sparse"] * 5),
    ("sliding_windows", [128, 128, 128, 128, 128]),
    ("layer_types", ["full_attention"] + (TYPES * 2)[1:5]),
])
def test_a_key_the_block_is_not_written_for_is_refused(key, value):
    block = cellspec.load_cell(CELL).reference
    said = {"rope_parameters": "rope_type", "mlp_layer_types": "layer_types",
            "sliding_windows": "layer_types"}.get(key, key)
    with pytest.raises(SystemExit, match=said):
        block.model_of({**_config(), key: value})


def test_the_exaone_block_s_counts_never_pass_a_count_from_the_shapes():
    """``decode_step`` at the committed cut, against ISSUE 43's
    arithmetic by hand: every matrix held here once in bf16, the
    routers and their biases in float32, the full layer's live keys and
    values once and the window layers' at the least the rows' windows
    can hold; and for every split of the live positions over the rows,
    no more than what the rows' shapes give."""
    cell = cellspec.load_cell(CELL)
    model, block = cell.config["model"], cell.reference
    d = 6144
    attention = d * (64 + 16) * 128 + 64 * 128 * d
    expert = 3 * d * 2048
    dense = 3 * d * 18432
    assert block.attention_params(model) == attention == 113_246_208
    assert block.expert_params(model) == block.shared_params(model) \
        == expert == 37_748_736
    assert block.dense_params(model) == dense == 339_738_624
    assert block.attention_layers(model) == (1, 4)
    assert block.kv_bytes_per_token(model) == 4096
    assert block.page_bytes(model, 128) == 524_288
    sparse = attention + 17 * expert + d * 128
    assert 755.7e6 < sparse < 755.9e6      # 1.51 GB in bf16
    assert 452.9e6 < attention + dense < 453.1e6
    tree = 4 * sparse + attention + dense + 2 * 19200 * d
    assert 3.71e9 < tree < 3.72e9          # 7.43 GB in bf16
    rows, live = 54.0, 54 * 4800.0
    step = block.decode_step(model, rows, live)
    weights = (2 * (5 * attention + dense + 4 * 17 * expert + 19200 * d)
               + 4 * 4 * (d + 1) * 128)
    held = live * 128 / 8192
    want = weights + 4096 * ((live + rows) + 4 * (held + rows))
    assert step["bytes"] == pytest.approx(want)
    assert 8.3e9 < step["bytes"] < 8.7e9
    assert 0.55 < 2 * 4 * 16 * expert / step["bytes"] < 0.60
    # bound by bytes: its operations need a tenth of the time its bytes do
    assert step["flops"] / 197e12 < 0.1 * step["bytes"] / 819e9
    for split in ([4800.0] * 54, [8192.0] * 31 + [228.0] * 23,
                  [1536.0] * 27 + [8064.0] * 27):
        assert sum(split) == pytest.approx(live, rel=0.02)
        by_shape = weights + 4096 * (
            (sum(split) + rows)
            + 4 * (sum(min(n, 128.0) for n in split) + rows))
        got = block.decode_step(model, rows, sum(split))["bytes"]
        assert got <= by_shape * (1 + 1e-9)
    short = dict(model, seq=128)
    assert block.window_tokens(short, 4.0, 400.0) == 400.0
    assert block.window_tokens(model, 4.0, 8192.0) == 128.0


# The block at a probe size: a dense layer and two periods of w w f w, 4
# of 8 gated experts held, 3 a token by sigmoid scores plus a bias, a
# shared expert, a window of 32 under contexts to 184, a head of its own.
PROBE6_CONFIG = {
    "reference": "exaone_moe",
    "source": "none: a probe size for the CPU tests",
    "reduced": ["num_experts"], "published": {"num_experts": 8},
    "first_routed_expert": 4,
    "deployment": "one of two virtual chips that share a layer: experts "
                  "4 to 7 of 8",
    "first_k_dense_replace": 1, "head_dim": 16, "hidden_act": "silu",
    "hidden_size": 32, "intermediate_size": 64,
    "layer_types": (TYPES * 3)[:9], "max_position_embeddings": 512,
    "mlp_layer_types": ["dense"] + ["sparse"] * 8,
    "model_type": "exaone_moe", "moe_intermediate_size": 16, "n_group": 1,
    "norm_topk_prob": True, "num_attention_heads": 4, "num_experts": 4,
    "num_experts_per_tok": 3, "num_hidden_layers": 9,
    "num_key_value_heads": 2, "num_nextn_predict_layers": 1,
    "num_shared_experts": 1, "rms_norm_eps": 1e-5,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "sliding_window": 32, "sliding_windows": ([32, 32, 32, 0] * 3)[:9],
    "tie_word_embeddings": False, "topk_group": 1, "vocab_size": 256,
    "mesh": {"axes": {"data": 1}},
    "payload": {"seq": 256, "serving_slots": 4, "serving_page_size": 16,
                "serving_pages": 96, "serving_window": 8,
                "serving_prefill_chunk": 32,
                "serving_prefix_cache": False,
                "serving_prefix_persist": False},
}


@pytest.fixture(scope="module")
def probe6(tmp_path_factory):
    """The harness tests' probe checkout with one more configuration and
    cell, added as files and entries: this block under the closed-loop
    probe mix."""
    root = probe_tree(str(tmp_path_factory.mktemp("checkout")))
    bench = os.path.join(root, "benchmark")
    with open(os.path.join(bench, "configs", "probe6.json"), "w") as fh:
        json.dump(PROBE6_CONFIG, fh, indent=1)
    with open(os.path.join(bench, "cells", "probe.tinyclosed.json")) as fh:
        load = json.load(fh)
    load["prefill_chunk"] = 32
    # the program computes in bf16 here as on the chip, against float32
    load["check"]["limits"] = {"token_gap_max": 1.0, "token_gap_mean": 0.06}
    with open(os.path.join(bench, "cells", "probe6.tinyclosed.json"),
              "w") as fh:
        json.dump(load, fh)
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        doc = json.load(fh)
    doc["configs"].append({
        "name": "probe6", "source": PROBE6_CONFIG["source"],
        "file": "benchmark/configs/probe6.json",
        "reduced": ["num_experts"], "why": "probe of the K-EXAONE block"})
    doc["workloads"].append({"name": "probe6.tinyclosed", "config": "probe6",
                             "traffic": "tinyclosed", "chips": 1,
                             "why": "probe"})
    for metric in doc["per_layer"]:
        if CELL in metric.get("workloads", ()):
            metric["workloads"].append("probe6.tinyclosed")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as fh:
        json.dump(doc, fh)
    return root


def test_the_exaone_block_runs_whole_on_the_cpu(probe6, tmp_path):
    """The server starts from ``model_of``'s ``[model]`` (the dense
    layer, the pattern, the router, the norms, a share of the experts),
    serves a closed loop whose contexts pass the window several times
    over, and is correct by the committed reference's float32 pass over
    the same share; the new reader needs a trace and is left out of the
    line, the others read as in any cell."""
    cell, line, said = _measure(probe6, 43, name="probe6.tinyclosed",
                                layers=True, out_dir=str(tmp_path))
    assert cell.reference.__file__.endswith("exaone_moe.py")
    model = cell.config["model"]
    assert (model["dense_layers"], model["experts"], model["experts_held"],
            model["expert_first"]) == (1, 8, 4, 4)
    assert line["failed"] == 0 and line["attempted"] >= 3
    assert line["correct"], said
    assert any("token_gap_mean" in s and s.endswith("ok") for s in said)
    got = line["metrics"]
    assert NEW not in got
    assert 0.0 < got["pool_live_pct.closed"]["value"] <= 100.0
    assert got["lock_unnamed_pct.closed"]["value"] == pytest.approx(
        0.0, abs=0.5)
    assert got["decode_step_wall_ms.closed"]["value"] > 0.0
    other = cellspec.load_cell("probe.tinyclosed", repo=probe6)
    assert NEW not in {m["name"] for m in other.per_layer}


def _capture(blocked: bool, steps: int = 4) -> list:
    """A hand-made capture of this cell's decode programs: four
    executions of ``steps`` steps, the first and the last cut short; a
    step is the leading layer's ``paged_attention`` call (10 us), three
    more in the period and, for the full layer, a ``paged_attention_
    blocked`` call (100 us; or, on a program without the blocked form,
    a gather's fusion), each followed by a fusion of 50 us."""
    device, events, t = "/device:TPU:0", [], 0.0
    full = ("paged_attention_blocked.9 bf16[64,64,1024]" if blocked
            else "fusion.77 bf16[64,8,8,1,8192]")
    body = (["paged_attention.4 bf16[64,64,1024]"] * 3 + [full]
            + ["paged_attention.5 bf16[64,64,1024]"])
    for i in range(4):
        start = t
        for _ in range(steps // 2 if i in (0, 3) else steps):
            for op in body:
                dur = 100e-6 if op == full else 10e-6
                events.append({"device": device, "line": trace.OPS_LINE,
                               "name": op, "start": t, "dur": dur})
                events.append({"device": device, "line": trace.OPS_LINE,
                               "name": "fusion.1", "start": t + dur,
                               "dur": 50e-6})
                t += dur + 50e-6
        events.append({"device": device, "line": trace.MODULES_LINE,
                       "name": "jit__paged_decode_window_capped_impl(7)",
                       "start": start, "dur": t - start})
        t += 0.002
    return events


def test_the_new_reader_reads_both_forms_and_nothing_without_a_blocked_call():
    """On a capture with a blocked call: the least time at 819 GB/s for
    the pages of both pools (the server's page-steps, a step's mean,
    over the two whole programs' eight steps, times half a MiB a page
    and layer: one full layer, four window layers) over the time of
    both forms' calls in those programs. On a program whose full layer
    takes the gather, on the parent's counters and on an untraced run:
    nothing, and no error."""
    read = metrics.readers()[NEW]
    cell = cellspec.load_cell(CELL)

    def ctx_of(events, **more):
        old = dict(_stats(640, 160), pages_live_steps_total=0,
                   window_pages_live_steps_total=0)
        new = dict(_stats(760, 190), pages_live_steps_total=120 * 2000,
                   window_pages_live_steps_total=120 * 150)
        return {"events": events, "cell": cell, "records": [],
                "trace_span": trace.span(events) if events else None,
                "peak": {"hbm_bytes_per_s": 819e9},
                "stats_start": old, "stats_end": new, **more}

    ctx = ctx_of(_capture(True))
    # 8 steps in the whole programs: 2,000 full pages and 150 window
    # pages a step; the calls took 8 x (4 x 10 + 100) us
    nbytes = 8 * 524288 * (1 * 2000 + 4 * 150)
    want = 100.0 * nbytes / 819e9 / (8 * 140e-6)
    assert read(ctx) == pytest.approx(want, rel=1e-6)
    assert metrics.readers()["paged_attention_roofline_pct.closed"](
        ctx_of(_capture(True))) == pytest.approx(want, rel=1e-6)
    assert read(ctx_of(_capture(False))) is None
    assert read(ctx_of(None)) is None
    assert read({**ctx_of(_capture(True)), "stats_start": {},
                 "stats_end": {}}) is None
