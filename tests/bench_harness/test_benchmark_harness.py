"""The benchmark's own harness (``benchmark/``, ``BENCHMARK.json``).

Everything here runs on the CPU at a probe size or on data: the
schedule, the trace reducers on a small recorded trace, the roofline
counts from shapes, and one whole run (server start, warm-up, ramp,
window, drain, check) by calling the harness's functions. The command
itself refuses a CPU backend, and a test shows that it does.
"""

import hashlib
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from benchmark import cellspec, check, metrics, reduce, roofline
from benchmark import schedule, trace

REPO = cellspec.REPO
HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(REPO, "BENCHMARK.json")) as _fh:
    BENCH = json.load(_fh)
CELLS = [w["name"] for w in BENCH["workloads"]]

# A configuration's file in the layout ``cellspec`` states: the source's
# keys under their published names (the block's file makes the program's
# ``model`` of them), ``published`` for what is reduced, the program's own
# ``mesh`` and ``payload``, ``notes`` for comments.
PROBE_CONFIG = {
    "reference": "starcoder2",
    "source": "none: a probe size for the CPU tests",
    "reduced": [],
    "published": {},
    "deployment": "one virtual CPU device holds every layer whole",
    "hidden_size": 64,
    "intermediate_size": 128,
    "num_attention_heads": 4,
    "num_key_value_heads": 2,
    "num_hidden_layers": 2,
    "vocab_size": 256,
    "mesh": {"axes": {"data": 1}},
    "payload": {"seq": 256, "serving_slots": 4, "serving_page_size": 16,
                "serving_pages": 96, "serving_window": 8,
                "serving_prefix_persist": False},
    "notes": {
        "payload.serving_prefix_persist":
            "As in the committed configuration: the default dumps the "
            "prefix cache every 30 s through small programs of new shapes. "
            "A run alone opens its window 15 s after the server started; "
            "beside five busy test workers set-up takes over 30 s and the "
            "dump fell inside the 4 s window (window_compiles 4 to 9)."},
}
# A configuration of another block (the repo's expert feed-forward), which
# ``references/starcoder2.py`` cannot compute: its own file beside it. It
# is written as the catalog's many-expert rows will be: the key that counts
# the experts holds how many are held here and is in ``reduced``, the
# published count stands under ``published``, ``deployment`` names the
# chips that share a layer.
PROBE2_CONFIG = {
    **PROBE_CONFIG,
    "reference": "probe2",
    "reduced": ["num_local_experts"],
    "published": {"num_local_experts": 8},
    "deployment": "2 chips share each layer's experts, and this is one of "
                  "them: 4 of the 8 experts, every other part of a layer "
                  "whole (the probe's program routes over the 4 it holds; "
                  "a router at the published width over a chip's share is "
                  "the PR's that adds such a block)",
    "num_local_experts": 4,
    "num_experts_per_tok": 2,
}
PROBE_METRIC = '''
"""A per-layer metric a later PR might add: requests the window saw."""
NAMES = ("probe_requests",)


def read(ctx):
    return float(len(ctx["window"]))
'''


def probe_tree(root: str) -> str:
    """A checkout with two new configurations (one of the committed
    block, one of a block with a reference file of its own), mixes,
    cells and a per-layer metric, added as files and entries only."""
    bench = os.path.join(root, "benchmark")
    shutil.copytree(os.path.join(REPO, "benchmark"), bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    for name, config in (("probe", PROBE_CONFIG), ("probe2", PROBE2_CONFIG)):
        with open(os.path.join(bench, "configs", name + ".json"),
                  "w") as fh:
            json.dump(config, fh, indent=1)
    shutil.copy(os.path.join(HERE, "probe2_reference.py"),
                os.path.join(bench, "references", "probe2.py"))
    with open(os.path.join(bench, "metrics", "probe_requests.py"),
              "w") as fh:
        fh.write(PROBE_METRIC)
    mix = {"prompt": {"dist": "lognormal", "median": 48, "sigma": 0.5,
                      "min": 32, "max": 96, "multiple": 32},
           "output": {"dist": "uniform", "min": 16, "max": 40},
           "pairing_seed": 0}
    with open(os.path.join(bench, "traffic", "tiny.json"), "w") as fh:
        json.dump(mix, fh)
    load = {"loop": "open", "rate_rps": 3.0, "ramp_s": 1.0,
            "drain_s": 30.0, "decode_window": 8,
            "programs": {"decode": "paged_decode_window",
                         "prefill": "paged_prefill"},
            "check": {"requests": 3, "limits": {"token_gap_max": 0.5,
                                                "token_gap_mean": 0.01}}}
    for cell in ("probe.tiny", "probe2.tiny"):
        with open(os.path.join(bench, "cells", cell + ".json"), "w") as fh:
            json.dump(load, fh)
    closed = {"prompt": {"dist": "uniform", "min": 32, "max": 64,
                         "multiple": 32},
              "output": {"dist": "uniform", "min": 40, "max": 120},
              "pairing_seed": 0}
    with open(os.path.join(bench, "traffic", "tinyclosed.json"), "w") as fh:
        json.dump(closed, fh)
    load = dict(load, loop="closed", clients=3, requests_per_client=40,
                drain_s=2.0)
    del load["rate_rps"]
    with open(os.path.join(bench, "cells", "probe.tinyclosed.json"),
              "w") as fh:
        json.dump(load, fh)
    doc = json.loads(json.dumps(BENCH))
    doc["workloads"].append({"name": "probe.tinyclosed", "config": "probe",
                             "traffic": "tinyclosed", "chips": 1,
                             "why": "probe"})
    doc["configs"].append({
        "name": "probe", "source": PROBE_CONFIG["source"],
        "file": "benchmark/configs/probe.json",
        "reduced": PROBE_CONFIG["reduced"], "why": "probe"})
    doc["workloads"].append({"name": "probe.tiny", "config": "probe",
                             "traffic": "tiny", "chips": 1, "why": "probe"})
    doc["configs"].append({
        "name": "probe2", "source": PROBE2_CONFIG["source"],
        "file": "benchmark/configs/probe2.json",
        "reduced": PROBE2_CONFIG["reduced"],
        "why": "probe of another block, a chip's share of its experts"})
    doc["workloads"].append({"name": "probe2.tiny", "config": "probe2",
                             "traffic": "tiny", "chips": 1, "why": "probe"})
    chat = ["probe.tiny", "probe2.tiny"]
    for name in ("ttft_p90_ms", "tpot_p50_ms"):  # what a chat cell reports
        doc["end_to_end"].append({
            "name": name, "unit": "ms", "better": "lower", "bound": 0.1,
            "source": "host_clock", "workloads": chat})
    doc["per_layer"].append({
        "name": "probe_requests", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "load generator",
        "moves": "ttft_p90_ms", "workloads": chat})
    doc["per_layer"].append({
        "name": "queue_wait_ms", "unit": "ms", "better": "lower",
        "source": "program_counter", "layer": "admission and batching",
        "moves": "ttft_p90_ms"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as fh:
        json.dump(doc, fh)
    return root


# ---- the schedule --------------------------------------------------------


@pytest.mark.parametrize("name", CELLS)
def test_two_seeds_offer_the_same_work(name):
    cell = cellspec.load_cell(name)
    vocab = cell.config["model"]["vocab"]
    a = schedule.build(cell.traffic, cell.load, 3, BENCH["run_seconds"],
                       vocab)
    b = schedule.build(cell.traffic, cell.load, 2**31 + 12345,
                       BENCH["run_seconds"], vocab)
    lengths = [sorted((r["prompt"], r["n_new"]) for r in p["requests"])
               for p in (a, b)]
    assert lengths[0] == lengths[1]
    assert a["work"] == b["work"] and a["work"]["requests"] >= 20
    order = [[(r["prompt"], r["n_new"]) for r in p["requests"]]
             for p in (a, b)]
    assert order[0] != order[1]
    if a["loop"] == "open":
        dues = [[r["due"] for r in p["requests"]] for p in (a, b)]
        assert dues[0] != dues[1]
        assert all(-cell.load["ramp_s"] <= d < BENCH["run_seconds"]
                   for d in dues[0])
        inside = [d for d in dues[0] if d >= 0]
        assert len(inside) == round(cell.load["rate_rps"]
                                    * BENCH["run_seconds"])


@pytest.mark.parametrize("name", CELLS)
def test_a_cells_lengths_fit_its_configuration(name):
    cell = cellspec.load_cell(name)
    plan = schedule.build(cell.traffic, cell.load, 1, BENCH["run_seconds"],
                          cell.config["model"]["vocab"])
    asked = plan["requests"] + [
        {"prompt": w["prompt"], "n_new": w["n_new"]}
        for w in schedule.warmup_requests(cell.traffic, cell.load)]
    assert max(r["prompt"] + r["n_new"] for r in asked) \
        <= cell.config["payload"]["seq"]
    multiple = cell.traffic["prompt"]["multiple"]
    assert all(r["prompt"] % multiple == 0 for r in plan["requests"])


def test_prompts_repeat_per_seed_and_never_share_a_first_token():
    a = schedule.prompt_tokens(7, 3, 64, 49152)
    assert a == schedule.prompt_tokens(7, 3, 64, 49152)
    assert a != schedule.prompt_tokens(8, 3, 64, 49152)
    firsts = [schedule.prompt_tokens(7, i, 32, 49152)[0]
              for i in range(-20, 400)]
    assert len(set(firsts)) == len(firsts)
    assert all(0 <= t < 49152 for t in a)


def test_the_load_generator_never_imports_jax():
    code = ("import sys, benchmark.loadgen, benchmark.schedule; "
            "sys.exit(1 if any(m == 'jax' or m.startswith('jax.') "
            "for m in sys.modules) else 0)")
    done = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                          timeout=120)
    assert done.returncode == 0


# ---- reductions ----------------------------------------------------------


def _record(due, first, last, n, prompt=64, error=None):
    return {"index": 0, "client": -1, "prompt": prompt, "n_new": n,
            "due": due, "sent": due + 0.001, "first": first, "last": last,
            "tokens": list(range(n)) if first is not None else [],
            "error": error,
            "bursts": ([[first, 1], [last, n - 1]]
                       if first is not None else [])}


def test_end_to_end_reductions():
    records = [_record(float(i), i + 0.5 + 0.01 * i, i + 2.5 + 0.01 * i, 21)
               for i in range(10)]
    records.append(_record(-3.0, -2.0, -1.0, 21))    # the ramp's
    records.append(_record(4.0, None, None, 5, error="HTTP 503"))
    window = reduce.window_requests(records, 10.0)
    assert len(window) == 11 and len(reduce.failed(window)) == 1
    ttft = reduce.end_to_end("ttft_p90_ms", records, 10.0)
    assert ttft == pytest.approx(500 + 10 * 8.1)
    assert reduce.end_to_end("tpot_p50_ms", records, 10.0) \
        == pytest.approx(100.0)
    # 10 first tokens and 8 tails of 20 land inside [0, 10) whole; credited
    # over the 2 s since each stream's first delivery, the two tails that
    # end after the window's close leave 14.2 and 4.1 of their 20 inside
    assert reduce.tokens_delivered_whole(records, 10.0) == 10 + 8 * 20
    assert reduce.end_to_end("out_tok_s", records, 10.0) \
        == pytest.approx((10 + 8 * 20 + 14.2 + 4.1) / 10.0)
    assert reduce.percentile([1, 2, 3, 4], 50) == 2.5


def test_a_delivery_is_credited_over_the_time_it_was_produced_in():
    """A stream under way when the window opens and still under way when
    it closes: 64 tokens every 2 s. The first delivery is credited whole
    where it falls; every later one evenly over the 2 s before it, by
    the part of them inside the window."""
    stream = _record(-5.0, -3.0, 13.0, 9 * 64)
    stream["bursts"] = [[-3.0 + 2.0 * k, 64] for k in range(9)]
    # deliveries at -3, -1, 1, ..., 13; window [0, 10): half of the one at
    # 1, all of 3, 5, 7, 9, half of the one at 11
    assert reduce.tokens_in_window([stream], 10.0) == pytest.approx(5 * 64)
    assert reduce.tokens_delivered_whole([stream], 10.0) == 5 * 64
    # lines of one delivery arrive a few milliseconds apart: one delivery
    stream["bursts"] = [[1.0 + 0.002 * k, 1] for k in range(64)]
    assert reduce.deliveries(stream) == [(pytest.approx(1.126), 64)]
    # a stream that starts inside the window: its first delivery whole
    late = _record(4.0, 6.0, 8.0, 128)
    late["bursts"] = [[6.0, 64], [8.0, 64]]
    assert reduce.tokens_in_window([late], 7.0) == pytest.approx(64 + 32)


def test_a_chain_that_ends_inside_the_window_is_a_dry_client():
    """Three clients with chains of two: one ends its second request at
    7 s of a 10 s window and stands idle after, one is cut at the
    window's end, one's second request failed. Open-loop requests have
    no client and never count."""
    plan = [{"client": c, "index": 2 * c + k} for c in range(3)
            for k in range(2)] + [{"client": -1, "index": 9}]

    def rec(client, due, last, **kw):
        return dict(_record(due, due + 0.5, last, 21), client=client, **kw)

    records = [rec(0, -2.0, 3.0), rec(0, 3.0, 7.0),
               rec(1, -2.0, 4.0), rec(1, 4.0, 10.5, error="cut", cut=True),
               rec(2, -2.0, 5.0), rec(2, 5.0, 6.0, error="HTTP 503"),
               rec(-1, 1.0, 2.0)]
    assert reduce.clients_dry(plan, records, 10.0) \
        == {"count": 1, "first_s": 7.0}
    # a chain that ends after the window closed kept its row to the end
    assert reduce.clients_dry(plan, records, 6.5) \
        == {"count": 0, "first_s": None}
    # two dry: the first of them is reported
    records[3] = rec(1, 4.0, 6.0)
    assert reduce.clients_dry(plan, records, 10.0) \
        == {"count": 2, "first_s": 6.0}
    cell = cellspec.load_cell(CELLS[0])
    assert cell.load["requests_per_client"] == 16


# ---- the trace reducers, on a small recorded trace -----------------------


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "trace_recorded.json")) as fh:
        return json.load(fh)


def test_trace_idle_share_and_busy_time(recorded):
    events = recorded["events"]
    lo, hi = trace.span(events)
    busy = trace.busy_seconds(events)
    assert 0 < busy <= hi - lo
    assert busy == pytest.approx(recorded["expect"]["busy_s"], rel=1e-6)
    gaps = trace.idle_gaps(events)
    first = trace.devices(events)[0]
    one = [e for e in events if e["device"] == first]
    lo1, hi1 = trace.span(one)
    assert sum(b - a for a, b in gaps) == pytest.approx(
        (hi1 - lo1) - trace.busy_seconds(one), rel=1e-6)


def _stats(steps: int, programs: int) -> dict:
    """A ``stats()`` snapshot as far as the step count reads it."""
    return {"decode_steps_total": steps,
            "phase_ms": {"loop/harvest_wait": [programs, 0.0]}}


def test_trace_named_programs_time_and_steps(recorded):
    events = recorded["events"]
    want = recorded["expect"]
    decode = trace.program_events(events, "paged_decode_window")
    assert len(decode) == want["decode_programs"]
    assert trace.program_seconds(events, "paged_decode_window") \
        == pytest.approx(want["decode_s"], rel=1e-6)
    names = [name for name, _ in trace.top_ops(events, 5)]
    assert names == want["top_ops"]
    # the recording was trimmed to the first three steps of its one decode
    # window: an execution the capture's end cut short. Its operations
    # still say three steps of 16 layers, but it is no whole program, and
    # a capture that holds none has no step time to give.
    most = max(_op_counts(events, decode[0]).values())
    assert [round(most / want["layers"])] == want["decode_steps"]
    assert trace.whole_programs(events, "paged_decode_window") == []
    cell = cellspec.load_cell(CELLS[0])
    ctx = {"events": events, "cell": cell, "records": [],
           "trace_span": trace.span(events),
           "stats_start": _stats(640, 10), "stats_end": _stats(2560, 40)}
    assert trace.decode_work(ctx)["steps"] == 0
    found = metrics.readers()
    assert found["decode_step_dev_ms.closed"](ctx) is None
    assert found["decode_roofline_pct.closed"](ctx) is None


def _op_counts(events: list, program: dict) -> dict:
    counts: dict = {}
    for e in trace.ops_inside(events, program):
        counts[e["name"]] = counts.get(e["name"], 0) + 1
    return counts


def two_kinds_of_layer_trace(steps=(4, 4, 4), step_s=0.010) -> list:
    """A hand-made capture of a model whose step is one dense layer and
    three expert layers, each of which runs ``expert_ffn`` for two
    experts: the most frequent operation runs six times a step, not once
    a layer. Decode programs of ``steps`` steps each, the first under
    way when the capture began and the last when it ended (both cut
    short, to half their steps), a prefill between two of them."""
    device, events, t = "/device:TPU:0", [], 0.0
    op_s = step_s / 14

    def program(name, n_steps, body):
        nonlocal t
        start = t
        for _ in range(n_steps):
            for op in body:
                events.append({"device": device, "line": trace.OPS_LINE,
                               "name": op, "start": t, "dur": op_s * 0.9})
                t += op_s
        events.append({"device": device, "line": trace.MODULES_LINE,
                       "name": name, "start": start, "dur": t - start})
        t += 0.002  # the host between two programs

    step = (["attention", "dense_ffn"]
            + ["attention", "router", "expert_ffn", "expert_ffn"] * 3)
    assert len(step) == 14
    last = len(steps) - 1
    for i, n in enumerate(steps):
        cut = n // 2 if i in (0, last) else n
        program("jit__paged_decode_window_capped_impl(7)", cut, step)
        if i == 0:
            program("jit__paged_prefill_impl(9)", 1, ["attention"] * 4)
    # a cut execution starts with the capture or ends with it
    first = min(e["start"] for e in events)
    for e in events:
        e["start"] -= first
    return events


def test_steps_are_the_servers_count_not_an_operations_frequency():
    """Two kinds of layer in a step: the old count (the most frequent
    operation's runs over the layers) is wrong, the server's count over
    the executions the capture holds whole is right."""
    events = two_kinds_of_layer_trace(steps=(4, 4, 4, 4))
    decode = trace.program_events(events, "paged_decode_window")
    assert len(decode) == 4
    whole = trace.whole_programs(events, "paged_decode_window")
    assert whole == decode[1:3]
    layers = 4  # one dense, three of experts
    old = [max(1, round(max(_op_counts(events, p).values()) / layers))
           for p in decode]
    assert old == [3, 6, 6, 3]  # 4 steps ran in a whole one: not 6
    cell = cellspec.load_cell(CELLS[0])
    # the window's snapshots: 30 programs harvested, 120 steps
    ctx = {"events": events, "cell": cell, "records": [],
           "trace_span": trace.span(events), "peak": None,
           "stats_start": _stats(640, 160), "stats_end": _stats(760, 190)}
    assert trace.steps_per_program(ctx) == 4.0
    work = trace.decode_work(ctx)
    assert work["programs"] == 2 and work["steps"] == 8.0
    assert work["seconds"] == pytest.approx(2 * 4 * 0.010, rel=1e-6)
    read = metrics.readers()["decode_step_dev_ms.closed"]
    assert read(ctx) == pytest.approx(10.0, rel=1e-6)
    # a program from before the counters: nothing to read, not a guess
    old_program = dict(ctx, stats_start={}, stats_end={})
    del old_program["decode_work"]
    assert trace.steps_per_program(old_program) is None
    assert read(old_program) is None


def test_a_stall_of_the_host_leaves_its_cause_in_the_report():
    """The garbage collector's pauses of 20 ms and more are kept with
    their time, shorter ones not."""
    import gc

    from benchmark.harness import GcMeter

    meter = GcMeter().install()
    try:
        gc.collect()
        n = len(meter.pauses)
        meter._on_gc("start", {"generation": 2})
        meter._began -= 0.5  # as if the collection had taken half a second
        meter._on_gc("stop", {"generation": 2})
    finally:
        meter.remove()
    assert len(meter.pauses) == n + 1
    at, took, generation = meter.pauses[-1]
    assert took >= 0.5 and generation == 2
    gc.collect()
    assert len(meter.pauses) == n + 1  # removed: no longer listening


def test_live_rows_and_tokens_from_records():
    records = [_record(0.0, 1.0, 3.0, 21, prompt=100),
               _record(0.0, 2.0, 4.0, 41, prompt=200)]
    rows, tokens = trace.live_rows_and_tokens(records, 2.0, 3.0, points=100)
    assert rows == pytest.approx(2.0)
    # halfway: 100 + 0.75 * 21 and 200 + 0.25 * 41
    assert tokens == pytest.approx(100 + 15.75 + 200 + 10.25, rel=1e-3)


# ---- roofline counts and peaks ------------------------------------------


@pytest.mark.parametrize("layers", [16, 30])  # as run; as published
def test_roofline_counts_from_shapes(layers):
    config = cellspec.load_cell("starcoder2-3b.batchgen").config
    assert config["model"]["n_layers"] == config["num_hidden_layers"] == 16
    assert config["published"]["num_hidden_layers"] == 30
    model = dict(config["model"], n_layers=layers)
    layer = 95_944_704  # 3072 x 3584 + 3072 x 3072 + 2 x 3072 x 12288
    total = layers * layer + 49152 * 3072
    block = cellspec.load_cell("starcoder2-3b.batchgen").reference
    assert block.__file__ == os.path.join(REPO, "benchmark", "references",
                                          "starcoder2.py")
    assert block.layer_params(model) == layer
    assert block.matrix_params(model) == total
    peak = roofline.peaks("TPU v5 lite")
    step = block.decode_step(model, rows=32, live_tokens=32 * 600)
    assert step["bytes"] == 2 * total + block.kv_bytes_per_token(model) \
        * (32 * 600 + 32)
    # a decode step at these batch sizes is bound by memory, not compute
    assert step["bytes"] / peak["hbm_bytes_per_s"] \
        > step["flops"] / peak["bf16_flops_per_s"]
    assert roofline.least_seconds(step, peak, 4) == pytest.approx(
        roofline.least_seconds(step, peak, 1) / 4)


def test_peaks_table_and_unknown_device():
    peak = roofline.peaks("TPU v5 lite")
    assert peak == {"bf16_flops_per_s": 197e12, "int8_ops_per_s": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}
    with pytest.raises(KeyError):
        roofline.peaks("cpu")


# ---- BENCHMARK.json against the files ------------------------------------


def test_every_named_thing_has_its_file():
    found = metrics.readers()
    for m in BENCH["per_layer"]:
        assert m["name"] in found, m["name"]
    # every reader file is read by some cell: none is kept for later
    read = {m["name"].removesuffix(".closed") for m in BENCH["per_layer"]}
    assert {name.removesuffix(".closed") for name in found} == read
    for kind, names in (("cells", CELLS),
                        ("traffic", [w["traffic"]
                                     for w in BENCH["workloads"]]),
                        ("configs", [c["name"] for c in BENCH["configs"]])):
        # two cells may share a traffic mix: a file each name, no other
        files = os.listdir(os.path.join(REPO, "benchmark", kind))
        assert len(files) == len(set(names))
        assert {f.rsplit(".", 1)[0] for f in files} == set(names)
    # a configuration names its block's file, and every file there is some
    # configuration's
    blocks = set()
    for conf in BENCH["configs"]:
        with open(os.path.join(REPO, conf["file"])) as fh:
            blocks.add(json.load(fh)["reference"])
    files = [f for f in os.listdir(os.path.join(REPO, "benchmark",
                                                "references"))
             if not f.startswith("__")]
    assert sorted(files) == sorted(b + ".py" for b in blocks)
    for name in CELLS:
        cell = cellspec.load_cell(name)
        for function in ("make_weights", "logits", "decode_step"):
            assert callable(getattr(cell.reference, function))
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
        assert set(cell.load["check"]["limits"]) \
            == {"token_gap_max", "token_gap_mean"}
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 4)


def _in_the_layout(conf: dict, repo: str) -> dict:
    """A configuration's file against its entry: the layout at the top
    of ``cellspec.py``, as far as data can be held to it."""
    with open(os.path.join(repo, conf["file"])) as fh:
        config = json.load(fh)
    assert isinstance(config, dict)
    assert conf["file"].endswith(".json")
    assert config["source"] == conf["source"]
    assert config["reduced"] == conf["reduced"]
    assert isinstance(config["reference"], str) and config["reference"]
    assert isinstance(config["deployment"], str) and config["deployment"]
    # what is reduced stands at the top level as run, and as published
    assert sorted(config["published"]) == sorted(conf["reduced"])
    for key in conf["reduced"]:
        assert key in config, key
        assert config[key] != config["published"][key], key
    # one statement of each size: the block's file makes the program's
    assert "model" not in config
    for section in ("mesh", "payload"):
        assert isinstance(config[section], dict), section
    # a departure stands under the published key the program does not run
    # as stated, or under a short name; a note is about a top-level key, a
    # section or one of a section's keys
    for about, text in config.get("departures", {}).items():
        assert isinstance(text, str) and text, about
    for about, text in config.get("notes", {}).items():
        section, _, key = about.partition(".")
        assert section in config, about
        assert not key or key in config[section], about
        assert isinstance(text, str) and text
    return config


@pytest.mark.parametrize("name", [c["name"] for c in BENCH["configs"]])
def test_a_committed_configuration_is_a_json_object_in_the_layout(name):
    conf = next(c for c in BENCH["configs"] if c["name"] == name)
    config = _in_the_layout(conf, REPO)
    # the source's own keys are there, not only the program's document
    assert len([k for k, v in config.items()
                if isinstance(v, (int, float))]) >= 5


def test_the_committed_configuration_holds_the_source_s_keys():
    """``starcoder2-3b``: every key of the published ``config.json`` that
    says something about the shape, under its published name, the depth
    as run with the published depth beside it; the keys the program's
    block does not run as stated are the keys of ``departures``."""
    with open(os.path.join(REPO, "benchmark", "configs",
                           "starcoder2-3b.json")) as fh:
        config = json.load(fh)
    published = {
        "hidden_size": 3072, "intermediate_size": 12288,
        "num_attention_heads": 24, "num_key_value_heads": 2,
        "vocab_size": 49152, "max_position_embeddings": 16384,
        "sliding_window": 4096, "rope_theta": 999999.4420358813,
        "hidden_act": "gelu_pytorch_tanh", "norm_epsilon": 1e-05,
        "use_bias": True, "model_type": "starcoder2",
        "mlp_type": "default", "norm_type": "layer_norm"}
    assert {k: config[k] for k in published} == published
    assert config["num_hidden_layers"] == 16
    assert config["published"] == {"num_hidden_layers": 30}
    unread = {"norm_type", "norm_epsilon", "use_bias", "sliding_window",
              "rope_theta"}
    assert unread <= set(config["departures"]) and unread <= set(config)
    # what ISSUE 32 took out as stale stays out
    assert "serving_overlap" not in json.dumps(config)
    assert "float32" not in json.dumps(config["departures"])


def _ints_doubled(config: dict) -> dict:
    return {k: 2 * v if type(v) is int else v for k, v in config.items()}


@pytest.mark.parametrize("name", ["starcoder2-3b.batchgen", "probe.tiny",
                                  "probe2.tiny"])
def test_the_program_s_sizes_are_made_from_the_published_keys(probe, name):
    """What a file states is what runs, for every configuration: the
    server's ``model`` is the block's ``model_of`` of the file, each of
    its sizes is a top-level key's value, and a file that stated every
    size twice as large would run every size twice as large (nothing in
    ``model_of`` is a size of its own)."""
    cell = cellspec.load_cell(name, repo=probe)
    with open(os.path.join(probe, "BENCHMARK.json")) as fh:
        conf = next(c for c in json.load(fh)["configs"]
                    if c["name"] == name.split(".")[0])
    with open(os.path.join(probe, conf["file"])) as fh:
        config = json.load(fh)
    document = cellspec.runtime_document(cell, "<dir>", "cpu")
    model = cell.config.pop("model")
    assert cell.config == config
    assert _same(model, cell.reference.model_of(config))
    assert _same(document["model"], model)
    stated = [v for v in config.values() if type(v) is int]
    assert model and all(type(v) is int and v in stated
                         for v in model.values()), model
    assert cell.reference.model_of(_ints_doubled(config)) == {
        k: 2 * v for k, v in model.items()}
    for key in conf["reduced"]:  # what is reduced is what runs
        assert config[key] in model.values(), key


@pytest.mark.parametrize("text, what", [
    ('reference = "starcoder2"\n[model]\nvocab = 256\n', "does not parse"),
    ('[{"reference": "starcoder2"}]', "holds a list"),
    ("", "does not parse"),
    ('{"reference": "starcoder2", "mesh": {}}', 'no object "payload"'),
    ('{"reference": "starcoder2", "hidden_size": 3072, "mesh": {}, '
     '"payload": {}, "model": {"d_model": 64}}', 'a "model" of its own'),
], ids=["toml", "array", "nothing", "no-payload", "a-second-model"])
def test_a_file_that_is_no_json_object_is_an_error(tmp_path, text, what):
    """One form and no second: the error names the file and the form."""
    root = str(tmp_path)
    os.makedirs(os.path.join(root, "benchmark", "configs"))
    name = "benchmark/configs/odd.json"
    with open(os.path.join(root, name), "w") as fh:
        fh.write(text)
    doc = json.loads(json.dumps(BENCH))
    doc["configs"][0]["file"] = name
    with open(os.path.join(root, "BENCHMARK.json"), "w") as fh:
        json.dump(doc, fh)
    with pytest.raises(SystemExit) as refused:
        cellspec.load_cell(CELLS[0], repo=root,
                           root=os.path.join(REPO, "benchmark"))
    said = str(refused.value)
    assert name in said and "a JSON object" in said and what in said
    assert '"reference"' in said and '"payload"' in said


@pytest.mark.parametrize("config, block, said", [
    ({"hidden_size": 64}, "starcoder2", "lacks the key 'vocab_size'"),
    (PROBE_CONFIG, "", "has no model_of(config)"),
], ids=["a-key-not-stated", "a-block-that-makes-none"])
def test_sizes_the_block_cannot_make_are_an_error(config, block, said):
    """A file that does not state a size the block's ``model_of`` reads,
    or a block's file without one: the error names both files."""
    conf = {"file": "benchmark/configs/x.json"}
    reference = (cellspec.load_cell(CELLS[0]).reference if block else
                 type(cellspec)("old"))
    reference.__file__ = "benchmark/references/old.py"
    with pytest.raises(SystemExit) as refused:
        cellspec.model_of(conf, config, reference)
    assert conf["file"] in str(refused.value)
    assert "references/old.py" in str(refused.value)
    assert said in str(refused.value)


def _same(a, b) -> bool:
    """Equal key for key, value for value and type for type (``1``,
    ``1.0`` and ``True`` are three values here)."""
    if type(a) is not type(b):
        return False
    if isinstance(a, dict):
        return a.keys() == b.keys() and all(_same(a[k], b[k]) for k in a)
    if isinstance(a, list):
        return len(a) == len(b) and all(map(_same, a, b))
    return a == b


# What ``cellspec.runtime_document(load_cell("starcoder2-3b.batchgen"),
# "<dir>", "tpu")`` gave on the parent of ISSUE 32, which read the
# configuration from a toml: the document the server of the one cell has
# started from since PR 23. A ``benchmark`` PR that resizes the deployment
# changes this literal, and measures every bound anew.
PARENT_DOCUMENT = {
    "runtime": {"name": "bench-starcoder2-3b.batchgen",
                "state_dir": "<dir>"},
    "tpu": {"platform": "tpu", "expected_chips": 1},
    "status": {"bind": "127.0.0.1", "port": 0},
    "mesh": {"axes": {"data": 1}},
    "model": {"vocab": 49152, "d_model": 3072, "n_heads": 24,
              "n_kv_heads": 2, "n_layers": 16, "d_ff": 12288},
    "payload": {"kind": "serve", "serving": "paged", "seq": 3072,
                "serving_slots": 64, "serving_page_size": 128,
                "serving_prefix_persist": False, "serving_pages": 768},
}
# sha256 of ``json.dumps(schedule.build(...), sort_keys=True)`` there, at
# the benchmark's 48 s, by seed: every request, due time and length.
PARENT_PLANS = {
    3: "7d02f1c3f53887aee2a1ab9935a5b971ab68c14ebe819024a117db4af997c3c6",
    2**31 + 12345:
        "14654389da3fa0c9d1314f38745ae0fbde87025d342dcced5ef2101a3fc06dfc",
}


def test_the_cell_starts_from_the_document_it_always_did():
    cell = cellspec.load_cell("starcoder2-3b.batchgen")
    document = cellspec.runtime_document(cell, "<dir>", "tpu")
    assert _same(document, PARENT_DOCUMENT), document
    assert not _same({"n": 1}, {"n": 1.0}) and not _same([0], [False])
    assert list(document) == list(PARENT_DOCUMENT)
    # an override lands in the payload and nowhere else
    over = cellspec.runtime_document(cell, "<dir>", "tpu",
                                     {"serving_pages": 640})
    assert over["payload"]["serving_pages"] == 640
    assert _same({**over, "payload": None},
                 {**PARENT_DOCUMENT, "payload": None})
    # the reference is handed the same ``model`` object
    assert _same(cell.config["model"], PARENT_DOCUMENT["model"])


@pytest.mark.parametrize("seed", sorted(PARENT_PLANS))
def test_the_cell_offers_the_plan_it_always_did(seed):
    cell = cellspec.load_cell("starcoder2-3b.batchgen")
    plan = schedule.build(cell.traffic, cell.load, seed,
                          BENCH["run_seconds"],
                          cell.config["model"]["vocab"])
    assert plan["work"] == {
        "requests": 640, "prompt_tokens": 204800, "output_tokens": 1110199,
        "lengths_sha256_16": "69d64c7e07b920fc"}
    text = json.dumps(plan, sort_keys=True)
    assert hashlib.sha256(text.encode()).hexdigest() == PARENT_PLANS[seed]


def test_a_share_of_the_experts_is_written_as_the_layout_says(probe):
    """``probe2`` as the catalog's many-expert rows will be written: the
    key that counts the experts, under its published name, holds how many
    are held here and is in ``reduced``; the published count stands under
    ``published``; ``deployment`` names the chips that share a layer."""
    with open(os.path.join(probe, "BENCHMARK.json")) as fh:
        doc = json.load(fh)
    for name in ("probe", "probe2"):
        conf = next(c for c in doc["configs"] if c["name"] == name)
        config = _in_the_layout(conf, probe)
    assert conf["reduced"] == ["num_local_experts"]
    assert config["num_local_experts"] == 4
    assert config["published"] == {"num_local_experts": 8}
    assert config["num_experts_per_tok"] == 2
    assert "2 chips share each layer" in config["deployment"]
    cell = cellspec.load_cell("probe2.tiny", repo=probe)
    document = cellspec.runtime_document(cell, "<dir>", "cpu")
    assert document["model"] == {
        "vocab": 256, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
        "n_layers": 2, "d_ff": 128, "experts": 4, "expert_top_k": 2}


def test_no_second_format_is_read_anywhere_in_the_benchmark():
    """A ``git grep`` for the standard library's toml reader over
    ``benchmark`` and ``tests/bench_harness`` finds nothing (this file
    does not spell its name either), and no file there is a toml."""
    word = "toml" + "lib"
    for top in (os.path.join(REPO, "benchmark"), HERE):
        for folder, _, files in os.walk(top):
            for name in files:
                assert not name.endswith(".toml"), name
                if not name.endswith(".py"):
                    continue
                with open(os.path.join(folder, name)) as fh:
                    assert word not in fh.read(), os.path.join(folder, name)
    assert not hasattr(cellspec, word)
    assert all(c["file"].endswith(".json") for c in BENCH["configs"])


# ---- one whole run at a probe size, on the CPU ---------------------------


@pytest.fixture(scope="module")
def probe(tmp_path_factory):
    return probe_tree(str(tmp_path_factory.mktemp("checkout")))


def _measure(root, seed, **kw):
    """One run through ``run.measure``, past its look for a chip. The
    runtime builds its mesh from ``jax.devices()``: of the eight virtual
    devices the tests have, it is handed one."""
    import time

    import jax

    from benchmark import run

    one = jax.devices()[:1]
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax, "devices", lambda *a, **k: one)
        return _measure_one(root, seed, run, time, **kw)


def _measure_one(root, seed, run, time, name="probe.tiny", **kw):
    cell = cellspec.load_cell(name, repo=root)
    device = {"platform": "cpu", "kind": "cpu", "count": 1}
    said = []
    line = run.measure(cell, seed, 4.0, False, device,
                       t_process=time.monotonic(), say=said.append, **kw)
    return cell, line, said


def test_new_files_and_entries_are_enough(probe):
    """A configuration, a mix, a cell and a per-layer metric added as
    files and entries: the harness finds each by name."""
    cell = cellspec.load_cell("probe.tiny", repo=probe)
    assert cell.config["model"]["d_model"] == 64
    assert cell.traffic["prompt"]["median"] == 48
    assert "probe_requests" in {m["name"] for m in cell.per_layer}
    found = metrics.readers(os.path.join(cell.root, "metrics"))
    assert found["probe_requests"]({"window": [1, 2, 3]}) == 3.0
    # the committed cells do not see the newcomer's metric
    real = cellspec.load_cell(CELLS[0], repo=probe)
    assert "probe_requests" not in {m["name"] for m in real.per_layer}


@pytest.mark.parametrize("config, missing", [
    ({}, '"reference": "<stem>"'),
    ({"reference": ""}, '"reference": "<stem>"'),
    ({"reference": "nowhere"}, os.path.join("references", "nowhere.py")),
])
def test_a_configuration_without_its_blocks_file_is_an_error(
        probe, config, missing):
    """Never a default: the error names the configuration's file and the
    key or the file it lacks."""
    root = os.path.join(probe, "benchmark")
    with pytest.raises(SystemExit) as refused:
        cellspec.load_reference({"file": "benchmark/configs/x.json"},
                                config, root)
    assert "benchmark/configs/x.json" in str(refused.value)
    assert missing in str(refused.value)


# The float32 logits of ``benchmark/reference.py`` on the parent of the PR
# that moved it (ISSUE 26), at the probe size, for the input below: sha256
# over the three arrays' bytes, for the reference and its two ``quant``s.
PARENT_LOGITS = {
    "": "6ec3771e9dbbb8d15f0ddb9a1f275a0f135738841995daba25edc680be847102",
    "int8":
        "5f1252542b5d77e2b7479674196d2a463c21e7cc549ec4879db72d3be8ea1c63",
    "bf16":
        "3b24c4eaaee5d431853a6140f511e91f35574e3e07ee0a61132b0bbb0ce321a8",
}


@pytest.mark.parametrize("quant", sorted(PARENT_LOGITS))
def test_the_moved_reference_computes_what_it_did_before(quant):
    """Bit for bit: the move changed where the block's file is, not its
    arithmetic."""
    block = cellspec.load_cell(CELLS[0]).reference
    model = {"vocab": 256, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
             "n_layers": 2, "d_ff": 128}
    weights = block.make_weights(model)
    sequences = [schedule.prompt_tokens(26, i, n, 256)
                 for i, n in enumerate((64, 96, 128))]
    rows = block.logits(model, weights, sequences, [31, 0, 100], quant=quant)
    assert [r.shape for r in rows] == [(33, 256), (96, 256), (28, 256)]
    digest = hashlib.sha256()
    for r in rows:
        assert r.dtype == np.float32
        digest.update(np.ascontiguousarray(r).tobytes())
    assert digest.hexdigest() == PARENT_LOGITS[quant]


def test_a_block_of_its_own_runs_by_its_own_file(probe, tmp_path):
    """A configuration of another block, added as a JSON object, a file
    under ``references/``, a cell file and entries: a whole CPU run is correct
    by that file's ``logits`` over that file's weights, and the committed
    block's file, asked about the same served tokens, says they are not
    its model's."""
    out = str(tmp_path)
    cell, line, said = _measure(probe, 26, name="probe2.tiny", out_dir=out)
    block = cell.reference
    assert block.__file__ == os.path.join(probe, "benchmark", "references",
                                          "probe2.py")
    assert block.CALLS == ["make_weights", "logits"]
    assert line["correct"] is True, said
    assert line["failed"] == 0 and line["attempted"] == 12
    held = line["check"]
    assert list(line)[-1] == "check"  # the numbers compared come last
    assert held["token_gap_mean"]["value"] <= held["token_gap_mean"]["limit"]
    assert set(held) == {"token_gap_max", "token_gap_mean", "failed",
                         "window_compiles"}
    # the same served tokens by the block the benchmark already had
    other = cellspec.load_cell("probe.tiny", repo=probe).reference
    assert other.__name__ != block.__name__
    with open(os.path.join(out, "probe2.tiny", "seed26-trace0.json")) as fh:
        report = json.load(fh)
    records, ours = report["records"], report["check"]
    assert ours["tokens"] > 50
    assert ours["token_gap_mean"] == held["token_gap_mean"]["value"]
    chosen = check.sample(records, 26, 4.0, 3, "open")
    model = cell.config["model"]
    theirs = check.token_gaps(model, other.make_weights(model), chosen, 26,
                              256, other)
    assert theirs["tokens"] == ours["tokens"]
    assert theirs["token_gap_mean"] > 10 * held["token_gap_mean"]["limit"]
    assert not check.verdict(theirs, cell.load["check"]["limits"],
                             lambda text: None)


def test_the_roofline_share_is_of_the_cells_own_blocks_count(probe):
    """``decode_roofline_pct`` takes the operations and bytes of a step
    from the file the cell's configuration names."""
    events = two_kinds_of_layer_trace()
    peak = roofline.peaks("TPU v5 lite")
    read = metrics.readers(os.path.join(probe, "benchmark", "metrics"))[
        "decode_roofline_pct.closed"]
    shares = {}
    for name in ("probe.tiny", "probe2.tiny"):
        cell = cellspec.load_cell(name, repo=probe)
        ctx = {"events": events, "cell": cell, "peak": peak,
               "records": [_record(-1.0, -0.5, 9.0, 400, prompt=100),
                           _record(-1.0, -0.5, 9.0, 400, prompt=60)],
               "trace_span": trace.span(events),
               "stats_start": _stats(0, 0), "stats_end": _stats(120, 30)}
        shares[name] = read(ctx)
        if name == "probe2.tiny":  # the reader went to this cell's file
            assert cell.reference.CALLS == ["decode_step"]
        work = ctx["decode_work"]
        assert work["rows"] == 2.0 and work["steps"] == 4.0
        step = cell.reference.decode_step(cell.config["model"], 2.0,
                                          work["live_tokens"])
        assert shares[name] == pytest.approx(
            100.0 * roofline.least_seconds(step, peak) * 4.0
            / work["seconds"])
    # four experts of which two rows can reach all: more bytes a step
    assert shares["probe2.tiny"] > 1.5 * shares["probe.tiny"] > 0


@pytest.fixture(scope="module")
def probe_run(probe, tmp_path_factory):
    """One traced-style run (per-layer metrics, report written). The
    probe's requests live some 30 ms on the CPU, the committed cell's
    half a minute on the chip: sampled every quarter second, one run in
    five here saw no request in flight in any of its sixteen samples. So
    this run samples every 20 ms, under a request's life as on the chip."""
    from benchmark import harness

    out = str(tmp_path_factory.mktemp("out"))
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(harness, "SAMPLE_EVERY", 0.02)
        cell, line, said = _measure(probe, 5, layers=True, out_dir=out)
    with open(os.path.join(out, "probe.tiny", "seed5-trace0.json")) as fh:
        return cell, line, said, json.load(fh)


def test_a_whole_run_on_the_cpu_is_correct(probe_run):
    cell, line, said, report = probe_run
    assert report["work"]["requests"] == 15 and report["line"] == line
    assert line["correct"] is True, said
    assert line["failed"] == 0 and line["attempted"] == 12
    got = line["metrics"]
    assert got["probe_requests"]["value"] == 12.0
    assert got["window_compiles"]["value"] == 0.0
    assert got["queue_wait_ms"]["value"] >= 0.0
    assert 0.0 < got["batch_occupancy_pct.closed"]["value"] <= 100.0
    assert got["delivered_tok_s.closed"]["value"] > 0.0
    assert got["setup_ramp_s"]["value"] == 1.0
    # nothing traced: the trace's metrics are left out, not invented
    assert "device_idle_pct" not in got
    assert any("token_gap_max" in s and "limit" in s for s in said)


def test_a_run_reports_the_end_to_end_metrics(probe):
    cell, line, said = _measure(probe, 2**31 + 77)
    assert set(line["metrics"]) == {"ttft_p90_ms", "tpot_p50_ms",
                                    "out_tok_s", "setup_s"}
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert line["device"]["platform"] == "cpu"
    assert "breakdown" not in line


def test_a_closed_loop_run_on_the_cpu(probe):
    """The committed cell's kind of load: clients that send their next
    request when the last one ends, the first cut to a residual life;
    what is under way at the close is cut and is no failure."""
    cell, line, said = _measure(probe, 11, name="probe.tinyclosed")
    assert set(line["metrics"]) == {"out_tok_s", "setup_s"}
    assert line["metrics"]["out_tok_s"]["value"] > 0
    assert line["failed"] == 0 and line["attempted"] >= 3
    assert any("token_gap_mean" in s and s.endswith("ok") for s in said)
    # three rows of a few dozen tokens end together now and then, and the
    # server then dispatches a short window in a variant no warm-up reached
    # (the committed cell's rows never all end within one window): such a
    # run is not correct, and the check names the program
    lowered = [s for s in said if "window_compiles" in s][0]
    assert line["correct"] is lowered.endswith("ok"), said
    assert lowered.endswith("ok") or "paged_decode_window" in lowered


def test_a_closed_loops_chains_are_the_mix_s_not_the_seed_s(probe):
    """Which requests start, prefill and end inside the window is the
    same for every seed: the seed deals fixed chains out among the
    clients (and draws the token ids)."""
    cell = cellspec.load_cell("probe.tinyclosed", repo=probe)
    plans = [schedule.build(cell.traffic, cell.load, seed, 4.0, 256)
             for seed in (1, 2, 3, 4)]
    chains = []
    for plan in plans:
        by_client: dict = {}
        for r in plan["requests"]:
            by_client.setdefault(r["client"], []).append(
                (r["due"], r["prompt"], r["n_new"]))
        chains.append(by_client)
    assert all(sorted(c.values()) == sorted(chains[0].values())
               for c in chains)
    assert any(c != chains[0] for c in chains)
    firsts = sorted(chain[0] for chain in chains[0].values())
    assert [due for due, _, _ in firsts] == pytest.approx(
        [-1.0 + 0.5 * (j + 0.5) / 3 for j in range(3)])
    full = schedule.paired_grid(cell.traffic, 3 * 40)
    assert all(n_new <= max(o for _, o in full) for _, _, n_new in firsts)
    assert len({n_new for _, _, n_new in firsts}) == 3  # cut by its share


def test_a_token_altered_where_it_is_produced_is_not_correct(
        probe, monkeypatch):
    """The timed path broken underneath: every eleventh token a harvested
    window emits is swapped for another, and ``correct`` comes out false."""
    from kvedge_tpu.models.serving import PagedGenerationServer

    emit_many = PagedGenerationServer._emit_many
    count = {"n": 0}

    def altered(req, tokens):
        out = []
        for token in tokens:
            count["n"] += 1
            out.append((token + 1) % 256 if count["n"] % 11 == 0 else token)
        emit_many(req, out)

    monkeypatch.setattr(PagedGenerationServer, "_emit_many",
                        staticmethod(altered))
    cell, line, said = _measure(probe, 9)
    assert line["failed"] == 0
    assert line["correct"] is False, said
    assert any("token_gap_max" in s and "FAILED" in s for s in said)


def test_the_int8_control_reads_wider_gaps():
    """The control at a size a test can hold: over the same streams, the
    reference computed in int8 puts first tokens that lie several times
    further below the float32 reference's best than the ones bf16, the
    precision a sound run serves in, puts first. (On the chip, at the
    cells' sizes and against the program itself: PERF.md.)"""
    reference = cellspec.load_cell(CELLS[0]).reference
    model = {"vocab": 2048, "d_model": 256, "n_heads": 4, "n_kv_heads": 2,
             "n_layers": 4, "d_ff": 512}
    weights = reference.make_weights(model)
    streams = [{"index": i, "prompt": 32, "n_new": 480,
                "tokens": schedule.prompt_tokens(9, 100 + i, 480, 2048)}
               for i in range(3)]
    sound = check.control_gaps(model, weights, streams, 9, 2048, reference,
                               quant="bf16")
    control = check.control_gaps(model, weights, streams, 9, 2048, reference)
    assert sound["tokens"] == control["tokens"] == 1440
    assert control["token_gap_mean"] > 3 * sound["token_gap_mean"] > 0
    assert control["differ"] > 2 * sound["differ"]


def test_the_check_reads_gaps_against_a_reference():
    class Ref:
        @staticmethod
        def logits(model, weights, sequences, first):
            import numpy as np
            out = []
            for seq, f in zip(sequences, first):
                rows = np.zeros((len(seq) - f, 8), np.float32)
                rows[:, 3] = 1.0      # the reference always prefers 3
                rows[:, 5] = 0.75
                out.append(rows)
            return out

    served = {"index": 0, "prompt": 4, "n_new": 4, "tokens": [3, 5, 3, 3],
              "due": 0.0, "first": 0.1, "last": 0.2, "error": None}
    numbers = check.token_gaps({}, {}, [served], 1, 8, Ref)
    assert numbers["tokens"] == 4 and numbers["differ"] == 1
    assert numbers["token_gap_max"] == pytest.approx(0.25)
    assert numbers["token_gap_mean"] == pytest.approx(0.0625)
    said = []
    assert check.verdict(numbers, {"token_gap_max": 0.3,
                                   "token_gap_mean": 0.1}, said.append)
    assert not check.verdict(numbers, {"token_gap_max": 0.2,
                                       "token_gap_mean": 0.1}, said.append)
    picked = check.sample(
        [dict(served, index=i, prompt=4 + i) for i in range(6)], 3, 1.0, 3,
        "open")
    assert len(picked) == 3 and picked[0]["prompt"] == 9


def test_the_command_refuses_a_cpu_backend():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    done = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert done.returncode != 0
    assert "correct" not in done.stdout
    assert "not a tpu" in done.stderr
