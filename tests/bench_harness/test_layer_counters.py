"""The nine per-layer metrics that read the server's own clock and
counts (ISSUE 24): each reader on a hand-made pair of snapshots, on a
pair from a program that lacks the keys (nothing to read: ``None``, and
no exception), and all nine in the lines of whole CPU runs at the probe
size, beside the metrics the cells already had."""

import json
import os

import pytest

from benchmark import cellspec, metrics

from test_benchmark_harness import _measure, probe_tree

with open(os.path.join(cellspec.REPO, "BENCHMARK.json")) as _fh:
    BENCH = json.load(_fh)


def _hist(count, total):
    return {"edges": [1.0], "counts": [0, count], "sum": total,
            "count": count}


def _phases(boundary, dispatch, emit):
    return {"loop/lock_wait": [9, 100.0], "loop/wait_work": [0, 0.0],
            "loop/boundary": [3, boundary], "loop/dispatch": [3, dispatch],
            "loop/harvest_wait": [3, 900.0], "loop/emit": [3, emit]}


START = {
    "clock_s": 100.0, "pages_total": 768,
    "prefill_lock_wait_ms": _hist(10, 1000.0),
    "prefill_chunk_ms": _hist(10, 50.0),
    "first_emit_ms": _hist(2, 3000.0),
    "phase_ms": _phases(10.0, 20.0, 30.0),
    "loop_lock_held_ms_total": 500.0,
    "decode_steps_total": 640, "decode_row_steps_total": 20000,
    "decode_bucket_steps_total": 40960, "pages_live_steps_total": 200000,
    "tokens_emitted_total": 20000,
}
END = {
    "clock_s": 148.0, "pages_total": 768,
    "prefill_lock_wait_ms": _hist(170, 97000.0),
    "prefill_chunk_ms": _hist(170, 850.0),
    "first_emit_ms": _hist(34, 51000.0),
    "phase_ms": _phases(250.0, 500.0, 1230.0),
    "loop_lock_held_ms_total": 36500.0,
    "decode_steps_total": 2560, "decode_row_steps_total": 91040,
    "decode_bucket_steps_total": 163840, "pages_live_steps_total": 934400,
    "tokens_emitted_total": 87200,
}
EXPECTED = {
    "prefill_lock_wait_ms": 96000.0 / 160,
    "prefill_chunk_ms": 800.0 / 160,
    "lock_loop_held_pct": 100 * 36.0 / 48.0,
    "loop_host_pct": 100 * 1.92 / 48.0,
    "decode_bucket_fill_pct": 100 * 71040 / 122880,
    "first_emit_ms": 48000.0 / 32,
    "emitted_tok_s": 67200 / 48.0,
    "pool_live_pct": 100 * 734400 / (1920 * 768),
    "decode_step_wall_ms": 48000.0 / 1920,
}
NEW = sorted(EXPECTED)
# what a program from before these counters says of itself
OLD_PROGRAM = {"in_flight": 3, "pages_total": 768,
               "ttft_ms": _hist(4, 100.0), "queue_ms": _hist(4, 10.0)}


def test_the_nine_are_what_this_test_knows():
    added = [m for m in BENCH["per_layer"]
             if m["name"].removesuffix(".closed") in EXPECTED]
    assert sorted(m["name"] for m in added) \
        == sorted(name + ".closed" for name in NEW)
    assert all(m["source"] == "program_counter" and "workloads" not in m
               and m["moves"] == "out_tok_s" for m in added)
    # appended in one block, wherever later entries have left it
    at = BENCH["per_layer"].index(added[0])
    assert BENCH["per_layer"][at:at + 9] == added


@pytest.mark.parametrize("name", NEW)
def test_a_reader_on_hand_made_snapshots(name):
    found = metrics.readers()
    ctx = {"stats_start": START, "stats_end": END}
    assert found[name](ctx) == pytest.approx(EXPECTED[name])
    assert found[name + ".closed"] is found[name]


@pytest.mark.parametrize("name", NEW)
def test_a_reader_finds_nothing_in_an_older_programs_snapshots(name):
    read = metrics.readers()[name]
    assert read({"stats_start": OLD_PROGRAM, "stats_end": OLD_PROGRAM}) \
        is None
    # the keys there and nothing between the snapshots: nothing to divide
    assert read({"stats_start": START, "stats_end": START}) is None
    # half a program (the start snapshot from before a restart)
    assert read({"stats_start": OLD_PROGRAM, "stats_end": END}) is None


@pytest.fixture(scope="module")
def probe(tmp_path_factory):
    return probe_tree(str(tmp_path_factory.mktemp("checkout")))


@pytest.mark.parametrize("cell", ["probe.tiny", "probe.tinyclosed"])
def test_a_probe_cells_cpu_run_reports_all_nine(probe, cell):
    """An open-loop cell and a closed-loop one, as a later PR might add
    them: both report ``out_tok_s``, so both report the nine, beside
    what they reported before; the readings obey what counts must."""
    loaded, line, said = _measure(probe, 31, name=cell, layers=True)
    got = {name: m["value"] for name, m in line["metrics"].items()}
    assert {name + ".closed" for name in NEW} <= set(got)
    assert {"queue_wait_ms.closed", "batch_occupancy_pct.closed",
            "window_compiles"} <= set(got)
    assert line["failed"] == 0
    for name in ("prefill_lock_wait_ms", "prefill_chunk_ms",
                 "first_emit_ms", "lock_loop_held_pct", "loop_host_pct"):
        assert got[name + ".closed"] >= 0.0
    for name in ("lock_loop_held_pct", "loop_host_pct",
                 "decode_bucket_fill_pct", "pool_live_pct"):
        assert 0.0 < got[name + ".closed"] <= 100.0
    assert got["emitted_tok_s.closed"] > 0.0
    assert got["decode_step_wall_ms.closed"] > 0.0
    units = {m["name"]: m["unit"] for m in loaded.per_layer}
    assert all(line["metrics"][name + ".closed"]["unit"]
               == units[name + ".closed"] for name in NEW)
