"""The six per-layer metrics that read the server's two ledgers (ISSUE
38: who holds the work lock, and what a request does while it holds
its pages): each reader on a hand-made pair of snapshots, on a pair
from a program that lacks the ledgers (nothing to read: ``None``, and
no exception), and all six in the line of a whole CPU run at the probe
size. A file of its own: a ``tracing`` PR adds files beside the
harness's and edits none of them. It also holds what two tests beside
it asserted of the cells' metric lists before they pinned a count
(``len(other) == 23``) that any new entry falsifies."""

import pytest

from benchmark import cellspec, metrics

from test_benchmark_harness import BENCH, _measure, probe_tree

HOLDERS = ("loop", "admit/start", "admit/prefill_chunk", "admit/first_pick",
           "cancel", "stats", "control")


def _lock(loop, start, chunk, pick, stats, unnamed=0.0):
    held = dict(zip(HOLDERS, (loop, start, chunk, pick, 0.0, stats, 0.0)))
    return {"lock_held_ms_total": sum(held.values()) + unnamed,
            "lock_held_ms": {n: [7, ms] for n, ms in held.items()},
            "lock_wait_ms": {n: [7, 1.0] for n in HOLDERS}}


def _requests(n, queued, admit, wait, prefill, pick, join, decode, wrote):
    return {"request_ms": {
        "queued": [n, queued], "admit": [n, admit],
        "prefill_wait": [n, wait], "prefill": [n, prefill],
        "pick": [n, pick], "join_wait": [n, join], "decode": [n, decode],
        "swapped": [0, 0.0], "first_write": [n, wrote]}}


START = {"clock_s": 100.0, **_lock(500.0, 10.0, 100.0, 300.0, 1.0),
         **_requests(2, 900.0, 4.0, 1000.0, 120.0, 600.0, 500.0, 20000.0,
                     8.0)}
END = {"clock_s": 148.0,
       **_lock(16500.0, 490.0, 7780.0, 19500.0, 25.0, unnamed=96.0),
       **_requests(122, 77700.0, 244.0, 145000.0, 7800.0, 36600.0,
                   30500.0, 1460000.0, 608.0)}
GAINED = 16000.0 + 480.0 + 7680.0 + 19200.0 + 24.0 + 96.0
EXPECTED = {
    "lock_held_pct": 100 * GAINED / 48000.0,
    "lock_pick_held_pct": 100 * 19200.0 / 48000.0,
    "lock_unnamed_pct": 100 * 96.0 / GAINED,
    "slot_decode_pct": 100 * 1440000.0 / (
        240.0 + 144000.0 + 7680.0 + 36000.0 + 30000.0 + 1440000.0),
    "join_wait_ms": 30000.0 / 120,
    "http_first_write_ms": 600.0 / 120,
}
NEW = sorted(EXPECTED)
# what a program from before the ledgers says of itself
OLD_PROGRAM = {"clock_s": 100.0, "in_flight": 3, "pages_total": 768,
               "loop_lock_held_ms_total": 500.0}
OLDER_END = dict(OLD_PROGRAM, clock_s=148.0, loop_lock_held_ms_total=9000.0)


def test_the_six_are_appended_entries_with_a_reader_each():
    added = [m for m in BENCH["per_layer"]
             if m["name"].removesuffix(".closed") in EXPECTED]
    assert [m["name"] for m in added] == [
        "lock_held_pct.closed", "lock_pick_held_pct.closed",
        "lock_unnamed_pct.closed", "slot_decode_pct.closed",
        "join_wait_ms.closed", "http_first_write_ms.closed"]
    # appended in one block, wherever later entries have left it
    at = BENCH["per_layer"].index(added[0])
    assert BENCH["per_layer"][at:at + 6] == added
    assert all(m["source"] == "program_counter" and "workloads" not in m
               and m["moves"] == "out_tok_s" for m in added)
    layers = {m["name"]: m["layer"] for m in added}
    assert layers.pop("http_first_write_ms.closed") == "HTTP and accounting"
    assert set(layers.values()) == {"admission and batching"}
    assert {m["name"]: m["better"] for m in added
            if m["better"] == "higher"} == {"slot_decode_pct.closed": "higher"}
    found = metrics.readers()
    for name in NEW:
        assert found[name + ".closed"] is found[name]


@pytest.mark.parametrize("name", NEW)
def test_a_reader_on_hand_made_snapshots(name):
    ctx = {"stats_start": START, "stats_end": END}
    assert metrics.readers()[name](ctx) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", NEW)
def test_a_reader_finds_nothing_in_an_older_programs_snapshots(name):
    read = metrics.readers()[name]
    assert read({"stats_start": OLD_PROGRAM, "stats_end": OLDER_END}) is None
    # the keys there and nothing between the snapshots: nothing to divide
    assert read({"stats_start": START, "stats_end": START}) is None
    # half a program (the start snapshot from before a restart)
    assert read({"stats_start": OLD_PROGRAM, "stats_end": END}) is None


def test_a_holder_the_reader_does_not_know_is_still_a_name():
    """``lock_unnamed_pct`` sums the names the snapshots carry, not a
    list of its own: a holder a later PR adds is named, not unnamed."""
    def more(snap, ms):
        held = dict(snap["lock_held_ms"], later=[1, ms])
        return dict(snap, lock_held_ms=held,
                    lock_held_ms_total=snap["lock_held_ms_total"] + ms)

    ctx = {"stats_start": more(START, 0.0), "stats_end": more(END, 5000.0)}
    assert metrics.readers()["lock_unnamed_pct"](ctx) == pytest.approx(
        100 * 96.0 / (GAINED + 5000.0))


@pytest.fixture(scope="module")
def probe(tmp_path_factory):
    return probe_tree(str(tmp_path_factory.mktemp("checkout")))


def test_a_probe_cells_cpu_run_reports_all_six(probe):
    """A closed-loop cell as a later PR might add it: it reports
    ``out_tok_s``, so it reports the six, beside what it reported
    before; the readings obey what the ledgers must."""
    loaded, line, said = _measure(probe, 38, name="probe.tinyclosed",
                                  layers=True)
    got = {name: m["value"] for name, m in line["metrics"].items()}
    assert {name + ".closed" for name in NEW} <= set(got)
    assert {"lock_loop_held_pct.closed", "queue_wait_ms.closed",
            "first_emit_ms.closed", "window_compiles"} <= set(got)
    assert line["failed"] == 0
    assert 0.0 < got["lock_pick_held_pct.closed"] \
        < got["lock_held_pct.closed"] <= 100.0
    assert got["lock_loop_held_pct.closed"] < got["lock_held_pct.closed"]
    assert 0.0 <= got["lock_unnamed_pct.closed"] < 1.0
    assert 0.0 < got["slot_decode_pct.closed"] < 100.0
    assert got["join_wait_ms.closed"] >= 0.0
    assert got["http_first_write_ms.closed"] >= 0.0
    units = {m["name"]: m["unit"] for m in loaded.per_layer}
    assert all(line["metrics"][name + ".closed"]["unit"]
               == units[name + ".closed"] for name in NEW)


# ---- what two tests beside this file asserted, less the pinned count -----


def _names(cell: str) -> set:
    return {m["name"] for m in cellspec.load_cell(cell).per_layer}


def test_the_granite_cell_reports_three_beyond_the_starcoder_cell():
    names, other = (_names("granite-4.0-h-small.batchgen"),
                    _names("starcoder2-3b.batchgen"))
    assert names - other == {"expert_held_pick_pct.closed",
                             "expert_imbalance.closed",
                             "state_reset_ms.closed"}
    assert other <= names


def test_the_solar_cell_reports_one_beyond_the_starcoder_cell():
    names, other = (_names("solar-open2-250b.batchgen"),
                    _names("starcoder2-3b.batchgen"))
    assert names - other == {"expert_touched_pct.closed"}
    assert other <= names


def test_an_entry_with_no_list_of_cells_is_reported_by_all_three():
    cells = [w["name"] for w in BENCH["workloads"]]
    unlisted = {m["name"] for m in BENCH["per_layer"]
                if "workloads" not in m}
    # "all three" were the cells of PR 38; every cell since is held too
    assert len(cells) >= 3 and len(unlisted) >= 23 + 6
    for cell in cells:
        assert unlisted <= _names(cell), cell
