"""The device's idle time put down to the program's spans
(``lib/spans.py``), on a hand-made trace and on the recorded one."""
import json
import os

import pytest

from lib import spans, trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def round_trace():
    """ns.  A sequential round: dispatch > train > step > dispatch, an
    edge aggregation inside the round's dispatch, a cloud aggregation
    after it, a runtime event inside a step, and idle holes that cross
    span boundaries (so labelling a hole by its midpoint would be
    wrong)."""
    ops = [[0, 60, "fusion.1", "gemm"], [200, 310, "fusion.2", "other"],
           [400, 420, "fusion.3", "gemm"], [600, 650, "add.4", "other"],
           [850, 860, "fusion.5", "other"], [950, 1000, "fusion.1", "gemm"]]
    host = [[0, 1000, "bench/window"], [0, 900, "bench/round"],
            [50, 800, "round/dispatch#round=2#"],
            [100, 500, "client/train#round=2,client=7,edge=0#"],
            [120, 300, "client/step"], [150, 200, "client/dispatch"],
            [160, 190, "DeferredTpuAllocator::Allocate"],
            [320, 480, "client/step"],
            [550, 700, "round/edge_agg#round=2,edge=0,clients=1#"],
            [820, 880, "round/cloud_agg#round=2#"]]
    return {"ops": ops, "spans": host}


def test_innermost_attribution_and_self_time():
    table = spans.program(round_trace(), (0, 1000))
    idle = {k: v["idle_s"] * 1e9 for k, v in table.items()}
    assert idle == pytest.approx({
        "round/dispatch": 190, "client/train": 50, "client/step": 170,
        "client/dispatch": 50, "round/edge_agg": 100,
        "round/cloud_agg": 50, "-": 90})
    own = {k: v["self_s"] * 1e9 for k, v in table.items()}
    assert own == pytest.approx({
        "round/dispatch": 200, "client/train": 60, "client/step": 290,
        "client/dispatch": 50, "round/edge_agg": 150,
        "round/cloud_agg": 60, "-": 190})
    assert table["client/step"]["n"] == 2
    assert table["client/step"]["host_s"] == pytest.approx(340e-9)
    assert table["round/dispatch"]["host_s"] == pytest.approx(750e-9)
    # the runtime event is no program span, and the bench/ spans are not
    assert set(table) == set(idle)
    assert sum(own.values()) == pytest.approx(1000)


def test_split_sums_to_idle_share():
    tr = round_trace()
    red = trace.reduce(tr, (0, 1000))
    idle_share = 100.0 * (1.0 - red["busy_s"] / red["window_s"])
    got = spans.split(tr, (0, 1000))
    assert got["idle_in_step"] == pytest.approx(27.0)
    assert got["idle_in_agg"] == pytest.approx(15.0)
    assert got["idle_in_other"] == pytest.approx(28.0)
    assert abs(got["idle_in_step"] + got["idle_in_agg"]
               + got["idle_in_other"] - idle_share) < 1e-9
    assert got["step_host_ms"] == pytest.approx(340e-9 / 2 * 1e3)
    assert got["agg_host_s"] == pytest.approx(210e-9)      # one round


def test_window_clips_spans():
    table = spans.program(round_trace(), (100, 600))
    assert table["round/dispatch"]["host_s"] == pytest.approx(500e-9)
    assert sum(v["self_s"] for v in table.values()) == pytest.approx(500e-9)
    assert sum(v["idle_s"] for v in table.values()) == pytest.approx(
        sum(b - a for a, b in spans.idle_holes(round_trace(), (100, 600)))
        / 1e9)


@pytest.mark.parametrize("name,want", [
    ("client/step", "client/step"),
    ("round/edge_agg#round=3,edge=1,clients=10#", "round/edge_agg"),
    ("bench/round", "bench/round"),
])
def test_span_name_drops_metadata(name, want):
    assert spans.span_name(name) == want


def test_nothing_without_program_spans():
    tr = round_trace()
    tr["spans"] = [s for s in tr["spans"]
                   if not spans.span_name(s[2]).startswith(spans.PROGRAM)]
    assert spans.split(tr, (0, 1000)) is None
    table = spans.program(tr, (0, 1000))
    assert list(table) == ["-"]
    assert table["-"]["idle_s"] == pytest.approx(700e-9)


def test_recorded_chip_trace_all_unattributed():
    """The trace recorded before the program's spans reached the
    profiler: no program span, every idle nanosecond under ``-``, the
    same idle time that ``trace.reduce`` gives."""
    with open(os.path.join(DATA, "trace_small.json")) as f:
        tr = json.load(f)
    win = trace.window_of(tr)
    red = trace.reduce(tr, win)
    assert spans.split(tr, win) is None
    table = spans.program(tr, win)
    assert table["-"]["idle_s"] == pytest.approx(
        red["window_s"] - red["busy_s"], rel=1e-12)
