"""The reduction from a trace to busy time, idle gaps and GEMM time, on
a hand-made trace and on a slice recorded from one v5e."""
import json
import os

import pytest

from lib import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def hand_trace():
    # ns; two overlapping GEMMs, one other op, a host gap in between
    ops = [[100, 300, "fusion.1", "gemm"],
           [200, 400, "convolution.2", "gemm"],
           [600, 700, "fusion.3", "other"],
           [950, 1200, "fusion.1", "gemm"]]
    spans = [[0, 1000, "bench/window"], [0, 450, "bench/round"],
             [420, 580, "PjitFunction(step)"],
             [700, 1000, "bench/tick"], [720, 900, "ExecuteHelper"]]
    return {"ops": ops, "spans": spans}


def test_busy_union_and_idle_share():
    red = trace.reduce(hand_trace(), (0, 1000))
    # busy: [100, 400] + [600, 700] + [950, 1000] clipped to the window
    assert red["busy_s"] == pytest.approx(450e-9)
    assert red["window_s"] == pytest.approx(1000e-9)
    assert red["gemm_s"] == pytest.approx(350e-9)
    assert red["ops"] == 4


def test_gaps_labelled_with_host_spans():
    red = trace.reduce(hand_trace(), (0, 1000))
    gaps = dict(red["idle_gaps"])
    assert gaps["bench/round"] == pytest.approx(100e-9)              # 0-100
    assert gaps["bench/window > PjitFunction(step)"] == \
        pytest.approx(200e-9)                                         # 400-600
    assert gaps["bench/tick > ExecuteHelper"] == \
        pytest.approx(250e-9)                                         # 700-950
    assert sum(gaps.values()) == pytest.approx(550e-9)


def test_top_ops_by_time():
    red = trace.reduce(hand_trace(), (0, 1000))
    top = red["device_ops"]
    assert top[0][0] == "fusion.1"
    assert top[0][1] == pytest.approx(250e-9)    # 200 + 50 clipped
    assert len(top) <= 10


def test_window_span():
    assert trace.window_of(hand_trace()) == (0, 1000)
    assert trace.window_of({"ops": [], "spans": []}) is None


@pytest.mark.parametrize("name,stats,kind", [
    ("fusion.12", {"long_name": "%fusion.12 = f32[32,16,16,256] fusion("
                   "...), kind=kOutput, calls=%fused_convolution"}, "gemm"),
    ("convolution.3", {}, "gemm"),
    ("dot.7", {}, "gemm"),
    ("block_masked_matmul", {}, "gemm"),
    ("fusion.9", {"long_name": "%fusion.9 = f32[32] fusion(...), "
                  "kind=kLoop, calls=%fused_add"}, "other"),
    ("copy.1", {}, "other"),
])
def test_classify(name, stats, kind):
    assert trace.classify(name, stats) == kind


def test_recorded_chip_trace():
    with open(os.path.join(DATA, "trace_small.json")) as f:
        tr = json.load(f)
    win = trace.window_of(tr)
    assert win is not None
    red = trace.reduce(tr, win)
    assert red["ops"] > 0
    assert 0 < red["busy_s"] <= red["window_s"]
    assert 0 <= red["gemm_s"] <= red["busy_s"]
    idle = red["window_s"] - red["busy_s"]
    assert sum(v for _, v in red["idle_gaps"]) <= idle * (1 + 1e-9)
