"""Cells, configurations, traffic mixes and per-layer metrics are found
by name: a new one is new files plus BENCHMARK.json entries."""
import json
import os

import pytest

import tinyroot
from lib import harness

NEW_METRIC = '''"""A throwaway metric."""


def read(ctx, red):
    return 42.0 if ctx.counters.get("images") else None
'''


def test_cell_found_by_name(tmp_path):
    root = tinyroot.make(str(tmp_path))
    cell = harness.find_cell("tiny.train", root)
    assert cell.config["name"] == "tiny-unet"
    assert cell.traffic["driver"] == "train"
    assert [m["name"] for m in cell.end_to_end] == ["train_img_per_s",
                                                    "setup_s"]
    assert [m["name"] for m in cell.per_layer] == ["compile_s.train"]
    assert harness.load_driver(cell).Cell is not None
    with pytest.raises(harness.BenchError):
        harness.find_cell("tiny.nope", root)


def test_new_metric_is_a_new_file(tmp_path):
    entry = {"unit": "x", "better": "higher", "source": "host_clock",
             "layer": "trainer", "moves": "train_img_per_s",
             "workloads": ["tiny.train"]}
    root = tinyroot.make(str(tmp_path), extra_metrics={
        "answer.train": (NEW_METRIC, entry)})
    cell = harness.find_cell("tiny.train", root)
    assert "answer.train" in [m["name"] for m in cell.per_layer]
    mod = harness.load_metric(cell, "answer.train")
    ctx = harness.Context(cell=cell, seed=1)
    assert mod.read(ctx, None) is None          # nothing to read
    ctx.counters["images"] = 10
    assert mod.read(ctx, None) == 42.0


def test_new_cell_and_config_are_new_files(tmp_path):
    root = tinyroot.make(str(tmp_path), cells=("train",))
    bench_json = os.path.join(root, "BENCHMARK.json")
    with open(bench_json) as f:
        spec = json.load(f)
    conf = dict(tinyroot.TINY_CONFIG, name="tiny-unet-2", image_size=16)
    with open(os.path.join(root, "bench", "configs", "tiny-unet-2.json"),
              "w") as f:
        json.dump(conf, f)
    with open(os.path.join(root, "bench", "workloads", "small.json"),
              "w") as f:
        json.dump(dict(tinyroot.TRAFFIC["train"],
                       data={"dataset": tinyroot.DATA, "batch_size": 4}), f)
    spec["configs"].append({"name": "tiny-unet-2", "source": "test",
                            "file": "bench/configs/tiny-unet-2.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "tiny2.small", "config": "tiny-unet-2",
                              "traffic": "small", "chips": 1, "why": "t"})
    spec["end_to_end"].insert(0, {"name": "step_s", "unit": "s",
                                  "better": "lower", "bound": 0.1,
                                  "source": "host_clock",
                                  "workloads": ["tiny2.small"]})
    with open(bench_json, "w") as f:
        json.dump(spec, f)
    cell = harness.find_cell("tiny2.small", root)
    assert cell.config["image_size"] == 16
    assert cell.traffic["data"]["batch_size"] == 4
    assert [m["name"] for m in cell.end_to_end] == ["step_s", "setup_s"]
    # the cell that was there is untouched
    old = harness.find_cell("tiny.train", root)
    assert old.config["image_size"] == 8
    assert [m["name"] for m in old.end_to_end] == ["train_img_per_s",
                                                   "setup_s"]


def test_repo_benchmark_cells_resolve():
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    for w in spec["workloads"]:
        cell = harness.find_cell(w["name"])
        assert cell.end_to_end and cell.per_layer
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        harness.load_driver(cell)
        for m in cell.per_layer:
            harness.load_metric(cell, m["name"])
