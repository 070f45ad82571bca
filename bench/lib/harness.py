"""Finds what a cell needs by name and runs it once.

A cell of ``BENCHMARK.json`` names a configuration (its ``file``) and a
traffic mix (``bench/workloads/<traffic>.json``).  The traffic file
names its driver (``bench/drivers/<driver>.py``); each per-layer metric
is read by ``bench/metrics/<metric>.py``.  Adding a cell, a traffic mix
or a metric therefore adds files and entries, and edits none.

A driver module defines ``Cell(ctx)`` with ``setup()``, ``window(seconds)``
(returns the end-to-end readings by metric name and leaves what the
per-layer readers need in ``ctx.counters``), ``release()`` (drops the
program's state) and ``check()`` (returns ``[(name, value, limit)]``,
each a number that has to stay at or under its limit).
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Any, Dict, List, Optional

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


class BenchError(Exception):
    """The cell cannot run here (unknown name, no chip, no program)."""


def load_module(path: str, name: Optional[str] = None):
    if not os.path.isfile(path):
        raise BenchError(f"no file {path}")
    spec = importlib.util.spec_from_file_location(
        name or "bench_" + os.path.basename(path)[:-3].replace(".", "_")
        .replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_json(path: str) -> dict:
    if not os.path.isfile(path):
        raise BenchError(f"no file {path}")
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class CellSpec:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    bench_dir: str


def find_cell(name: str, root: str = ROOT) -> CellSpec:
    bench = read_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"unknown workload {name!r}; known: "
                         f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise BenchError(f"workload {name} names unknown config "
                         f"{w['config']!r}")
    config = read_json(os.path.join(root, configs[w["config"]]["file"]))
    bench_dir = os.path.join(root, bench["paths"][0])
    traffic = read_json(os.path.join(bench_dir, "workloads",
                                     w["traffic"] + ".json"))
    mine = lambda m: name in m.get("workloads", [name])
    e2e = [m for m in bench["end_to_end"] if mine(m)]
    e2e_names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (name in m["workloads"] if "workloads" in m
                 else m["moves"] in e2e_names)]
    return CellSpec(name, int(w["chips"]), config, traffic, e2e, layer,
                bench_dir)


def load_driver(cell: CellSpec):
    return load_module(os.path.join(cell.bench_dir, "drivers",
                                    cell.traffic["driver"] + ".py"))


def load_metric(cell: CellSpec, metric: str):
    return load_module(os.path.join(cell.bench_dir, "metrics",
                                    metric + ".py"))


@dataclasses.dataclass
class Context:
    """What a driver gets: the cell, the run's seed, and ``precision``
    (the configuration's, unless a control run asks for the program's
    lower-precision path).  ``timed`` is False where no window is timed
    (``bench/control.py``): set-up may then leave out warm-up.
    ``counters`` is filled by the driver for the per-layer readers."""
    cell: CellSpec
    seed: int
    precision: str = "fp32"
    timed: bool = True
    counters: Dict[str, Any] = dataclasses.field(default_factory=dict)
    log: Any = None

    def say(self, tag: str, **nums) -> None:
        if self.log is not None:
            self.log(f"[{tag}] " + " ".join(f"{k}={v}"
                                            for k, v in nums.items()))
