"""Everything a cell needs, found by name under the benchmark's directory.

* ``BENCHMARK.json`` at the checkout's root: cells and metrics;
* ``bench/configs/<config>.json``: one deployment; its ``generator`` names
  ``bench/gen/<generator>.py`` and its ``frontend`` names
  ``bench/frontends/<frontend>.py``;
* ``bench/traffic/<traffic>.json``: one mix; its ``kind`` names
  ``bench/drivers/<kind>.py``;
* ``bench/metrics/<metric>.py``: one reader per metric, ``read(run)``.

A later change adds a file and an entry; it edits none of these.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))


def _module(path: str, name: str):
    if not os.path.isfile(path):
        raise FileNotFoundError(path)
    key = "bench_dyn." + name.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(key, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[key] = mod
    spec.loader.exec_module(mod)
    return mod


def _json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict
    traffic: dict
    end_to_end: list      # metric entries of BENCHMARK.json this cell reports
    per_layer: list


class Registry:
    def __init__(self, root: str, bench_dir: str = BENCH_DIR):
        self.root = root
        self.bench_dir = bench_dir
        self.spec = _json(os.path.join(root, "BENCHMARK.json"))

    def cell(self, name: str) -> Cell:
        try:
            w = next(w for w in self.spec["workloads"] if w["name"] == name)
        except StopIteration:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json") from None
        cfg_entry = next(c for c in self.spec["configs"]
                         if c["name"] == w["config"])
        e2e = [m for m in self.spec["end_to_end"]
               if name in m.get("workloads", [name])]
        moved = {m["name"] for m in e2e}
        per_layer = [m for m in self.spec["per_layer"]
                     if name in m.get("workloads", [name])
                     and m["moves"] in moved]
        return Cell(
            name=name, chips=int(w["chips"]),
            config_name=w["config"], traffic_name=w["traffic"],
            config=_json(os.path.join(self.root, cfg_entry["file"])),
            traffic=self.traffic(w["traffic"]),
            end_to_end=e2e, per_layer=per_layer)

    def traffic(self, name: str) -> dict:
        return _json(os.path.join(self.bench_dir, "traffic", name + ".json"))

    def generator(self, name: str):
        return _module(os.path.join(self.bench_dir, "gen", name + ".py"),
                       "gen." + name)

    def frontend(self, name: str):
        return _module(os.path.join(self.bench_dir, "frontends",
                                    name + ".py"), "frontends." + name)

    def driver(self, kind: str):
        return _module(os.path.join(self.bench_dir, "drivers", kind + ".py"),
                       "drivers." + kind)

    def metric(self, name: str):
        return _module(os.path.join(self.bench_dir, "metrics", name + ".py"),
                       "metrics." + name)
