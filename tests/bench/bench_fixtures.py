"""Fixture of the benchmark's tests: a copy of the benchmark with parts
added as files, the way a later change adds them."""
from __future__ import annotations

import json
import os
import shutil

import pytest

from bench.registry import Registry

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))


@pytest.fixture
def fixture_tree(tmp_path):
    """A copy of the benchmark with a new configuration, traffic mix,
    metric and frontend added as files, and a new cell as an entry."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        spec = json.load(fh)
    bench = tmp_path / "bench"
    shutil.copytree(os.path.join(ROOT, "bench"), bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    (bench / "configs" / "tiny.json").write_text(json.dumps({
        "name": "tiny", "source": "fixture", "frontend": "topo_fixture",
        "generator": "ws_dataset", "graph_seed": 3,
        "generator_params": {"graphs": 12, "copies": 1, "avg_vertices": 10,
                             "avg_edges": 18.6, "sigma": 0.2,
                             "min_vertices": 7, "max_vertices": 16,
                             "k_ring": 4, "p_rewire": 0.1},
        "serving": {"dim": 1, "method": "both", "sublevel": True,
                    "max_batch": 8, "pad_batch_to": 8,
                    "buckets": [[16, 64, 96]]},
        "exclude": {"max_vertices": 16, "max_edges": 64,
                    "max_triangles": 96},
        "check": {"dims": [1], "per_bucket": 16, "largest_per_bucket": 1},
        "reduced": [], "assumed": {}}))
    (bench / "traffic" / "trickle.json").write_text(json.dumps(
        {"kind": "poisson", "rate_graphs_per_s": 60, "sweep_s": 0.01}))
    (bench / "traffic" / "flood.json").write_text(json.dumps(
        {"kind": "backlog", "backlog_graphs": 64, "prime_s": 0.2,
         "sweep_s": 0.01}))
    (bench / "metrics" / "pool_graphs.tiny.py").write_text(
        "def read(run):\n    return float(len(run.records['due']))\n")
    (bench / "frontends" / "topo_fixture.py").write_text(
        "from bench.frontends.topo import Frontend  # noqa: F401\n")
    spec["configs"].append({"name": "tiny", "source": "fixture",
                            "file": "bench/configs/tiny.json",
                            "reduced": [], "why": "fixture"})
    spec["workloads"].append({"name": "tiny.trickle", "config": "tiny",
                              "traffic": "trickle", "chips": 1,
                              "why": "fixture"})
    spec["workloads"].append({"name": "tiny.flood", "config": "tiny",
                              "traffic": "flood", "chips": 1,
                              "why": "fixture"})
    spec["per_layer"].append({
        "name": "pool_graphs.tiny", "unit": "graphs", "better": "higher",
        "source": "host_clock", "layer": "fixture",
        "moves": "graphs_per_s", "workloads": ["tiny.trickle"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    return Registry(str(tmp_path), bench_dir=str(bench))


