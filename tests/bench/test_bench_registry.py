"""BENCHMARK.json and discovery of the benchmark's parts by name."""
from __future__ import annotations

import json
import os
import re

import pytest

from bench.registry import Registry
from bench_fixtures import fixture_tree  # noqa: F401

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


def test_every_cell_resolves_to_its_files():
    reg = Registry(ROOT)
    spec = _spec()
    for w in spec["workloads"]:
        cell = reg.cell(w["name"])
        assert cell.chips == 1
        assert cell.config["name"] == w["config"]
        reg.generator(cell.config["generator"])
        reg.frontend(cell.config["frontend"])
        reg.driver(cell.traffic["kind"])
        assert {m["name"] for m in cell.end_to_end} >= {"graphs_per_s",
                                                        "setup_s"}
        assert cell.per_layer
        for m in cell.end_to_end + cell.per_layer:
            assert callable(reg.metric(m["name"]).read)


def test_benchmark_json_keeps_its_rules():
    spec = _spec()
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    names = ([c["name"] for c in spec["configs"]]
             + [w["name"] for w in spec["workloads"]]
             + [m["name"] for m in spec["end_to_end"] + spec["per_layer"]])
    assert all(NAME.match(n) for n in names)
    assert len(names) == len(set(names))
    e2e = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in e2e
    for m in spec["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    cells = {w["name"] for w in spec["workloads"]}
    for m in spec["per_layer"]:
        assert m["moves"] in e2e
        assert set(m["workloads"]) <= cells
    for c in spec["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        assert c["file"].startswith(tuple(p + "/" for p in spec["paths"]))


def test_peaks_table_names_its_source():
    with open(os.path.join(ROOT, "bench", "peaks.json")) as fh:
        peaks = json.load(fh)
    assert "TPU v5e" in peaks["source"]
    assert peaks["devices"]["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9


def test_new_parts_are_found_by_name(fixture_tree):
    reg = fixture_tree
    cell = reg.cell("tiny.trickle")
    assert cell.config["frontend"] == "topo_fixture"
    assert cell.traffic == {"kind": "poisson", "rate_graphs_per_s": 60,
                            "sweep_s": 0.01}
    assert [m["name"] for m in cell.per_layer] == ["pool_graphs.tiny"]
    assert {m["name"] for m in cell.end_to_end} == {"graphs_per_s",
                                                    "setup_s"}
    assert reg.frontend("topo_fixture").Frontend
    assert reg.driver("poisson").schedule
    # the cells already there are untouched by the addition
    assert reg.cell("proteins.steady").traffic_name == "poisson-proteins"
    with pytest.raises(KeyError):
        reg.cell("no.such.cell")
    with pytest.raises(FileNotFoundError):
        reg.metric("no_such_metric")
