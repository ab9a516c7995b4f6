"""bench/run.py refuses to measure anywhere but on a listed TPU."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import types

import pytest

from bench import run

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
ARGS = ["--workload", "ego-ca-condmat.offline", "--seed", "3",
        "--seconds", "10", "--trace", "0"]


def _run(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, script] + ARGS, cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def _no_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if "correct" in json.loads(line):
                return False
        except (json.JSONDecodeError, TypeError):
            continue
    return True


def test_exits_nonzero_off_the_chip():
    p = _run(ROOT, os.path.join("bench", "run.py"))
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert _no_result(p.stdout)


def test_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for d in json.load(open(os.path.join(ROOT, "BENCHMARK.json")))["paths"]:
        shutil.copytree(os.path.join(ROOT, d), tmp_path / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(str(tmp_path), os.path.join("bench", "run.py"))
    assert p.returncode != 0
    assert _no_result(p.stdout)


def _fake_devices(monkeypatch, kind, n=1):
    import jax

    dev = types.SimpleNamespace(platform="tpu", device_kind=kind)
    monkeypatch.setattr(jax, "devices", lambda *a: [dev] * n)


def test_refuses_a_device_kind_missing_from_the_peaks(monkeypatch):
    _fake_devices(monkeypatch, "TPU v99 imaginary")
    with pytest.raises(SystemExit) as e:
        run.device_check(1)
    assert "peaks.json" in str(e.value.code)


def test_refuses_fewer_chips_than_the_cell_asks(monkeypatch):
    _fake_devices(monkeypatch, "TPU v5 lite", n=1)
    with pytest.raises(SystemExit):
        run.device_check(4)
    assert run.device_check(1)["kind"] == "TPU v5 lite"
