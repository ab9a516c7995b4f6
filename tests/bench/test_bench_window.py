"""Window arithmetic (bench/window.py) and the Poisson schedule."""
from __future__ import annotations

import importlib.util
import os

import numpy as np
import pytest

from bench import window

NAN = float("nan")


def _records():
    # window [10, 20): due, done, ok
    due = np.array([5.0, 10.0, 11.0, 12.0, 19.0, 19.5, 21.0])
    done = np.array([10.5, 10.2, 13.0, NAN, 25.0, 19.9, 21.5])
    ok = np.array([True, True, True, False, True, True, True])
    return due, done, ok


def test_completions_only_inside_the_window():
    due, done, ok = _records()
    # 10.5 (due before), 10.2, 13.0, 19.9 in; 25.0 and 21.5 out; failed out
    assert window.completed_in(done, ok, 10, 20) == 4


def test_latency_from_due_includes_answers_after_close():
    due, done, ok = _records()
    lat = window.latencies(due, done, ok, 10, 20)
    assert sorted(lat.tolist()) == pytest.approx([0.2, 0.4, 2.0, 6.0])


def test_summary_counts_failures_and_takes_p95_over_all_due():
    due, done, ok = _records()
    s = window.summarize(due, done, ok, 10, 20)
    assert s["due"] == 5 and s["failed"] == 1 and s["completed"] == 4
    assert s["graphs_per_s"] == pytest.approx(0.4)
    lat = np.array([0.2, 2.0, 6.0, 0.4])
    assert s["latency_p95_ms"] == pytest.approx(np.percentile(lat, 95) * 1e3)
    assert s["latency_p50_ms"] == pytest.approx(np.percentile(lat, 50) * 1e3)


def _poisson():
    path = os.path.join(os.path.dirname(__file__), "..", "..", "bench",
                        "drivers", "poisson.py")
    spec = importlib.util.spec_from_file_location("poisson_driver", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_poisson_schedule_same_work_for_every_seed():
    p = _poisson()
    a = p.schedule(500.0, 10.0, 2**31 + 7)
    b = p.schedule(500.0, 10.0, 3)
    assert np.all(np.diff(a) >= 0) and a[0] == 0.0 and a[-1] < 10.0
    # the same gaps in another order: the same count within the tail
    assert abs(len(a) - len(b)) <= 3 and abs(len(a) - 5000) <= 3
    assert not np.array_equal(a[:50], b[:50])
    gaps = np.diff(a)
    assert gaps.mean() == pytest.approx(1 / 500.0, rel=0.01)
    # exponential: the coefficient of variation is about 1
    assert gaps.std() / gaps.mean() == pytest.approx(1.0, abs=0.05)
    assert np.array_equal(a, p.schedule(500.0, 10.0, 2**31 + 7))
