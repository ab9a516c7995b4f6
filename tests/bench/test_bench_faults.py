"""A whole run on the CPU past the chip check: sound, then with the served
path broken underneath, where ``correct`` has to come out false."""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import pytest

from bench import run
from bench_fixtures import fixture_tree  # noqa: F401

SECONDS = 2.0


def _run(reg, cell, seed=2**31 + 5):
    return run.run_cell(reg, cell, seed, SECONDS, traced=False)


def _answer_altered(d):
    """Every PD_1 birth shifted where the answer is produced."""
    return dataclasses.replace(
        d, birth=jnp.where(d.dim == 1, d.birth + 1.0, d.birth))


def _half_batch_dropped(d):
    """The second half of every batch comes back empty."""
    keep = jnp.arange(d.valid.shape[0]) < d.valid.shape[0] // 2
    return dataclasses.replace(d, valid=d.valid & keep[:, None])


@pytest.mark.parametrize("cell", ["tiny.trickle", "tiny.flood"])
def test_sound_run_is_correct_and_reports_its_metrics(fixture_tree, cell):
    out = _run(fixture_tree, cell)
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    if cell == "tiny.trickle":   # the open loop offers rate * seconds
        assert out["attempted"] == 120
    assert set(out["metrics"]) == {"graphs_per_s", "setup_s"}
    assert out["metrics"]["graphs_per_s"]["value"] > 0
    checks = out["checks"]
    assert checks["pd_mismatched"]["value"] == 0
    assert checks["checked"]["value"] >= checks["checked"]["min"] >= 1
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("fault", [_answer_altered, _half_batch_dropped])
def test_broken_path_is_not_correct(fixture_tree, monkeypatch, fault):
    from repro.core.api import TopoPlan

    execute = TopoPlan.execute
    monkeypatch.setattr(TopoPlan, "execute",
                        lambda self, g: fault(execute(self, g)))
    out = _run(fixture_tree, "tiny.flood")   # full batches: both faults show
    assert out["correct"] is False
    assert out["checks"]["pd_mismatched"]["value"] > 0


def test_lost_answers_are_not_correct(fixture_tree, monkeypatch):
    """Once the window opens, every other future is never resolved: late is
    not wrong, but an answer that never comes is."""
    from repro.serve.futures import ServeFuture

    resolve = ServeFuture._resolve
    open_window = run.Client.open_window
    seen = []

    def lossy(self, value):
        if seen:
            seen.append(1)
            if len(seen) % 2:
                return False
        return resolve(self, value)

    def opened(self, t0):
        seen.append(1)
        open_window(self, t0)

    monkeypatch.setattr(ServeFuture, "_resolve", lossy)
    monkeypatch.setattr(run.Client, "open_window", opened)
    monkeypatch.setattr(run, "WAIT_S", 1.0)
    out = _run(fixture_tree, "tiny.trickle")
    assert out["correct"] is False
    assert out["checks"]["unanswered"]["value"] > 0
    assert out["failed"] == out["checks"]["unanswered"]["value"]
