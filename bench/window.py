"""Window arithmetic: what a run reports from its request records.

Every request carries the time it was due (``due``; for a closed loop, the
time it was submitted) and the time its answer came (``done``, NaN when it
never came), both on ``time.perf_counter``.  The window is ``[t0, t1)``.

* ``graphs_per_s``: answers that came inside the window, over its length.
  Failed requests do not count.
* latency: ``done - due`` of every request due inside the window, those
  answered after it closed included; one that never came has no latency
  and is counted as unanswered instead.
"""
from __future__ import annotations

import numpy as np


def completed_in(done: np.ndarray, ok: np.ndarray, t0: float,
                 t1: float) -> int:
    return int(np.sum(ok & (done >= t0) & (done < t1)))


def due_in(due: np.ndarray, t0: float, t1: float) -> np.ndarray:
    return (due >= t0) & (due < t1)


def latencies(due: np.ndarray, done: np.ndarray, ok: np.ndarray, t0: float,
              t1: float) -> np.ndarray:
    """Seconds from due to answer, of the answered requests due in the
    window."""
    sel = due_in(due, t0, t1) & ok & np.isfinite(done)
    return done[sel] - due[sel]


def summarize(due, done, ok, t0: float, t1: float) -> dict:
    due, done, ok = (np.asarray(x) for x in (due, done, ok))
    lat = latencies(due, done, ok, t0, t1)
    n_due = int(np.sum(due_in(due, t0, t1)))
    out = {
        "window_s": t1 - t0,
        "completed": completed_in(done, ok, t0, t1),
        "due": n_due,
        "failed": n_due - len(lat),
    }
    out["graphs_per_s"] = out["completed"] / (t1 - t0)
    if len(lat):
        out["latency_p50_ms"] = float(np.percentile(lat, 50)) * 1e3
        out["latency_p95_ms"] = float(np.percentile(lat, 95)) * 1e3
        out["beyond_p95"] = int(np.sum(lat > np.percentile(lat, 95)))
    return out
