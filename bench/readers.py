"""Arithmetic shared by the metric readers under ``bench/metrics/``.

Each reader takes the finished :class:`bench.run.Run` and returns a number,
or ``None`` where the run holds nothing to read (an untraced run for a
trace metric, no batch executed), and the metric is then left out.
"""
from __future__ import annotations

import numpy as np


def graphs_per_s(run):
    return run.summary["graphs_per_s"]


def latency_p95_ms(run):
    return run.summary.get("latency_p95_ms")


def latency_p50_ms(run):
    return run.summary.get("latency_p50_ms")


def setup_s(run):
    return run.setup_s


def admit_us(run):
    """Mean seconds inside ``submit`` of the requests sent in the window."""
    r = run.records
    sel = (r["sent"] >= run.t0) & (r["sent"] < run.t1)
    return float(np.mean(r["admit"][sel])) * 1e6 if sel.any() else None


def batch_fill(run):
    """Share of executed batch rows that carried a request."""
    c = run.counters_window
    rows = c["served"] + c["padded_rows"]
    return 100.0 * c["served"] / rows if rows else None


def host_ms_per_batch(run):
    """Host time packing a batch and resolving its futures, per batch."""
    if run.spans is None:
        return None
    host = sum(e - s for s, e, n in run.spans
               if n in ("serve.gather", "serve.resolve"))
    batches = sum(1 for _, _, n in run.spans if n == "serve.batch")
    return host / batches * 1e3 if batches else None


def device_ms_per_batch(run):
    """Device busy time per batch executed in the traced stretch."""
    if run.trace is None or not run.counters_traced["batches"]:
        return None
    return run.trace["busy_s"] / run.counters_traced["batches"] * 1e3


def device_idle_share(run):
    if run.trace is None:
        return None
    return 100.0 * run.trace["idle_share"]
