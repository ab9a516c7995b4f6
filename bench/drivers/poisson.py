"""Open loop: arrivals at ``rate_graphs_per_s`` on a seeded schedule.

The gaps are the ``N = rate * seconds`` quantiles of the exponential law,
``-ln(1 - (j + 1/2) / N) / rate``, in an order drawn from the seed: every
seed offers the same amounts of work at the same mean rate, in another
order.  Each request is due at its scheduled time and is timed from then;
how late the client actually submitted goes to the report.
"""
from __future__ import annotations

import time

import numpy as np


def schedule(rate: float, seconds: float, seed: int) -> np.ndarray:
    """Offsets from the window's start of every arrival due in it."""
    n = max(1, int(round(rate * seconds)))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    gaps = np.random.default_rng([seed, 0x9015]).permutation(gaps)
    offsets = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return offsets[offsets < seconds]


def run(client, params: dict, seconds: float, seed: int) -> tuple:
    rate = float(params["rate_graphs_per_s"])
    sweep_s = float(params["sweep_s"])
    offsets = schedule(rate, seconds, seed)
    t0 = time.perf_counter() + 0.05
    due = t0 + offsets
    client.open_window(t0)
    pending_at_open = client.frontend.pending()
    last_sweep = t0
    for d in due:
        now = time.perf_counter()
        if d - now > 0.002 and now - last_sweep > sweep_s:
            client.sweep()
            last_sweep = time.perf_counter()
            now = last_sweep
        if d > now:
            time.sleep(d - now)
        client.submit(float(d))
    t1 = t0 + seconds
    left = t1 - time.perf_counter()
    if left > 0:
        time.sleep(left)
    pending_at_close = client.frontend.pending()
    late = np.asarray(client.sent[-len(due):]) - due
    return t0, t1, {"offered": len(due),
                    "rate_graphs_per_s": rate,
                    "late_p95_ms": float(np.percentile(late, 95)) * 1e3,
                    "late_max_ms": float(late.max()) * 1e3,
                    "pending_at_open": pending_at_open,
                    "pending_at_close": pending_at_close}
