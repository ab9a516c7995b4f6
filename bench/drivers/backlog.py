"""Closed loop at a fixed depth: keep ``backlog_graphs`` requests pending.

Before the window the backlog is filled and served for ``prime_s`` seconds,
so the window opens on a server in its steady state.  In the window the
client tops the backlog up, in the pool's seeded order, each time it reads
the answers that have come (every ``sweep_s`` seconds).  A request is due
when it is submitted.
"""
from __future__ import annotations

import time


def _top_up(client, depth: int) -> None:
    client.sweep()
    for _ in range(depth - len(client.open)):
        client.submit(time.perf_counter())


def run(client, params: dict, seconds: float, seed: int) -> tuple:
    """Drive the window; returns (t0, t1, facts for the report)."""
    depth = int(params["backlog_graphs"])
    sweep_s = float(params["sweep_s"])
    _top_up(client, depth)
    end = time.perf_counter() + float(params["prime_s"])
    while time.perf_counter() < end:
        time.sleep(sweep_s)
        _top_up(client, depth)
    t0 = time.perf_counter()
    client.open_window(t0)
    t1 = t0 + seconds
    while True:
        now = time.perf_counter()
        if now >= t1:
            break
        time.sleep(min(sweep_s, t1 - now))
        if time.perf_counter() < t1:
            _top_up(client, depth)
    return t0, t1, {"backlog_graphs": depth}
