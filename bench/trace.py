"""Reduction of a profiler trace to device busy time, idle gaps and ops.

``read_xplane`` turns the ``.xplane.pb`` that ``jax.profiler`` writes into
plain interval lists, in nanoseconds on the trace's one clock:

* ``device``: per device plane (``/device:TPU:0``, ...), the events of its
  ``XLA Ops`` line (every line of the plane where that line is absent);
* ``host``: the events of every line of the ``/host:CPU`` plane, among them
  the benchmark's own ``jax.profiler.TraceAnnotation`` spans.

The functions below work on those lists alone, so a test can feed them a
synthetic trace.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict

Interval = tuple  # (start_ns, end_ns, name)


def read_xplane(trace_dir: str) -> dict:
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    device: dict[str, list] = {}
    host: list = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = list(plane.lines)
            ops = [ln for ln in lines if ln.name == "XLA Ops"] or lines
            device[plane.name] = [
                (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                for ln in ops for ev in ln.events]
        elif plane.name == "/host:CPU":
            host.extend((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                        for ln in plane.lines for ev in ln.events)
    return {"device": device, "host": host}


def clip(intervals, t0: float, t1: float) -> list:
    out = []
    for s, e, name in intervals:
        s, e = max(s, t0), min(e, t1)
        if e > s:
            out.append((s, e, name))
    return out


def union_length(intervals) -> float:
    """Length of the union of the intervals."""
    total, end = 0.0, None
    start = None
    for s, e, _ in sorted(intervals):
        if end is None or s > end:
            if end is not None:
                total += end - start
            start, end = s, e
        else:
            end = max(end, e)
    if end is not None:
        total += end - start
    return total


def gaps(intervals, t0: float, t1: float) -> list:
    """Idle stretches of ``[t0, t1)`` that no interval covers, longest
    first, as (start, end)."""
    out, at = [], t0
    for s, e, _ in sorted(clip(intervals, t0, t1)):
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if t1 > at:
        out.append((at, t1))
    return sorted(out, key=lambda g: g[0] - g[1])


def overlap_by_name(host, start: float, end: float) -> list:
    """Host event names and how long each overlaps ``[start, end)``,
    largest first."""
    acc: dict[str, float] = defaultdict(float)
    for s, e, name in clip(host, start, end):
        acc[name] += e - s
    return sorted(acc.items(), key=lambda kv: -kv[1])


def op_name(name: str) -> str:
    """An HLO instruction's name without its text: ``%while.12 = (...)
    while(...)`` reads ``%while.12``."""
    return name.split(" = ", 1)[0] if name.startswith("%") else name


def window_of(host, name: str):
    """(start, end) of the first host event called ``name``."""
    for s, e, n in host:
        if n == name:
            return s, e
    raise KeyError(f"no host event {name!r} in the trace")


def summarize(trace: dict, t0: float, t1: float, top: int = 10) -> dict:
    """Busy and idle time of the devices in ``[t0, t1)``, averaged over the
    device planes that ran an op in it, with the ops that took most time and
    the longest idle gaps of the first such device, each named by the host
    events inside it."""
    inside = {p: clip(ev, t0, t1) for p, ev in trace["device"].items()}
    planes = sorted(p for p, ev in inside.items() if ev)
    if not planes:
        raise ValueError("the trace holds no device op inside the window")
    window = t1 - t0
    busy = [union_length(inside[p]) for p in planes]
    first = inside[planes[0]]
    per_op: dict[str, float] = defaultdict(float)
    for s, e, name in first:
        per_op[op_name(name)] += e - s
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    named_gaps = []
    for s, e in gaps(first, t0, t1)[:top]:
        host = overlap_by_name(trace["host"], s, e)[:3]
        label = ", ".join(n for n, _ in host) or "no host event"
        named_gaps.append([label, (e - s) * 1e-9])
    busy_s = sum(busy) / len(busy) * 1e-9
    return {
        "busy_s": busy_s,
        "window_s": window * 1e-9,
        "idle_share": 1.0 - busy_s / (window * 1e-9),
        "device_ops": [[n, d * 1e-9] for n, d in ops],
        "idle_gaps": named_gaps,
        "device_events": len(first),
        "device_planes": planes,
    }
