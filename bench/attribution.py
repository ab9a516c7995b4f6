"""Device time, idle time and waiting, attributed to the program's own names.

The program names what the harness's trace reduction (``bench/trace.py``)
sees only as anonymous ops and host events:

* each plan's jitted program is the HLO module ``jit_topo_plan_...``, and
  its ops carry ``op_name`` metadata under the named scopes ``plan.reduce``
  and ``plan.persist``.  A TPU trace gives an op only its instruction name
  (``%while.12``), so :func:`op_scopes` reads the scope from the module's
  compiled HLO text and :func:`phase_split` charges each op to the module
  execution (``XLA Modules`` line) that encloses it;
* while tracing is on, every ``obs`` span is a profiler annotation on the
  host plane, so :func:`idle_in` can say how much of the device's idle time
  fell inside a ``serve.batch``;
* the ``serve.batch`` span that answers a request opens when the drain has
  picked it up, so :func:`queue_waits` can time each request's wait in the
  queue from the client's records.

Times are nanoseconds on the trace's clock, except the queue wait, which
is seconds on ``time.perf_counter``.  The functions take plain lists, so a
test can feed them a synthetic trace.
"""
from __future__ import annotations

import glob
import os
import re
from collections import Counter

import numpy as np

from bench.trace import clip, op_name, union_length

PHASES = ("plan.reduce", "plan.persist")
_INSTR = re.compile(r'^\s*(?:ROOT\s+)?(%[\w.\-]+) = .*?op_name="([^"]*)"')


def read_modules(trace_dir: str, plane: str = "/device:TPU:0") -> dict:
    """One device plane of a recorded trace: ``modules``, the program
    executions of its ``XLA Modules`` line, and ``ops``, the events of its
    ``XLA Ops`` line, each as (start_ns, end_ns, name)."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    out = {"modules": [], "ops": []}
    for p in ProfileData.from_file(paths[-1]).planes:
        if p.name != plane:
            continue
        for ln in p.lines:
            key = {"XLA Modules": "modules", "XLA Ops": "ops"}.get(ln.name)
            if key:
                out[key].extend((ev.start_ns, ev.start_ns + ev.duration_ns,
                                 ev.name) for ev in ln.events)
    return out


def module_name(event_name: str) -> str:
    """``jit_topo_plan_prunit_e64_t96_d1(1778...)`` reads
    ``jit_topo_plan_prunit_e64_t96_d1``."""
    return event_name.split("(", 1)[0]


def op_scopes(hlo_text: str, scopes=PHASES) -> dict:
    """Instruction name -> the first of ``scopes`` its ``op_name`` metadata
    lies under, for every instruction of a compiled module that has one."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        path = m.group(2).split("/")
        for s in scopes:
            if s in path:
                out[m.group(1)] = s
                break
    return out


def phase_split(modules, ops, scopes_by_module: dict, t0: float,
                t1: float) -> dict:
    """Device time in ``[t0, t1)`` by phase, each the union length of its
    ops, so ops nested in a ``while`` count once: one entry per scope;
    ``unscoped``, the rest of the plans' programs (ops without a scope that
    no scoped op encloses, such as layout copies of the inputs); ``other``,
    the ops of every other program.  The entries add up to the busy time.
    ``scopes_by_module`` maps each plan's module name to :func:`op_scopes`
    of its compiled HLO; an op is charged to the module execution (an
    interval of ``modules``) that holds its start."""
    mods = sorted((s, e, module_name(n)) for s, e, n in clip(modules, t0, t1))
    starts = np.asarray([s for s, _, _ in mods])
    per: dict = {s: [] for s in PHASES}
    plan_ops = []
    inside = clip(ops, t0, t1)
    for s, e, name in inside:
        i = int(np.searchsorted(starts, s, side="right")) - 1
        if i < 0 or s >= mods[i][1] or mods[i][2] not in scopes_by_module:
            continue
        plan_ops.append((s, e, name))
        scope = scopes_by_module[mods[i][2]].get(op_name(name))
        if scope is not None:
            per[scope].append((s, e, scope))
    out = {s: union_length(v) for s, v in per.items()}
    scoped = union_length([iv for v in per.values() for iv in v])
    in_plans = union_length(plan_ops)
    out["unscoped"] = in_plans - scoped
    out["other"] = union_length(inside) - in_plans
    return out


def executions(modules, t0: float, t1: float) -> dict:
    """Program executions that start in ``[t0, t1)``, by module name."""
    return dict(Counter(module_name(n) for s, _, n in modules
                        if t0 <= s < t1))


def idle_in(busy, spans, t0: float, t1: float) -> float:
    """Length of the device's idle time in ``[t0, t1)`` (no interval of
    ``busy`` covers it) that some interval of ``spans`` covers."""
    busy = clip(busy, t0, t1)
    return union_length(busy + clip(spans, t0, t1)) - union_length(busy)


def queue_waits(records: dict, batches, t0: float, t1: float) -> np.ndarray:
    """Seconds each answered request due in ``[t0, t1)`` waited in the
    queue: from the end of its ``submit`` call (``sent + admit``) to the
    start of the ``serve.batch`` span whose interval holds its answer.
    ``batches`` are that span's (start, end) on ``perf_counter``; they do
    not overlap, since one thread drains.  A request answered outside
    every batch is left out."""
    due, done, ok = records["due"], records["done"], records["ok"]
    sel = (due >= t0) & (due < t1) & ok & np.isfinite(done)
    if not batches:
        return np.zeros(0)
    b = np.asarray(sorted(batches), dtype=float)
    i = np.searchsorted(b[:, 0], done[sel], side="right") - 1
    hit = (i >= 0) & (done[sel] <= b[np.maximum(i, 0), 1])
    submitted = (records["sent"] + records["admit"])[sel]
    return b[i[hit], 0] - submitted[hit]


def queue_wait_p95_ms(run):
    """p95 of :func:`queue_waits` over the window, in ms (traced runs)."""
    if run.spans is None:
        return None
    from bench.run import _obs_spans

    # answers due in the window may come from batches after its close
    batches = [(s, e) for s, e, n in _obs_spans(run.t0, float("inf"))
               if n == "serve.batch"]
    w = queue_waits(run.records, batches, run.t0, run.t1)
    return float(np.percentile(w, 95)) * 1e3 if len(w) else None
