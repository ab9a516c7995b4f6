"""A traced run of one cell, with its device time split by plan phase.

    python bench/breakdown.py --workload <cell> --seed <n> --seconds <s>

The same run as ``bench/run.py ... --trace 1`` (same device check, set-up,
window, profiled stretch, metrics and check; the same result line last on
standard output), with a ``[bench] phases`` line before it that reads the
profiled stretch by the program's own names (``bench/attribution.py``):

* ``reduce_ms_per_batch``, ``persist_ms_per_batch``: device time under the
  ``plan.reduce`` / ``plan.persist`` scopes per batch; ``unscoped_ms_per
  _batch``, the plans' ops in neither scope; ``other_ms_per_batch``, the
  other programs (the eager pad, slice and squeeze programs); the four add
  up to ``device_ms_per_batch``;
* ``idle_in_batch_share``: % of the stretch in which the device was idle
  while the serving thread was inside a ``serve.batch`` span, beside
  ``device_idle_share``;
* ``programs_per_batch``: device program executions per batch, by module;
* ``buckets``: per bucket, over the window, the batches, mean graphs per
  batch and mean ``queued`` (requests left in the bucket's queue when the
  batch was cut), from the ``serve.batch`` spans.

The benchmark's command never runs this; it is how the split is read until
the harness's own trace reduction keeps the raw trace.  Each plan serves one
batch shape here (``max_batch = pad_batch_to``), since instruction names are
read from the compiled program of that shape.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import attribution as at  # noqa: E402
from bench import run  # noqa: E402
from bench.registry import Registry  # noqa: E402


def plan_scopes(server) -> dict:
    """Module name -> :func:`bench.attribution.op_scopes` of the compiled
    program of every bucket's plan, at the server's one batch shape."""
    import jax
    import jax.numpy as jnp

    from repro.core.graph import GraphBatch

    c = server.config
    if c.max_batch != c.pad_batch_to:
        raise ValueError("one batch shape per plan needs max_batch == "
                         "pad_batch_to")
    out = {}
    for b in c.buckets:
        n, rows = b.n_pad, c.max_batch
        g = GraphBatch(adj=jax.ShapeDtypeStruct((rows, n, n), jnp.bool_),
                       mask=jax.ShapeDtypeStruct((rows, n), jnp.bool_),
                       f=jax.ShapeDtypeStruct((rows, n), jnp.float32))
        text = server.plan_for(b).executor.lower(g).compile().as_text()
        name = text.split(None, 2)[1].rstrip(",")   # "HloModule <name>, ..."
        out[name] = at.op_scopes(text)
    return out


def batch_spans(t0: float, t1: float) -> list:
    """(start on ``perf_counter``, args) of every ``serve.batch`` span that
    starts in ``[t0, t1)``; the span clock's offset is read as
    ``bench/run.py`` reads it."""
    from repro import obs

    mark = time.perf_counter()
    with obs.span("bench.clock"):
        pass
    events = obs.trace_events()
    ref = next(e for e in reversed(events) if e["name"] == "bench.clock")
    offset = mark - ref["ts"] * 1e-6
    return [(offset + e["ts"] * 1e-6, e["args"]) for e in events
            if e["name"] == "serve.batch"
            and t0 <= offset + e["ts"] * 1e-6 < t1]


def bucket_table(spans) -> dict:
    rows: dict = {}
    for _, a in spans:
        rows.setdefault(a["bucket"], []).append(a)
    out = {}
    for b, rs in sorted(rows.items()):
        queued = [r["queued"] for r in rs if "queued" in r]
        out[b] = {"batches": len(rs),
                  "graphs_mean": sum(r["graphs"] for r in rs) / len(rs),
                  "queued_mean": (sum(queued) / len(queued)
                                  if queued else None)}
    return out


def phase_tracer(server):
    """``bench.run.Tracer`` that also reduces the raw trace by phase before
    the harness deletes it; the class keeps the last run's ``phases`` and
    window start ``t0``."""

    class PhaseTracer(run.Tracer):
        phases: dict = {}
        t0 = None

        def summary(self) -> dict:
            from bench import trace as tr

            t = tr.read_xplane(self.dir)
            w0, w1 = tr.window_of(t["host"], "bench.window")
            dev = at.read_modules(self.dir)
            split = at.phase_split(dev["modules"], dev["ops"],
                                   plan_scopes(server), w0, w1)
            busy = tr.clip(dev["ops"], w0, w1)
            batch = [e for e in t["host"] if e[2] == "serve.batch"]
            n = self.counters["batches"] or float("nan")
            window = w1 - w0
            PhaseTracer.t0 = self.client.t0
            PhaseTracer.phases = {
                **{f"{k.split('.')[-1]}_ms_per_batch": v * 1e-6 / n
                   for k, v in split.items()},
                "device_ms_per_batch": tr.union_length(busy) * 1e-6 / n,
                "idle_in_batch_share":
                    100.0 * at.idle_in(busy, batch, w0, w1) / window,
                "device_idle_share":
                    100.0 * (1 - tr.union_length(busy) / window),
                "batches": n,
                "programs_per_batch": {
                    m: k / n for m, k in sorted(
                        at.executions(dev["modules"], w0, w1).items())},
            }
            return super().summary()

    return PhaseTracer


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    reg = Registry(ROOT)
    cell = reg.cell(args.workload)
    dev = run.device_check(cell.chips)
    run.log("device", **dev)

    import jax
    jax.config.update("jax_compilation_cache_dir", run.CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    cfg = cell.config
    fe = reg.frontend(cfg["frontend"]).Frontend(cfg["serving"])
    tracer = run.Tracer = phase_tracer(fe.server)
    out = run.run_cell(reg, args.workload, args.seed, args.seconds, True,
                       frontend=fe)
    t0 = tracer.t0
    run.log("phases", **tracer.phases,
            buckets=bucket_table(batch_spans(t0, t0 + args.seconds)))
    out["device"] = {"platform": dev["platform"], "kind": dev["kind"],
                     "count": dev["count"], **out["device"]}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
