"""Run one benchmark cell on the chip and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process: it checks that JAX sees a TPU listed in ``bench/peaks.json``
(and as many chips as the cell asks for) before any work, builds the cell's
traffic from the seed, starts the server's own loop on a thread, warms up
every shape the traffic uses, then measures for ``--seconds`` seconds with
a client that submits and reads futures.  After the window it waits for
every answer due in it, reads the device's peak memory, stops the server
and compares a seeded sample of the answers with the plain reference
(``bench/reference.py``).  Earlier lines on standard output report the
device, the pool, the set-up and the window; the compared numbers are the
last lines on standard error; the last line on standard output is the
result as JSON.  ``--trace 1`` measures the same window with the profiler
on for a few seconds in its middle and reports the per-layer metrics.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402

import numpy as np  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import reference, window  # noqa: E402
from bench.client import Client  # noqa: E402
from bench.pool import Stream, apply_caps, largest_per_bucket  # noqa: E402
from bench.registry import BENCH_DIR, Registry  # noqa: E402

TRACE_S = 3.0        # length of the profiled stretch in a --trace 1 run
WAIT_S = 60.0        # how long answers due in the window are awaited
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def log(what: str, **kv) -> None:
    print(f"[bench] {what} " + json.dumps(kv, default=float), flush=True)


class CompileWatch:
    """Backend compiles (a program loaded from the persistent cache counts
    too) and persistent-cache hits and misses, over the process."""

    _shared = None

    @classmethod
    def shared(cls) -> "CompileWatch":
        """The process's one watch: JAX's listeners cannot be removed."""
        if cls._shared is None:
            cls._shared = cls()
        return cls._shared

    def __init__(self):
        import jax.monitoring as mon

        self.n = {"compiles": 0, "compile_s": 0.0, "cache_hits": 0,
                  "cache_misses": 0}
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n["compiles"] += 1
            self.n["compile_s"] += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.n["cache_hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.n["cache_misses"] += 1

    def snap(self) -> dict:
        return dict(self.n)


def _delta(a: dict, b: dict) -> dict:
    return {k: b[k] - a[k] for k in a}


@dataclasses.dataclass
class Run:
    """What the metric readers see of a finished run."""

    cell: str
    t0: float
    t1: float
    setup_s: float
    records: dict
    summary: dict
    counters_window: dict
    spans: list | None = None
    trace: dict | None = None
    counters_traced: dict | None = None


class Tracer:
    """Profiles ``TRACE_S`` seconds in the middle of the window from a
    thread of its own, so the client never waits for the profiler."""

    def __init__(self, frontend, client, seconds: float):
        self.frontend = frontend
        self.client = client
        self.offset = max(0.0, (seconds - TRACE_S) / 2)
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        self.bounds = None
        self.counters = None
        self.error = None
        self.thread = threading.Thread(target=self._run, name="tracer")

    def _run(self):
        import jax

        try:
            while self.client.t0 is None:      # the traffic mix opens the window
                time.sleep(0.005)
            start = self.client.t0 + self.offset
            time.sleep(max(0.0, start - time.perf_counter()))
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0   # Python call events slow the host
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            try:
                c0 = self.frontend.counters()
                a = time.perf_counter()
                with jax.profiler.TraceAnnotation("bench.window"):
                    time.sleep(TRACE_S)
                b = time.perf_counter()
                c1 = self.frontend.counters()
            finally:
                jax.profiler.stop_trace()
            self.bounds = (a, b)
            self.counters = _delta(c0, c1)
        except Exception as e:  # reported; the run's metrics go without it
            self.error = repr(e)

    def summary(self) -> dict:
        from bench import trace as tr

        t = tr.read_xplane(self.dir)
        w0, w1 = tr.window_of(t["host"], "bench.window")
        # the marker spans every gap: name the gaps by what else ran
        t["host"] = [e for e in t["host"] if e[2] != "bench.window"]
        out = tr.summarize(t, w0, w1)
        shutil.rmtree(self.dir, ignore_errors=True)
        return out


def _obs_spans(t0: float, t1: float) -> list:
    """The program's spans inside [t0, t1), as (start, end, name) on
    ``perf_counter``; the offset of the span clock is read from a span
    opened here at a known time."""
    from repro import obs

    mark = time.perf_counter()
    with obs.span("bench.clock"):
        pass
    events = obs.trace_events()
    ref = next(e for e in reversed(events) if e["name"] == "bench.clock")
    offset = mark - ref["ts"] * 1e-6
    out = []
    for e in events:
        s = offset + e["ts"] * 1e-6
        if t0 <= s < t1:
            out.append((s, s + e["dur"] * 1e-6, e["name"]))
    return out


def check(frontend, pool, kept: list, dims, min_checked: int,
          unanswered: int, compiles_in_window: int) -> dict:
    """Compare every kept answer, in the dimensions the configuration
    guarantees, with the reference's diagram of its pool graph; returns the
    compared numbers, each with its limit.  A program compiled or loaded
    inside the window fails the run: the warm-up missed a shape."""
    served = [(i, frontend.diagram(a, dims)) for i, a in kept]
    kept.clear()
    want = {}
    mismatched = 0
    for i, got in served:
        if i not in want:
            g = pool[i]
            want[i] = reference.diagram(g.n, g.edges, g.f, dims)
        mismatched += got != want[i]
    return {"pd_mismatched": {"value": int(mismatched), "max": 0},
            "unanswered": {"value": int(unanswered), "max": 0},
            "checked": {"value": len(served), "min": int(min_checked)},
            "compiles_in_window": {"value": int(compiles_in_window),
                                   "max": 0}}


def passes(checks: dict) -> bool:
    return all(c["value"] <= c["max"] if "max" in c else c["value"] >= c["min"]
               for c in checks.values())


def run_cell(reg: Registry, cell_name: str, seed: int, seconds: float,
             traced: bool, t_start: float = T_START,
             frontend=None) -> dict:
    """Everything after the device check; returns the result object.
    ``frontend`` stands in for the configuration's own (the control)."""
    watch = CompileWatch.shared()
    cell = reg.cell(cell_name)
    cfg = cell.config
    gen = reg.generator(cfg["generator"])
    graphs, gen_report = gen.make_pool(cfg["generator_params"],
                                       int(cfg["graph_seed"]))
    pool, excluded = apply_caps(graphs, cfg["exclude"])
    fe = frontend or reg.frontend(cfg["frontend"]).Frontend(cfg["serving"])
    labels = [fe.bucket_of(g) for g in pool]
    per_bucket = {b: labels.count(b) for b in sorted(set(labels))}
    log("pool", config=cell.config_name, traffic=cell.traffic_name,
        generated=gen_report, graphs=len(pool), per_bucket=per_bucket,
        excluded_above_top_rung=excluded)

    stream = Stream(pool, seed)
    by_bucket: dict = {}
    for g, label in zip(pool, labels):
        by_bucket.setdefault(label, []).append(g)
    w0 = watch.snap()
    t_warm = time.perf_counter()
    fe.warm(by_bucket)
    log("warm", seconds=time.perf_counter() - t_warm,
        **_delta(w0, watch.snap()))

    if traced:
        from repro import obs
        obs.configure(enabled=True)
        obs.clear_trace()
    # the pool is the harness's data, not the server's: keep the collector
    # from rescanning its tens of thousands of objects inside the window
    gc.collect()
    gc.freeze()
    client = Client(fe, stream, labels, int(cfg["check"]["per_bucket"]),
                    largest_per_bucket(pool, labels,
                                       int(cfg["check"]["largest_per_bucket"])),
                    trace_submit=_submit_annotation() if traced else None)
    driver = reg.driver(cell.traffic["kind"])
    fe.start()
    tracer = None
    try:
        if traced:
            tracer = Tracer(fe, client, seconds)
            tracer.thread.start()
        wc0 = watch.snap()
        t0, t1, drive_report = driver.run(client, cell.traffic, seconds, seed)
        c1 = fe.counters()
        in_window = _delta(wc0, watch.snap())
        setup_s = t0 - t_start
        if tracer is not None:
            tracer.thread.join()
        client.wait_all(WAIT_S)
    finally:
        fe.stop()
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    recs = client.arrays()
    summ = window.summarize(recs["due"], recs["done"], recs["ok"], t0, t1)
    due = window.due_in(recs["due"], t0, t1)
    unanswered = int(np.sum(due & ~recs["ok"]))
    log("window", seconds=t1 - t0, setup_s=setup_s,
        compiles_in_window=in_window,
        passes_over_pool=stream.passes, **drive_report,
        **{k: v for k, v in summ.items() if k != "window_s"},
        errors=client.errors[:3])
    run = Run(cell=cell_name, t0=t0, t1=t1, setup_s=setup_s, records=recs,
              summary=summ,
              counters_window=_delta(client.counters_at_open, c1))
    device = {}
    if traced:
        run.spans = _obs_spans(t0, t1)
        if tracer.error is None:
            run.trace = tracer.summary()
            run.counters_traced = tracer.counters
            device = {"busy_s": run.trace["busy_s"],
                      "window_s": run.trace["window_s"]}
            log("trace", **{k: v for k, v in run.trace.items()
                            if k not in ("device_ops", "idle_gaps")})
        else:
            log("trace", error=tracer.error)
    entries = cell.per_layer if traced else cell.end_to_end
    metrics = {}
    for m in entries:
        v = reg.metric(m["name"]).read(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    t_ref = time.perf_counter()
    checks = check(fe, pool, client.kept, cfg["check"]["dims"],
                   min_checked=len(per_bucket), unanswered=unanswered,
                   compiles_in_window=in_window["compiles"])
    log("check", reference_s=time.perf_counter() - t_ref,
        kept_per_bucket=client.taken)
    out = {"correct": passes(checks), "attempted": summ["due"],
           "failed": summ["failed"], "metrics": metrics,
           "device": {"memory_peak_bytes": peak, **device}}
    if traced and run.trace is not None:
        out["breakdown"] = {"device_ops": run.trace["device_ops"],
                            "idle_gaps": run.trace["idle_gaps"]}
    out["checks"] = checks
    return out


def _submit_annotation():
    import jax

    return lambda: jax.profiler.TraceAnnotation("bench.submit")


def device_check(chips: int) -> dict:
    """The device the cell runs on, or SystemExit before any work."""
    import jax

    with open(os.path.join(BENCH_DIR, "peaks.json")) as fh:
        peaks = json.load(fh)["devices"]
    devs = jax.devices()
    d = devs[0]
    kind = getattr(d, "device_kind", "?")
    if d.platform != "tpu":
        sys.exit(f"bench: JAX finds no TPU (platform {d.platform!r})")
    if kind not in peaks:
        sys.exit(f"bench: device kind {kind!r} is not in bench/peaks.json")
    if len(devs) < chips:
        sys.exit(f"bench: the cell needs {chips} chips, JAX sees {len(devs)}")
    return {"platform": d.platform, "kind": kind, "count": len(devs),
            "jax": jax.__version__}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    reg = Registry(ROOT)
    cell = reg.cell(args.workload)
    dev = device_check(cell.chips)
    log("device", **dev)

    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    out = run_cell(reg, args.workload, args.seed, args.seconds,
                   bool(args.trace))
    log("compile", **CompileWatch.shared().snap())
    out["device"] = {"platform": dev["platform"], "kind": dev["kind"],
                     "count": dev["count"], **out["device"]}
    for name, c in out["checks"].items():
        rule = (f"<= {c['max']}" if "max" in c else f">= {c['min']}")
        print(f"check {name}: {c['value']} (limit {rule})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
