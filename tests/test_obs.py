"""TopoScope (repro.obs): registry, tracing, exporters, trace report.

Locks down the observability contract the serving stack now depends on:

* metrics — thread-safe counters/gauges/histograms with label sets,
  Prometheus ``le`` bucket semantics, name/type conflicts rejected;
* tracing — off by default with a bounded disabled-path cost, nestable
  spans producing Perfetto-loadable Chrome-trace JSON that round-trips
  through ``export_chrome_trace`` → ``repro.obs.report``;
* the end-to-end drain: with tracing on, a repack="on" TopoServe drain
  emits the full serve.*/plan.* span tree and feeds ``obs.span_seconds``;
* PerfGate integration — ``telemetry.*`` rows classify as info.
"""
from __future__ import annotations

import json
import threading
import time

import networkx as nx
import pytest

from repro import obs
from repro.obs.metrics import Counter, MetricsRegistry
from repro.obs.report import aggregate, format_report, load_trace, self_times


@pytest.fixture
def traced():
    """Enable tracing for one test; restore the disabled default after."""
    obs.configure(enabled=True)
    obs.clear_trace()
    try:
        yield
    finally:
        obs.configure(enabled=False)
        obs.clear_trace()


# ----------------------------------------------------------------- registry

def test_counter_labels_and_totals():
    reg = MetricsRegistry()
    c = reg.counter("req.count", help="requests")
    c.inc(bucket="n16", frontend="topo")
    c.inc(3, bucket="n32", frontend="topo")
    c.inc(bucket="n16", frontend="sim")
    assert c.value(bucket="n16", frontend="topo") == 1
    assert c.value(bucket="n32", frontend="topo") == 3
    assert c.total(frontend="topo") == 4      # superset sum
    assert c.total() == 5
    assert c.labeled("bucket") == {"n16": 2.0, "n32": 3.0}
    with pytest.raises(ValueError):
        c.inc(-1)


def test_counter_thread_safety():
    c = Counter("c")
    n_threads, n_incs = 8, 2000

    def worker(i):
        for _ in range(n_incs):
            c.inc(thread=i % 2)  # two contended series

    ts = [threading.Thread(target=worker, args=(i,))
          for i in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert c.total() == n_threads * n_incs


def test_gauge_updown():
    reg = MetricsRegistry()
    g = reg.gauge("sessions.live")
    g.inc()
    g.inc()
    g.dec()
    assert g.value() == 1
    g.set(7, instance="s-0")
    assert g.value(instance="s-0") == 7


def test_histogram_bucket_math():
    reg = MetricsRegistry()
    h = reg.histogram("lat", buckets=(1.0, 2.0, 4.0))
    for v in (1.0, 1.5, 4.0, 5.0):  # le semantics: 1.0 lands in le=1.0
        h.observe(v)
    (series,) = h.snapshot_series().values()
    assert series["buckets"] == [(1.0, 1), (2.0, 2), (4.0, 3), ("+Inf", 4)]
    assert series["count"] == 4
    assert series["sum"] == pytest.approx(11.5)


def test_histogram_rejects_bad_buckets():
    reg = MetricsRegistry()
    with pytest.raises(ValueError):
        reg.histogram("bad", buckets=(2.0, 1.0))
    with pytest.raises(ValueError):
        reg.histogram("bad2", buckets=())


def test_registry_type_conflict_and_reset():
    reg = MetricsRegistry()
    c = reg.counter("x")
    assert reg.counter("x") is c  # get-or-create
    with pytest.raises(TypeError):
        reg.gauge("x")
    c.inc(5)
    reg.reset()
    assert c.total() == 0          # series cleared ...
    assert reg.get("x") is c       # ... instrument still registered


def test_snapshot_is_json_ready():
    reg = MetricsRegistry()
    reg.counter("a").inc(2, k="v")
    reg.histogram("b", buckets=(1.0,)).observe(0.5)
    snap = reg.snapshot()
    json.dumps(snap)  # must not raise
    assert snap["a"]["type"] == "counter"
    assert snap["a"]["series"] == [{"labels": {"k": "v"}, "value": 2.0}]
    assert snap["b"]["series"][0]["count"] == 1


# ---------------------------------------------------------------- exporters

def test_prometheus_text_format():
    reg = MetricsRegistry()
    reg.counter("serve.req", help="req count").inc(2, bucket="n16")
    reg.histogram("serve.lat", buckets=(0.1, 1.0)).observe(0.05)
    text = obs.prometheus_text(reg)
    assert '# TYPE serve_req_total counter' in text
    assert '# HELP serve_req_total req count' in text
    assert 'serve_req_total{bucket="n16"} 2' in text
    assert '# TYPE serve_lat histogram' in text
    assert 'serve_lat_bucket{le="0.1"} 1' in text
    assert 'serve_lat_bucket{le="+Inf"} 1' in text
    assert 'serve_lat_count 1' in text
    assert text.endswith("\n")


def test_append_jsonl_round_trip(tmp_path):
    reg = MetricsRegistry()
    reg.counter("c").inc()
    path = str(tmp_path / "metrics.jsonl")
    obs.append_jsonl(path, reg)
    reg.counter("c").inc()
    obs.append_jsonl(path, reg)
    lines = [json.loads(ln) for ln in open(path)]
    assert len(lines) == 2
    assert lines[1]["metrics"]["c"]["series"][0]["value"] == 2.0
    assert lines[0]["ts"] <= lines[1]["ts"]


# ------------------------------------------------------------------ tracing

def test_span_disabled_is_noop():
    assert not obs.enabled()
    with obs.span("x", foo=1) as sp:
        assert sp is obs.span("y")  # shared singleton
        sp.set(bar=2)               # must be accepted and dropped
    assert obs.trace_events() == []


def test_span_disabled_overhead():
    # acceptance bound: the disabled path must stay under 1 us/span so
    # always-on call sites cannot move serving numbers
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(10_000):
            with obs.span("overhead.probe"):
                pass
        best = min(best, (time.perf_counter() - t0) / 10_000)
    assert best < 1e-6, f"disabled span cost {best * 1e9:.0f} ns"


def test_span_nesting_and_attrs(traced):
    with obs.span("t.outer", frontend="topo") as outer:
        assert obs.current_span() is outer
        with obs.span("t.inner") as inner:
            inner.set(graphs=3)
        outer.set(served=1)
    assert obs.current_span() is None
    by_name = {e["name"]: e for e in obs.trace_events()}
    assert set(by_name) == {"t.outer", "t.inner"}
    inner, outer = by_name["t.inner"], by_name["t.outer"]
    assert inner["args"]["parent"] == "t.outer"
    assert "parent" not in outer["args"]
    assert inner["args"]["graphs"] == 3
    assert outer["args"]["served"] == 1
    assert outer["cat"] == "t" and outer["ph"] == "X"
    # interval containment (all in microseconds)
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1.0


def test_span_records_error_and_unwinds(traced):
    with pytest.raises(RuntimeError):
        with obs.span("t.fail"):
            raise RuntimeError("boom")
    (ev,) = obs.trace_events()
    assert ev["args"]["error"] == "RuntimeError"
    assert obs.current_span() is None  # stack unwound despite the raise


def test_span_feeds_duration_histogram(traced):
    h = obs.get_instrument("obs.span_seconds")
    before = {k: v.count for k, v in h.series().items()}
    with obs.span("t.feed"):
        pass
    key = (("span", "t.feed"),)
    assert h.series()[key].count == before.get(key, 0) + 1


def _host_events(trace_dir, name):
    import glob
    import os

    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    return [{k: str(v) for k, v in ev.stats}
            for p in ProfileData.from_file(path).planes
            if p.name == "/host:CPU"
            for ln in p.lines for ev in ln.events if ev.name == name]


@pytest.mark.parametrize("enabled", [True, False])
def test_span_lands_on_the_profiler_host_plane(tmp_path, enabled):
    """While tracing is on, a span is a profiler annotation with its
    attributes; off, the profiler sees nothing of it."""
    import jax

    obs.configure(enabled=enabled)
    try:
        jax.profiler.start_trace(str(tmp_path))
        try:
            with obs.span("serve.batch", bucket="n16", queued=3):
                pass
        finally:
            jax.profiler.stop_trace()
    finally:
        obs.configure(enabled=False)
        obs.clear_trace()
    got = _host_events(tmp_path, "serve.batch")
    assert got == ([{"bucket": "n16", "queued": "3"}] if enabled else [])


def test_trace_capacity_drops_not_grows(traced):
    obs.configure(capacity=5)
    try:
        for i in range(8):
            with obs.span("t.cap"):
                pass
        assert len(obs.trace_events()) == 5
        assert obs.dropped_events() == 3
    finally:
        obs.configure(capacity=200_000)


def test_chrome_trace_export_round_trip(tmp_path, traced):
    with obs.span("t.a", shape="G64_D128"):
        with obs.span("t.b"):
            pass
    path = str(tmp_path / "trace.json")
    assert obs.export_chrome_trace(path) == path
    doc = json.load(open(path))
    assert doc["displayTimeUnit"] == "ms"
    assert doc["otherData"]["dropped"] == 0
    events = doc["traceEvents"]
    assert {e["name"] for e in events} == {"t.a", "t.b"}
    for e in events:
        assert {"name", "cat", "ph", "ts", "dur", "pid", "tid",
                "args"} <= set(e)
    # report loader accepts both the object form and a bare array
    assert len(load_trace(path)) == 2
    json.dump(events, open(str(tmp_path / "bare.json"), "w"))
    assert len(load_trace(str(tmp_path / "bare.json"))) == 2


def test_cross_thread_spans_get_own_tid(traced):
    def other():
        with obs.span("t.worker"):
            pass

    t = threading.Thread(target=other)
    with obs.span("t.main"):
        t.start()
        t.join()
    by_name = {e["name"]: e for e in obs.trace_events()}
    assert by_name["t.worker"]["tid"] != by_name["t.main"]["tid"]
    # the worker thread has its own (empty) span stack: no false parent
    assert "parent" not in by_name["t.worker"]["args"]


# ------------------------------------------------------------- trace report

def _ev(name, ts, dur, tid=1, **args):
    return {"name": name, "cat": name.split(".")[0], "ph": "X", "ts": ts,
            "dur": dur, "pid": 1, "tid": tid, "args": args}


def test_self_times_subtract_children():
    events = [
        _ev("serve.drain", 0.0, 100.0),
        _ev("kernels.pairwise_l1", 10.0, 40.0, shape="G64_D128"),
        _ev("serve.drain", 0.0, 50.0, tid=2),  # other thread: independent
    ]
    st = {(e["name"], e["tid"]): s for e, s in self_times(events)}
    assert st[("serve.drain", 1)] == pytest.approx(60.0)
    assert st[("kernels.pairwise_l1", 1)] == pytest.approx(40.0)
    assert st[("serve.drain", 2)] == pytest.approx(50.0)


def test_aggregate_attaches_cost_cells():
    events = [
        _ev("serve.drain", 0.0, 100.0),
        _ev("kernels.pairwise_l1", 10.0, 40.0, shape="G64_D128"),
        _ev("kernels.pairwise_l1", 55.0, 40.0, shape="G64_D128"),
    ]
    rows = aggregate(events)
    assert [r["span"] for r in rows] == ["kernels.pairwise_l1",
                                        "serve.drain"]  # by -self_us
    krow = rows[0]
    assert krow["calls"] == 2 and krow["shape"] == "G64_D128"
    assert krow["cost_cell"] is not None
    assert "cell" in krow["cost_cell"] and "bound" in krow["cost_cell"]
    assert rows[1]["cost_cell"] is None  # non-kernel span

    text = format_report(events, top=1)
    assert "kernels.pairwise_l1" in text
    assert "1 more rows" in text
    assert format_report([]) == "(empty trace)"


# -------------------------------------------------- end-to-end serve tracing

def test_topo_serve_drain_emits_span_tree(traced):
    from repro.serve import TopoServe, TopoServeConfig

    srv = TopoServe(TopoServeConfig(method="prunit", repack="on"))
    graphs = [nx.cycle_graph(6), nx.petersen_graph(), nx.path_graph(5)]
    futs = []
    for g in graphs:
        nodes = sorted(g.nodes())
        idx = {u: i for i, u in enumerate(nodes)}
        futs.append(srv.submit(
            edges=[(idx[u], idx[v]) for (u, v) in g.edges()],
            n_vertices=len(nodes)))
    assert srv.drain() == len(graphs)
    for f in futs:
        f.result()

    names = {e["name"] for e in obs.trace_events()}
    assert {"serve.drain", "serve.batch", "serve.gather", "serve.sync",
            "serve.resolve", "plan.reduce", "plan.measure", "plan.repack",
            "plan.persist"} <= names
    by_name = {e["name"]: e for e in obs.trace_events()}
    assert by_name["serve.batch"]["args"]["parent"] == "serve.drain"
    assert by_name["serve.drain"]["args"]["served"] == len(graphs)
    # the drain span must cover its children (the >=95% wall-clock
    # acceptance is checked on the bench-scale run; here: containment)
    drain = by_name["serve.drain"]
    for e in obs.trace_events():
        if e is drain or e["tid"] != drain["tid"]:
            continue
        assert e["ts"] >= drain["ts"] - 1.0
        assert e["ts"] + e["dur"] <= drain["ts"] + drain["dur"] + 1.0
    # idle drain: early return, no extra span
    n_before = len(obs.trace_events())
    assert srv.drain() == 0
    assert len(obs.trace_events()) == n_before


def test_serve_stats_view_backed_by_registry():
    from repro.serve import TopoServe, TopoServeConfig

    srv = TopoServe(TopoServeConfig(method="none"))
    srv.submit(edges=[(0, 1), (1, 2)], n_vertices=3)
    srv.drain()
    stats = srv.stats
    assert stats["submitted"] == 1 and stats["served"] == 1
    assert stats["batches"] == 1 and stats["failed"] == 0
    # a second server must not see the first one's counts (instance labels)
    srv2 = TopoServe(TopoServeConfig(method="none"))
    assert srv2.stats["submitted"] == 0


# --------------------------------------------------------- perfgate plumbing

def test_telemetry_rows_classify_as_info():
    from repro.perfgate.references import classify_metric

    spec = classify_metric("telemetry", "kernel_calls_pairwise_l1")
    assert spec.direction == "info"
    spec = classify_metric("telemetry", "plan_cache_misses")
    assert spec.direction == "info"


def test_telemetry_delta_tracks_counters():
    from benchmarks.common import telemetry_delta, telemetry_snapshot

    before = telemetry_snapshot()
    obs.counter("kernels.calls").inc(2, kernel="obs_test_probe")
    delta = telemetry_delta(before)
    assert delta["kernel_calls_obs_test_probe"] == 2
    for k in ("plan_cache_hits", "plan_cache_misses",
              "plan_cache_evictions"):
        assert k in delta  # always present, even when zero
