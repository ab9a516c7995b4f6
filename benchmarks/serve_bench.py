"""TopoServe throughput/latency benchmark + served-vs-direct parity check.

Per padding bucket: graphs/s, p50/p99 request latency, executed batches —
and a bit-identical comparison of every served diagram against a direct
``topological_signature`` call on the same packed batches (the serve path
must be a pure scheduling layer, never a numerics layer).

A TopoWatch panel follows the parity check: a second, live round behind a
running HTTP exporter + installed SLO engine measures the fully-watched
request path against the bare one (``watch_overhead_pct``: exporter
scraping, SLO ticking, request-context minting, flight recording — budget
≤2%), and ``--inject-slow-drain`` detunes the drain deterministically so
the latency SLO trips, flips ``/slo`` to breach, and leaves a flight dump
under ``results/obs/`` — the CI smoke asserts that whole chain.

  PYTHONPATH=src python -m benchmarks.serve_bench [--quick]
                                                  [--inject-slow-drain]
  PYTHONPATH=src python -m benchmarks.run --only serve
"""
from __future__ import annotations

import argparse
import gc
import json
import time
import urllib.request

import numpy as np

from benchmarks.common import Report
from repro import obs
from repro.core.api import plan_cache_info, topological_signature
from repro.core.persistence_jax import diagrams_bitwise_equal
from repro.launch.compile_cache import use_compile_cache
from repro.serve import TopoServe, TopoServeConfig
from repro.serve.topo_serve import pack_requests


def _query_stream(n_queries: int, seed: int = 0,
                  n_range: tuple[int, int] = (6, 56)):
    """Synthetic ego-net-regime queries spanning the bucket ladder.

    Orders are drawn uniformly from ``n_range`` (half-open); the default
    spans the three smaller buckets.
    """
    import networkx as nx

    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n_queries):
        n = int(rng.integers(*n_range))
        kind = rng.integers(0, 3)
        if kind == 0:
            g = nx.gnp_random_graph(n, float(rng.uniform(0.1, 0.3)),
                                    seed=int(rng.integers(2**31)))
        elif kind == 1:
            g = nx.barabasi_albert_graph(n, min(3, n - 1),
                                         seed=int(rng.integers(2**31)))
        else:
            g = nx.powerlaw_cluster_graph(n, min(2, n - 1), 0.4,
                                          seed=int(rng.integers(2**31)))
        out.append((list(g.edges()), n))
    return out


def run(report: Report, quick: bool = False,
        inject_slow_drain: bool = False) -> None:
    n_queries = 60 if quick else 400
    max_batch = 32 if quick else 128
    # pad_batch_to == max_batch -> every executed batch has ONE shape per
    # bucket, so after warmup the timed region never recompiles
    cfg = TopoServeConfig(dim=1, method="prunit", sublevel=False,
                          max_batch=max_batch, pad_batch_to=max_batch,
                          record_batches=True)
    server = TopoServe(cfg)
    queries = _query_stream(n_queries, seed=11)

    # warmup round: compile every touched bucket out of the timed region
    warm = [server.submit(edges=e, n_vertices=n) for (e, n) in queries]
    server.drain()
    for f in warm:
        f.result()
    server.executed_batches.clear()
    # report deltas over the timed region only: server.stats accumulates the
    # warmup drain, and the plan cache is process-cumulative (other suites
    # compile through it under `-m benchmarks.run`)
    batches_before = server.stats["batches"]
    cache_before = plan_cache_info()

    # Exclude the cyclic collector from the timed region (timeit-style):
    # when full collections land is a function of process-wide allocation
    # counts, so merely importing another package can shift multi-ms GC
    # pauses into the submit loop and double the per-bucket p50s.  The
    # bench measures the serving layer, not collector scheduling.
    gc.collect()
    gc_was_enabled = gc.isenabled()
    gc.disable()
    try:
        t0 = time.perf_counter()
        futs = [server.submit(edges=e, n_vertices=n) for (e, n) in queries]
        server.drain()
        results = [f.result() for f in futs]
        wall = time.perf_counter() - t0
    finally:
        if gc_was_enabled:
            gc.enable()

    report.add("serve", "graphs_per_s", len(futs) / wall)
    by_bucket: dict = {}
    for f in futs:
        by_bucket.setdefault(f.bucket, []).append(f)
    for bucket, bfuts in sorted(by_bucket.items()):
        lat = np.array([f.latency_s() for f in bfuts]) * 1e3
        tag = f"serve_n{bucket.n_pad}"
        report.add(tag, "graphs", len(bfuts))
        report.add(tag, "latency_p50_ms", np.percentile(lat, 50))
        report.add(tag, "latency_p99_ms", np.percentile(lat, 99))
    report.add("serve", "batches", server.stats["batches"] - batches_before)
    info = plan_cache_info()
    report.add("serve", "plan_cache_hits",
               info["hits"] - cache_before["hits"])
    report.add("serve", "plan_cache_misses",
               info["misses"] - cache_before["misses"])

    # ---- parity: replay the exact executed batches through the direct API
    import jax

    checked = 0
    mismatches = 0
    for bucket, reqs, bfuts in server.executed_batches:
        g = pack_requests(reqs, bucket)
        direct = topological_signature(
            g, dim=cfg.dim, method=cfg.method, sublevel=cfg.sublevel,
            edge_cap=bucket.edge_cap, tri_cap=bucket.tri_cap,
            quad_cap=cfg.quad_cap, reducer=cfg.reducer,
        )
        for i, fut in enumerate(bfuts):
            row = jax.tree.map(lambda x: x[i], direct)
            if not diagrams_bitwise_equal(fut.result(), row):
                mismatches += 1
            checked += 1
    assert checked == len(results), (checked, len(results))
    report.add("serve", "parity_mismatches", mismatches)
    if mismatches:
        raise AssertionError(
            f"{mismatches}/{len(results)} served diagrams differ from direct "
            "topological_signature output")
    print(f"[serve_bench] parity OK: {len(results)} served diagrams "
          "bit-identical to direct computation")

    # with REPRO_OBS=1 the timed drains above produced spans — export the
    # Chrome trace + a Prometheus snapshot under results/obs/ (TopoWatch
    # scratch, gitignored; CI uploads them as artifacts) so a smoke job or
    # a human with Perfetto can inspect the run
    if obs.enabled():
        trace_path = obs.export_chrome_trace(
            "results/obs/trace_serve_bench.json")
        prom_path = obs.export_prometheus(
            "results/obs/metrics_serve_bench.prom")
        print(f"[serve_bench] obs: wrote {trace_path} "
              f"({len(obs.trace_events())} spans) and {prom_path}")

    _watch_panel(report, queries, cfg,
                 inject_slow_drain=inject_slow_drain, quick=quick)


def _serve_round(server: TopoServe, queries) -> float:
    """Wall seconds to submit + drain + collect one full query stream."""
    gc.collect()
    t0 = time.perf_counter()
    futs = [server.submit(edges=e, n_vertices=n) for (e, n) in queries]
    server.drain()
    for f in futs:
        f.result()
    return time.perf_counter() - t0


def _watch_panel(report: Report, queries, cfg: TopoServeConfig,
                 inject_slow_drain: bool, quick: bool) -> None:
    """Live TopoWatch round: exporter + SLO engine around a serve loop.

    Measures the watched-vs-bare request path (same compiled plans — the
    bare round re-runs first so both sides are warm), scrapes /metrics and
    /healthz from the running exporter mid-traffic, and (opt-in) injects a
    deterministic drain-side stall that trips the p99 latency SLO: verdict
    visible at /slo, counted in slo.breaches_total (which PerfGate gates
    abs_upper), flight ring dumped to results/obs/FLIGHT_<rev>.json.
    """
    import threading

    from repro.obs import flight, slo
    from repro.obs.http import start_http_server

    # --- bare round (TopoWatch passive: no exporter, no SLO engine)
    bare = TopoServe(cfg)
    _serve_round(bare, queries)            # warm the per-size executables
    bare_s = min(_serve_round(bare, queries) for _ in range(3))

    # --- watched round: exporter scraping + SLO engine ticking in the
    # background while the same stream is served.  Un-injected ceilings
    # are deliberately unreachable (30s): the panel proves the machinery
    # runs at zero marginal cost, not that this machine is fast — and a
    # real breach here would poison telemetry.slo_breaches_total, which
    # PerfGate gates abs_upper against a zero baseline.
    tight = 0.050 if inject_slow_drain else 30.0
    engine = slo.SLOEngine(slo.default_serve_slos(
        latency_p99_s=tight, latency_p50_s=tight,
        rules=(slo.BurnRule(long_s=2.0, short_s=0.5, factor=1.0),)))
    slo.install(engine)
    srv = start_http_server(port=0)
    stop_scrape = threading.Event()

    def scraper():
        # realistic scrape cadence: Prometheus defaults to whole seconds;
        # 0.25s is already 4-40x tighter than production pulls
        while not stop_scrape.is_set():
            urllib.request.urlopen(srv.url + "/metrics").read()
            urllib.request.urlopen(srv.url + "/slo").read()
            stop_scrape.wait(0.25)

    scr = threading.Thread(target=scraper, daemon=True)
    scr.start()
    watched = TopoServe(cfg)
    if inject_slow_drain:
        # deterministic detune: every drain stalls past the (tightened)
        # p99 ceiling, so the burn-rate rules must fire
        inner = watched.drain
        stall = 4.0 * tight

        def slow_drain():
            time.sleep(stall)
            return inner()

        watched.drain = slow_drain
    _serve_round(watched, queries)
    n_rounds = 3 if quick else 5
    watched_s = []
    for _ in range(n_rounds):
        watched_s.append(_serve_round(watched, queries))
        engine.tick()
        time.sleep(0.1)  # burn windows need >1 distinct snapshot times
    engine.tick()
    stop_scrape.set()
    scr.join(timeout=2)

    health = json.load(urllib.request.urlopen(srv.url + "/healthz"))
    slo_doc = json.load(urllib.request.urlopen(srv.url + "/slo"))
    srv.stop()
    slo.install(None)

    if not inject_slow_drain:
        overhead = 100.0 * (min(watched_s) - bare_s) / bare_s
        report.add("serve_watch", "watch_overhead_pct", overhead)
    report.add("serve_watch", "slo_objectives", len(slo_doc["status"]))
    breached = [k for k, v in slo_doc["status"].items()
                if v["status"] == "breach"]
    report.add("serve_watch", "slo_breached", len(breached))
    print(f"[serve_bench] topowatch: health={health['status']} "
          f"breached={breached or 'none'}")
    if inject_slow_drain:
        dump = flight.last_dump_path()
        assert breached, "slow-drain injection did not trip any SLO"
        assert dump is not None, "SLO breach left no flight dump"
        print(f"[serve_bench] slow-drain injection tripped {breached}; "
              f"flight dump: {dump}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small stream (CI / CPU smoke)")
    ap.add_argument("--inject-slow-drain", action="store_true",
                    help="detune the watched drain to force one SLO "
                         "breach + flight dump (CI smoke)")
    args = ap.parse_args()
    use_compile_cache()
    report = Report()
    run(report, quick=args.quick, inject_slow_drain=args.inject_slow_drain)
    print(report.csv())


if __name__ == "__main__":
    main()
