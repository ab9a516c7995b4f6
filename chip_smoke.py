"""Bring-up smoke test: the served path on one TPU chip, checked end to end.

    python chip_smoke.py [--seed N]        # one chip: the five phases below
    python chip_smoke.py --four-chips      # 4 chips: the two mesh paths only

One process drives every phase through the public entry points (TopoServe,
StreamServe, SimilarityServe, the plan cache); all data is generated from
``--seed``.  Phases, each checked against an independent computation:

1. TopoServe, single phase, at full bucket width (``max_batch = 256``):
   1-hop ego nets of a Table-1 surrogate network, topped up with seeded
   ego-regime graphs until every default bucket runs at least one full
   batch of 256.  Every served diagram must be bit-identical to a direct
   ``topological_signature`` on the same packed batch, and a sample per
   bucket must match the pure-Python reference ``persistence_ref``.
2. TopoServe with the Pallas GF(2) reducer (``reducer="pallas"``) on one
   full top-bucket batch: bit-identical to phase 1; the kernel's "vmap" and
   "grid" batch modes on that batch's boundary blocks must equal the jnp
   ``reduce_packed``.
3. TopoServe, two phase (``repack="on"``): the same traffic; every graph's
   guaranteed-dimension pairs must equal phase 1's exactly.
4. StreamServe: one 64-graph ego-decay session; after every update step the
   session's pairs must equal a from-scratch ``topological_signature``.
5. SimilarityServe over a ShardedIndex (``coarse="lsh"``) holding a corpus
   of >= 100k diagrams: ids must equal an unsharded TopoIndex query and
   distances a NumPy L1 over the same embeddings.

``--four-chips`` runs only TopoServe over ``make_serve_mesh(4)`` and
ShardedIndex over ``make_index_mesh(4)``, each against its one-device
counterpart.  Per-phase counts, compile seconds and peak device memory go to
earlier lines; the last line is ``{"ok": true, "device": {...}}``.  Any
mismatch, failed future or exception exits non-zero without that line, and
so does a run that finds no TPU: nothing here runs on the CPU.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
NETWORK = "CA-CondMat"  # Table-1 surrogate whose 1-hop ego nets are served
N_PAD = 4096            # its order: 4,096 centres, top-ups fill the rest
BATCH = 256          # TopoServe max_batch = pad_batch_to: full bucket width
REF_PER_BUCKET = 32  # graphs per bucket checked against persistence_ref
CORPUS = 102_400     # SimilarityServe / ShardedIndex corpus (diagrams)
CORPUS_CHUNK = 12_800
N_QUERIES = 256
K = 10
DIST_RTOL = 1e-5     # served vs NumPy L1, relative to max(1, |reference|)


def log(phase: str, **kv) -> None:
    print(f"[chip_smoke] {phase} " + json.dumps(kv, default=_jsonable),
          flush=True)


def _jsonable(x):
    if isinstance(x, (np.integer, np.floating)):
        return x.item()
    return str(x)


def require(cond, what: str) -> None:
    if not cond:
        raise AssertionError(what)


class CompileWatch:
    """Backend compile seconds and persistent-cache hits, per phase."""

    def __init__(self):
        import jax.monitoring as mon

        self.secs = 0.0
        self.compiles = 0
        self.hits = 0
        self.misses = 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += duration
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def mark(self) -> tuple:
        return (self.secs, self.compiles, self.hits, self.misses)

    def since(self, mark: tuple) -> dict:
        s, c, h, m = mark
        return {"compile_s": round(self.secs - s, 3),
                "compiles": self.compiles - c,
                "cache_hits": self.hits - h, "cache_misses": self.misses - m}


def peak_bytes():
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use", "not reported")


# ------------------------------------------------------------------ traffic

def ego_traffic(seed: int, router):
    """Ego-net requests of one Table-1 surrogate, and how many exceed the
    top rung: those ``router.bucket_for`` rejects, and those with more
    vertices than the top rung, are counted, not served."""
    import jax
    import jax.numpy as jnp

    from repro.data.ego import ego_batch, ego_sizes
    from repro.data.graphs import load_large_network

    g = load_large_network(NETWORK, jax.random.PRNGKey(seed), n_pad=N_PAD)
    adj, f, mask = g.adj[0], g.f[0], g.mask[0]
    top = max(router.config.buckets).n_pad
    sizes = np.asarray(ego_sizes(adj))
    live = np.asarray(mask)
    centers = np.nonzero(live & (sizes <= top))[0]
    over_top = int((live & (sizes > top)).sum())
    eb = ego_batch(adj, f, top, jnp.asarray(centers))
    adjs, masks, fs = (np.asarray(x) for x in (eb.adj, eb.mask, eb.f))
    out = []
    for i in range(len(centers)):
        nv = int(masks[i].sum())            # members fill the leading slots
        a = adjs[i, :nv, :nv]
        iu, iv = np.nonzero(np.triu(a, 1))
        try:
            bucket = router.bucket_for(nv, len(iu), _triangles(a))
        except ValueError:
            over_top += 1
            continue
        out.append(dict(edges=list(zip(iu.tolist(), iv.tolist())),
                        n_vertices=nv, f=fs[i, :nv].tolist(),
                        bucket=bucket, source="ego"))
    return out, over_top


def top_up(traffic, router, seed: int):
    """Seeded ego-regime graphs (``benchmarks.serve_bench._query_stream``)
    for every bucket holding fewer than one full batch."""
    from benchmarks.serve_bench import _query_stream

    lo = 6
    for j, b in enumerate(sorted(router.config.buckets)):
        have = sum(1 for r in traffic if r["bucket"] == b)
        draw = 0
        while have < BATCH:
            for edges, n in _query_stream(64, seed=seed + 1000 * j + draw,
                                          n_range=(lo, b.n_pad + 1)):
                a = np.zeros((n, n), bool)
                for u, v in edges:
                    a[u, v] = a[v, u] = True
                try:
                    if router.bucket_for(n, int(np.triu(a, 1).sum()),
                                         _triangles(a)) != b:
                        continue
                except ValueError:
                    continue
                traffic.append(dict(edges=edges, n_vertices=n, f=None,
                                    bucket=b, source="topup"))
                have += 1
                if have >= BATCH:
                    break
            draw += 1
        lo = b.n_pad + 1
    return traffic


def _triangles(a: np.ndarray) -> int:
    a = a.astype(np.int64)
    return int(np.trace(a @ a @ a) // 6)


def _f_of(req) -> np.ndarray:
    if req["f"] is not None:
        return np.asarray(req["f"], np.float32)
    deg = np.zeros(req["n_vertices"], np.float32)
    for u, v in {(min(u, v), max(u, v)) for u, v in req["edges"] if u != v}:
        deg[u] += 1
        deg[v] += 1
    return deg


def pairs(d, dims) -> list:
    """Sorted (dim, birth, death) of one per-graph Diagrams slice."""
    b, e, k, v = (np.asarray(x) for x in (d.birth, d.death, d.dim, d.valid))
    sel = v & np.isin(k, dims)
    return sorted(zip(k[sel].tolist(), b[sel].tolist(), e[sel].tolist()))


# ------------------------------------------------------------------- phases

def serve_all(server, traffic):
    futs = [server.submit(edges=r["edges"], n_vertices=r["n_vertices"],
                          f=r["f"]) for r in traffic]
    served = server.drain()
    results = [f.result() for f in futs]   # raises if any batch failed
    require(served == len(traffic), f"drain served {served}/{len(traffic)}")
    require(server.stats["failed"] == 0, "TopoServe failed futures")
    return futs, results


def phase_topo_single(traffic, watch):
    from repro.core.api import topological_signature
    from repro.core.persistence_jax import diagrams_bitwise_equal
    from repro.core.persistence_ref import diagrams_equal, persistence_diagrams
    from repro.serve import SINGLE_PHASE_BUCKETS, TopoServe, TopoServeConfig
    from repro.serve.topo_serve import pack_requests
    import jax

    mark = watch.mark()
    cfg = TopoServeConfig(buckets=SINGLE_PHASE_BUCKETS, max_batch=BATCH,
                          pad_batch_to=BATCH, record_batches=True)
    srv = TopoServe(cfg)
    t0 = time.perf_counter()
    futs, results = serve_all(srv, traffic)
    wall = time.perf_counter() - t0
    full = {}
    checked = 0
    for bucket, reqs, bfuts in srv.executed_batches:
        if len(reqs) == BATCH:
            full[bucket] = full.get(bucket, 0) + 1
        g = pack_requests(reqs, bucket, BATCH)
        direct = topological_signature(
            g, dim=cfg.dim, method=cfg.method, sublevel=cfg.sublevel,
            edge_cap=bucket.edge_cap, tri_cap=bucket.tri_cap,
            quad_cap=cfg.quad_cap, reducer=cfg.reducer)
        for i, fut in enumerate(bfuts):
            row = jax.tree.map(lambda x: x[i], direct)
            require(diagrams_bitwise_equal(fut.result(), row),
                    f"served diagram differs from direct call ({bucket})")
            checked += 1
    require(checked == len(traffic), "not every served graph was checked")
    for b in cfg.buckets:
        require(full.get(b, 0) >= 1, f"bucket {b} ran no full batch")
    # the paper's claim: PD_dim after reduction == PD_dim of the input
    ref_ok = {}
    for b in cfg.buckets:
        idx = [i for i, r in enumerate(traffic) if r["bucket"] == b]
        for i in idx[:REF_PER_BUCKET]:
            r = traffic[i]
            n = r["n_vertices"]
            a = np.zeros((n, n), bool)
            for u, v in r["edges"]:
                a[u, v] = a[v, u] = True
            want = persistence_diagrams(a, _f_of(r), max_dim=cfg.dim)
            got = {cfg.dim: [(x, y) for (_, x, y)
                             in pairs(results[i], [cfg.dim])]}
            require(diagrams_equal({cfg.dim: want.get(cfg.dim, [])}, got),
                    f"graph {i} ({b}) differs from persistence_ref")
            ref_ok[f"n{b.n_pad}"] = ref_ok.get(f"n{b.n_pad}", 0) + 1
    log("topo_serve", graphs=len(traffic), bitwise_checked=checked,
        full_batches={f"n{b.n_pad}": full[b] for b in cfg.buckets},
        batches=srv.stats["batches"], ref_checked=ref_ok,
        drain_wall_s=round(wall, 3), peak_bytes=peak_bytes(),
        **watch.since(mark))
    return results


def phase_topo_pallas(traffic, single, watch):
    """The Pallas GF(2) reducer on one full top-bucket batch, compiled.

    Served with ``reducer="pallas"`` (the one-matrix kernel, batched by
    vmap), every diagram must be bit-identical to phase 1's jnp-reduced
    one.  The batch's own per-dimension boundary blocks (unreduced graphs,
    so the kernel sees the largest columns) then go through both batch
    modes of ``ops.gf2_reduce_batch``, "vmap" and "grid", and each must
    give the (owner, positive) of the jnp ``reduce_packed``.  Only the top
    bucket runs: each bucket's Pallas pipeline is another cold compile.
    """
    from functools import partial

    import jax

    from repro.core.filtration import build_filtered_complex
    from repro.core.persistence_jax import (
        diagrams_bitwise_equal,
        pack_boundary_blocks,
        reduce_packed,
    )
    from repro.kernels import ops
    from repro.serve import SINGLE_PHASE_BUCKETS, TopoServe, TopoServeConfig
    from repro.serve.topo_serve import pack_requests

    mark = watch.mark()
    top = SINGLE_PHASE_BUCKETS[-1]
    idx = [i for i, r in enumerate(traffic) if r["bucket"] == top][:BATCH]
    reqs = [traffic[i] for i in idx]
    require(len(reqs) == BATCH, f"top bucket holds {len(reqs)} graphs")
    cfg = TopoServeConfig(buckets=(top,), max_batch=BATCH,
                          pad_batch_to=BATCH, reducer="pallas",
                          record_batches=True)
    srv = TopoServe(cfg)
    _, served = serve_all(srv, reqs)
    require(srv.stats["batches"] == 1, "the Pallas batch was split")
    for i, d in zip(idx, served):
        require(diagrams_bitwise_equal(d, single[i]),
                f"graph {i}: Pallas-reduced diagram differs from jnp")

    # rows of each dimension's block: vertices, edges, triangles (dim 1)
    caps = [top.n_pad, top.edge_cap, top.tri_cap]

    def blocks_of(adj, mask, f):
        fc = build_filtered_complex(adj, mask, f, cfg.dim, top.edge_cap,
                                    top.tri_cap, cfg.quad_cap, cfg.sublevel)
        return pack_boundary_blocks(fc, caps)[0]

    g = pack_requests(srv.executed_batches[0][1], top)
    blocks = jax.jit(jax.vmap(blocks_of))(g.adj, g.mask, g.f)
    shapes = []
    for rows, b in zip(caps, blocks):
        want = jax.jit(jax.vmap(partial(reduce_packed, n_rows=rows)))(b)
        for mode in ("vmap", "grid"):
            got = jax.jit(partial(ops.gf2_reduce_batch, n_rows=rows,
                                  batch_mode=mode))(b)
            for name, x, y in zip(("owner", "positive"), got, want):
                require(np.array_equal(np.asarray(x), np.asarray(y)),
                        f"gf2_reduce {mode} {b.shape}: {name} differs "
                        "from reduce_packed")
        shapes.append(list(b.shape))
    log("topo_pallas", graphs=len(reqs), bucket=f"n{top.n_pad}",
        served_bitwise=len(served), kernel_blocks=shapes,
        kernel_modes=["vmap", "grid"], peak_bytes=peak_bytes(),
        **watch.since(mark))


def phase_topo_repack(traffic, single, watch):
    from repro.serve import SINGLE_PHASE_BUCKETS, TopoServe, TopoServeConfig

    mark = watch.mark()
    srv = TopoServe(TopoServeConfig(buckets=SINGLE_PHASE_BUCKETS,
                                    max_batch=BATCH, pad_batch_to=BATCH,
                                    repack="on"))
    exact_dim = srv.plan_for(srv.config.buckets[0]).exact_from_dim()
    dims = list(range(exact_dim, srv.config.dim + 1))
    t0 = time.perf_counter()
    _, results = serve_all(srv, traffic)
    wall = time.perf_counter() - t0
    for i, (a, b) in enumerate(zip(results, single)):
        require(pairs(a, dims) == pairs(b, dims),
                f"repack graph {i}: pairs differ from single phase")
    rungs = {f"n{k[0]}->n{k[1]}": v
             for k, v in sorted(srv.stats["repack_rungs"].items())}
    log("topo_repack", graphs=len(results), equal_dims=dims, rungs=rungs,
        drain_wall_s=round(wall, 3), peak_bytes=peak_bytes(),
        **watch.since(mark))


def phase_stream(seed: int, watch):
    import jax

    from repro.core.api import topological_signature
    from repro.core.delta import delta_step
    from repro.data.temporal import ego_decay_stream
    from repro.serve import StreamServe
    from repro.stream import TopoStreamConfig, dim_pairs

    mark = watch.mark()
    steps = 24
    g0, deltas = ego_decay_stream(
        jax.random.PRNGKey(seed + 7), batch=64, n_pad=64, n_core=16,
        n_double=20, n_pendant=20, steps=steps)
    cfg = TopoStreamConfig(dim=1, method="both", edge_cap=320, tri_cap=512,
                           recompute_pad="full")
    srv = StreamServe(cfg)
    sid = srv.create_session(g0)
    skipped = 0
    for t in range(steps):
        fut = srv.submit(sid, delta_step(deltas, t))
        require(srv.drain() == 1, f"stream step {t} not applied")
        d = fut.result()
        skipped += fut.info["hits"]
        ref = topological_signature(
            srv.graph(sid), dim=cfg.dim, method=cfg.method,
            sublevel=cfg.sublevel, edge_cap=cfg.edge_cap,
            tri_cap=cfg.tri_cap, quad_cap=cfg.quad_cap)
        for b in range(g0.batch):
            require(dim_pairs(d, b, cfg.dim) == dim_pairs(ref, b, cfg.dim),
                    f"stream step {t} graph {b} differs from scratch")
    stats = srv.stats()
    log("stream_serve", steps=steps, graphs=g0.batch,
        graph_updates=stats["graph_updates"], skipped_recomputes=skipped,
        recomputes=stats["recomputes"], peak_bytes=peak_bytes(),
        **watch.since(mark))


def build_corpus(seed: int, config):
    """(TopoIndex of CORPUS noisy-copy diagrams, query diagrams)."""
    from repro.index import TopoIndex
    from repro.metrics.testing import noisy_copies, seed_diagram_arrays

    rng = np.random.default_rng(seed)
    seeds = seed_diagram_arrays(rng, n_seeds=512, s=16)
    index = TopoIndex(config)
    for c in range(CORPUS // CORPUS_CHUNK):
        index.add(noisy_copies(seeds, rng, CORPUS_CHUNK, 0.02, 0.5),
                  ids=[f"c{c * CORPUS_CHUNK + i}"
                       for i in range(CORPUS_CHUNK)])
    require(len(index) == CORPUS, "corpus add lost diagrams")
    return index, noisy_copies(seeds, rng, N_QUERIES, 0.02, 0.5)


def check_neighbors(emb_q, base, ids, dists, want_ids, what):
    """ids == the unsharded answer; distances == NumPy L1 (float64)."""
    row_of = {g: i for i, g in enumerate(base.ids)}
    for q in range(len(ids)):
        require(list(ids[q]) == list(want_ids[q]),
                f"{what} query {q}: ids differ from unsharded TopoIndex")
        rows = np.asarray([row_of[g] for g in ids[q]])
        ref = np.abs(emb_q[q][None, :].astype(np.float64)
                     - base._emb[rows].astype(np.float64)).sum(-1)
        err = np.abs(np.asarray(dists[q], np.float64) - ref)
        require((err <= DIST_RTOL * np.maximum(1.0, ref)).all(),
                f"{what} query {q}: distance off NumPy L1 by {err.max()}")


def phase_similarity(seed: int, traffic, watch):
    from repro.index import TopoIndexConfig
    from repro.serve import (SINGLE_PHASE_BUCKETS, SimilarityServe,
                             TopoServeConfig)
    from repro.serve.similarity import _stack_by_shape

    mark = watch.mark()
    base, _ = build_corpus(seed, TopoIndexConfig(coarse="lsh"))
    sim = SimilarityServe(
        index=base, sharded=True, default_k=K,
        config=TopoServeConfig(buckets=SINGLE_PHASE_BUCKETS,
                               max_batch=BATCH, pad_batch_to=BATCH))
    rng = np.random.default_rng(seed + 11)
    pick = rng.permutation(len(traffic))
    adds, queries = pick[:N_QUERIES], pick[N_QUERIES:2 * N_QUERIES]
    for j in adds:
        r = traffic[j]
        sim.add(edges=r["edges"], n_vertices=r["n_vertices"], f=r["f"],
                gid=f"t{j}")
    futs = []
    for j in queries:
        r = traffic[j]
        futs.append(sim.submit(edges=r["edges"], n_vertices=r["n_vertices"],
                               f=r["f"], k=K))
    t0 = time.perf_counter()
    resolved = sim.drain()
    wall = time.perf_counter() - t0
    results = [f.result() for f in futs]
    st = sim.stats
    require(resolved == N_QUERIES, f"resolved {resolved}/{N_QUERIES}")
    require(st["add_failures"] == 0, "SimilarityServe dropped adds")
    require(len(sim.index) == CORPUS + N_QUERIES,
            f"index holds {len(sim.index)}, want {CORPUS + N_QUERIES}")
    emb_dev = sim.index._emb_dev
    for idxs, batch in _stack_by_shape([r.diagrams for r in results]):
        want = base.query(batch, k=K)
        emb_q = np.asarray(base.embed(batch))
        check_neighbors(emb_q, base, [results[i].ids for i in idxs],
                        [results[i].distances for i in idxs], want.ids,
                        "similarity")
    log("similarity_serve", corpus=CORPUS, added=N_QUERIES,
        queries=N_QUERIES, k=K, stage1_candidates=st["stage1_candidates"],
        mesh=dict(sim.index.mesh.shape),
        emb_devices=len(emb_dev.sharding.device_set),
        drain_wall_s=round(wall, 3), peak_bytes=peak_bytes(),
        **watch.since(mark))


def phase_four_chips(seed: int, traffic, watch):
    import jax

    from repro.index import ShardedIndex, TopoIndexConfig
    from repro.launch.mesh import make_index_mesh, make_serve_mesh
    from repro.serve import SINGLE_PHASE_BUCKETS, TopoServe, TopoServeConfig
    from repro.serve.topo_serve import pack_requests

    mark = watch.mark()
    top = SINGLE_PHASE_BUCKETS[-1]
    reqs = [r for r in traffic if r["bucket"] == top]
    # the one-device reference runs the batch each chip of the mesh runs
    # (BATCH / 4): the same per-graph program, a quarter of the compile
    cfg = TopoServeConfig(buckets=(top,), max_batch=BATCH // 4,
                          pad_batch_to=BATCH // 4)
    _, one = serve_all(TopoServe(cfg), reqs)
    mesh = make_serve_mesh(4)
    srv = TopoServe(dataclasses.replace(cfg, max_batch=BATCH,
                                        pad_batch_to=BATCH,
                                        record_batches=True), mesh=mesh)
    _, four = serve_all(srv, reqs)
    for i, (a, b) in enumerate(zip(one, four)):
        require(all(np.array_equal(np.asarray(x), np.asarray(y),
                                   equal_nan=True)
                    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))),
                f"mesh TopoServe graph {i} differs from one device")
    bucket, batch, _ = srv.executed_batches[0]
    d = srv.plan_for(top).execute(pack_requests(batch, bucket))
    serve_devices = len(d.birth.sharding.device_set)
    require(serve_devices == 4, f"TopoServe output on {serve_devices} devices")
    log("four_chip_topo_serve", graphs=len(reqs), mesh=dict(mesh.shape),
        output_devices=serve_devices, **watch.since(mark))

    mark = watch.mark()
    for coarse in ("lsh", "none"):
        base, queries = build_corpus(seed, TopoIndexConfig(coarse=coarse))
        want = base.query(queries, k=K)
        sharded = ShardedIndex.from_index(base, mesh=make_index_mesh(4))
        got = sharded.query(queries, k=K)
        check_neighbors(np.asarray(base.embed(queries)), base, got.ids,
                        got.distances, want.ids, f"ShardedIndex[{coarse}]")
        devs = {"emb": len(sharded._emb_dev.sharding.device_set)}
        if sharded._codes_dev is not None:
            devs["codes"] = len(sharded._codes_dev.sharding.device_set)
        require(all(v == 4 for v in devs.values()),
                f"ShardedIndex arrays on {devs} devices")
        log("four_chip_sharded_index", coarse=coarse, corpus=CORPUS,
            queries=N_QUERIES, stage=got.stats["stage"],
            mesh=dict(sharded.mesh.shape), array_devices=devs,
            **watch.since(mark))
        mark = watch.mark()


# --------------------------------------------------------------------- main

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip mesh paths")
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"[chip_smoke] no TPU: JAX found {platform!r} devices; "
              "this smoke runs only on the chip", file=sys.stderr)
        return 2
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        print(f"[chip_smoke] need {want} chips, found {len(devices)}",
              file=sys.stderr)
        return 2
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    from repro.launch.compile_cache import use_compile_cache

    cache_dir = use_compile_cache()
    watch = CompileWatch()
    log("device", platform=platform, kind=devices[0].device_kind,
        count=len(devices), jax=jax.__version__, compile_cache=cache_dir)

    from repro.serve import SINGLE_PHASE_BUCKETS, TopoServe, TopoServeConfig

    t0 = time.perf_counter()
    router = TopoServe(TopoServeConfig(buckets=SINGLE_PHASE_BUCKETS))
    traffic, over_top = ego_traffic(args.seed, router)
    n_ego = len(traffic)
    top_up(traffic, router, args.seed)
    per_bucket = {f"n{b.n_pad}": {
        s: sum(1 for r in traffic if r["bucket"] == b and r["source"] == s)
        for s in ("ego", "topup")} for b in router.config.buckets}
    log("traffic", network=NETWORK, n_pad=N_PAD, ego_nets=n_ego,
        over_top_rung=over_top, topup=len(traffic) - n_ego,
        per_bucket=per_bucket, setup_s=round(time.perf_counter() - t0, 3),
        peak_bytes=peak_bytes())

    if args.four_chips:
        phase_four_chips(args.seed, traffic, watch)
    else:
        single = phase_topo_single(traffic, watch)
        phase_topo_pallas(traffic, single, watch)
        phase_topo_repack(traffic, single, watch)
        phase_stream(args.seed, watch)
        phase_similarity(args.seed, traffic, watch)
    log("total", compile_s=round(watch.secs, 3), compiles=watch.compiles,
        cache_hits=watch.hits, cache_misses=watch.misses,
        peak_bytes=peak_bytes())
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
