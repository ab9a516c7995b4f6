"""TopoServe: batched persistence-diagram serving on padding buckets.

Turns the batch-at-a-time TDA core into a request-serving path (the
ROADMAP's "serve heavy traffic" direction; docs/ARCHITECTURE.md §TopoServe):

* clients ``submit()`` single graphs (edge list + optional filtering values)
  and get back a ``TopoFuture``;
* the scheduler assigns each request to a **padding bucket** — a fixed
  ``(n_pad, edge_cap, tri_cap)`` shape class — so the number of distinct jit
  signatures is bounded by the bucket ladder, not by the query distribution;
* ``drain()`` packs each bucket's queue into a padded GraphBatch, executes
  the bucket's plan through the process-wide plan cache
  (``repro.core.api.make_topo_plan``), fetches the batch's Diagrams to the
  host in one transfer, and resolves each future with its graph's row.

A bucket whose caps hold every graph of its order is **order-only**: it
admits by order any graph that fits no single-phase bucket, and the server
serves it reduce-first (reduce → measure → repack → persist at the sparse
rungs of ``order_only_ladder_for``), whatever ``repack`` says.  A graph is
answered only where what it reduces to fits one of those rungs; one that
does not fails its own future.  Where the method reduces nothing, that is
known at ``submit``, which rejects the graph there.

The loop is deliberately sync-first (``submit``/``drain`` under one lock) so
it is trivially testable; ``serve_forever`` runs the same drain as a blocking
loop for a dedicated thread, and ``serve_forever_async`` wraps it for an
asyncio event loop.  On a multi-device mesh, bucket batches are padded to a
multiple of the mesh size and sharded over the ("pod", "data") axes via the
plan's shard_map executor (repro/launch/mesh.py::make_serve_mesh).
"""
from __future__ import annotations

import dataclasses
import threading
import time
from collections import deque
from typing import Optional, Sequence

import jax
import numpy as np

from repro import obs
from repro.obs import flight as _flight
from repro.obs.context import DeadlineExceeded, resolve_submit
from repro.core.api import TopoPlan, make_topo_plan
from repro.core.graph import GraphBatch, from_edge_lists
from repro.core.persistence_jax import Diagrams
from repro.core.reduction import passes_for_method
from repro.core.repack import (ShapeClass, default_ladder, holds_every_graph,
                               select_classes)
from repro.serve.futures import ServeFuture

# TopoScope instruments (always on; one series per server instance via the
# ``instance`` label, so tests and multi-server processes never mix stats).
# ``TopoServe.stats`` is a dict-shaped view over these — the registry is
# the single source of truth.
_C_SUBMITTED = obs.counter("serve.submitted",
                           help="requests accepted per bucket")
_C_SERVED = obs.counter("serve.served", help="futures resolved per bucket")
_C_RESOLVE_BYTES = obs.counter(
    "serve.resolve_bytes",
    help="device->host bytes of executed batches' diagrams per bucket")
_C_FAILED = obs.counter("serve.failed", help="futures failed at drain")
_C_BATCHES = obs.counter("serve.batches", help="executed batches per bucket")
_C_PADDED = obs.counter("serve.padded_rows",
                        help="empty pad rows executed (mesh divisibility)")
_C_RUNGS = obs.counter(
    "serve.repack_rungs",
    help="two-phase graphs per (input bucket, persist rung)")
_C_REDUCED_V = obs.counter(
    "serve.reduced_vertices",
    help="post-reduction vertices of two-phase requests per bucket")
_C_REDUCED_E = obs.counter(
    "serve.reduced_edges",
    help="post-reduction edges of two-phase requests per bucket")
_C_REDUCED_T = obs.counter(
    "serve.reduced_triangles",
    help="post-reduction triangles of two-phase requests per bucket")
_C_PERSIST_CALLS = obs.counter(
    "serve.persist_calls",
    help="persist programs run by two-phase batches per bucket")
_H_QWAIT = obs.histogram(
    "serve.queue_wait_seconds",
    help="submit -> drain-pickup wait per request (picked_at - submitted_at)")

# TopoWatch instruments: request outcomes + loop liveness.  The latency
# histogram feeds the per-bucket p50/p99 SLOs (obs/slo.py); the heartbeat
# and ready gauges back /healthz and /readyz (obs/http.py).
_H_LATENCY = obs.histogram(
    "serve.request_latency_seconds",
    help="submit -> resolve wall time per bucket")
_C_DEADLINE = obs.counter(
    "serve.deadline_exceeded",
    help="requests failed by the drain deadline sweep, per bucket")
_C_CANCELLED = obs.counter(
    "serve.cancelled", help="cancelled requests skipped at drain")
_G_HEARTBEAT = obs.gauge(
    "serve.heartbeat_ts",
    help="wall-clock timestamp of the drain loop's last iteration")
_G_READY = obs.gauge(
    "serve.ready", help="1 once serve_forever warmed the bucket plans")


@dataclasses.dataclass(frozen=True, order=True)
class Bucket:
    """One padding bucket == one jit signature class.

    Every graph routed here is padded to ``n_pad`` vertices and persisted
    with this bucket's simplex caps, so all its batches share one compiled
    executable per batch size (and one per (batch,) shape when the server
    pads batches to a fixed multiple).
    """

    n_pad: int
    edge_cap: int
    tri_cap: int

    @property
    def order_only(self) -> bool:
        """Caps that hold every graph of this order: the bucket admits by
        order alone and is served reduce-first, persisting only what fits
        the sparse rungs of ``order_only_ladder_for``."""
        return holds_every_graph(self.n_pad, self.edge_cap, self.tri_cap)


def order_only_bucket(n_pad: int) -> Bucket:
    """The order-only bucket admitting every graph of at most ``n_pad``
    vertices."""
    return Bucket(n_pad=n_pad, edge_cap=n_pad * (n_pad - 1) // 2,
                  tri_cap=n_pad * (n_pad - 1) * (n_pad - 2) // 6)


# Single-phase rungs: ego-net-regime graphs (the paper's §6.2 workload).
# Caps grow with the vertex budget; a graph lands in the first rung that
# fits its order, its edge count and its triangle count.
SINGLE_PHASE_BUCKETS = (
    Bucket(n_pad=16, edge_cap=64, tri_cap=96),
    Bucket(n_pad=32, edge_cap=160, tri_cap=256),
    Bucket(n_pad=64, edge_cap=320, tri_cap=512),
    Bucket(n_pad=128, edge_cap=768, tri_cap=1024),
)
# Above them, order-only rungs for graphs of up to 2,048 vertices (the
# graph-classification corpora, REDDIT-BINARY's threads among them).
DEFAULT_BUCKETS = SINGLE_PHASE_BUCKETS + tuple(
    order_only_bucket(n) for n in (256, 512, 1024, 2048))

# Order-only buckets persist what they reduce to at sparse rungs: orders
# doubling from 8 to half the largest order-only order, with at most 2
# edges a vertex and 64 triangles.  No bound on a reduction gives these
# caps (a cone reduces to one vertex, a cocktail-party graph not at all):
# they are fitted to the post-reduction counts of generated
# REDDIT-BINARY-shaped threads (PERF.md §4): at most 0.44 of the input
# order, 1.51 edges a vertex and 10 triangles.
PERSIST_EDGES_PER_VERTEX = 2
PERSIST_TRI_CAP = 64


@dataclasses.dataclass(frozen=True)
class TopoServeConfig:
    """Scheduler policy + the pipeline parameters shared by every bucket.

    Order-only buckets always take the two-phase plan.  ``repack="on"``
    switches the other buckets to it as well: drain becomes reduce →
    measure → repack → persist, where the persist phase runs at each
    graph's post-reduction :class:`ShapeClass` instead of the input
    bucket's caps.  The persist ladder is shared across buckets (see
    ``repack_ladder_for``), so reduced graphs from different input buckets
    execute the same compiled persist plans.
    """

    buckets: tuple[Bucket, ...] = DEFAULT_BUCKETS
    dim: int = 1
    method: str = "both"
    sublevel: bool = True
    quad_cap: int = 0
    reducer: str = "jnp"
    max_batch: int = 256      # largest executed batch per bucket flush
    pad_batch_to: int = 1     # executed batches padded to a multiple of this
    record_batches: bool = False  # keep (bucket, requests) per executed batch
    repack: str = "off"       # "off" | "on": two-phase reduce→repack→persist


@dataclasses.dataclass(frozen=True)
class TopoRequest:
    """One client graph, host-side (hashable ids only; arrays built at pack)."""

    edges: tuple[tuple[int, int], ...]
    n_vertices: int
    f: Optional[tuple[float, ...]] = None  # None -> degree filtration


class TopoFuture(ServeFuture):
    """Handle for one submitted graph; resolved by a later ``drain()``.

    ``result()`` returns the graph's Diagrams row on the host: NumPy leaves
    shaped (S,), no batch axis, each a copy of its own (the drain fetches
    the whole batch in one device->host transfer).  Thread-safe plumbing —
    including ``cancel()`` and the request id / deadline carried from
    ``submit()`` — lives in ``ServeFuture``.  On the two-phase path
    ``repack_class`` carries the persist :class:`ShapeClass` this request
    was re-bucketed into (set at drain, before the future resolves).
    """

    __slots__ = ("bucket", "repack_class")

    def __init__(self, bucket: Bucket, request_id: Optional[str] = None,
                 deadline: Optional[float] = None):
        super().__init__(request_id=request_id, deadline=deadline)
        self.bucket = bucket
        self.repack_class: ShapeClass | None = None


# a pad row: the empty graph (no live vertex, +inf filtration)
_EMPTY_REQUEST = TopoRequest(edges=(), n_vertices=0)


def pack_requests(reqs: Sequence[TopoRequest], bucket: Bucket,
                  rows: int | None = None) -> GraphBatch:
    """Pad a bucket's requests into one GraphBatch (shared with benchmarks
    so served-vs-direct parity checks run the exact same packing), with
    empty graphs after them up to ``rows`` rows.  Packed on the host and
    uploaded once: padding compiles no program of its own."""
    reqs = tuple(reqs)
    reqs += (_EMPTY_REQUEST,) * ((rows or len(reqs)) - len(reqs))
    if all(r.f is None for r in reqs):
        f_values = None  # from_edge_lists' vectorized degree-filtration default
    else:
        f_values = [r.f if r.f is not None
                    else _degree_f(r.edges, r.n_vertices) for r in reqs]
    return from_edge_lists(
        [list(r.edges) for r in reqs],
        [r.n_vertices for r in reqs],
        n_pad=bucket.n_pad,
        f_values=f_values,
    )


def _degree_f(edges: Sequence[tuple[int, int]], n_vertices: int) -> tuple[float, ...]:
    # dedupe first: duplicate/bidirectional entries must not inflate degrees
    # (from_edge_lists' adjacency-based default dedupes implicitly, and the
    # two paths must agree or co-batching would change a request's numerics)
    deg = np.zeros(n_vertices, dtype=np.float32)
    for (u, v) in {(min(u, v), max(u, v)) for (u, v) in edges if u != v}:
        deg[u] += 1
        deg[v] += 1
    return tuple(float(x) for x in deg)


def repack_ladder_for(buckets: Sequence[Bucket],
                      quad_cap: int = 0) -> tuple[ShapeClass, ...]:
    """The ONE persist-shape ladder shared by every repack-enabled server.

    Rungs are the single-phase buckets themselves (so a reduced graph that
    stays large persists at a familiar bucket shape) plus the default pow2
    sub-rungs below the smallest of them (where most reduced ego-regime
    graphs land); order-only buckets are never rungs.  TopoServe and
    SimilarityServe both derive their ladders here — one definition, one
    set of persist plan-cache keys, so reduced queries from any serving
    surface share compiled persist pipelines.
    """
    single = sorted(b for b in buckets if not b.order_only)
    if not single:
        return ()
    smallest = single[0]
    sub = default_ladder(smallest.n_pad, smallest.edge_cap,
                         smallest.tri_cap, quad_cap)[:-1]
    classes = {ShapeClass(n_pad=b.n_pad, edge_cap=b.edge_cap,
                          tri_cap=b.tri_cap, quad_cap=quad_cap)
               for b in single}
    classes.update(sub)
    return tuple(sorted(classes))


def order_only_ladder_for(buckets: Sequence[Bucket],
                          quad_cap: int = 0) -> tuple[ShapeClass, ...]:
    """The persist ladder of the order-only buckets: sparse rungs of order
    8, 16, ... up to half the largest order-only order, each holding
    ``PERSIST_EDGES_PER_VERTEX`` edges a vertex and ``PERSIST_TRI_CAP``
    triangles (or the complete graph's, where fewer)."""
    top = max((b.n_pad for b in buckets if b.order_only), default=0)
    rungs = []
    n = 8
    while n <= top // 2:
        rungs.append(ShapeClass(
            n_pad=n,
            edge_cap=min(PERSIST_EDGES_PER_VERTEX * n, n * (n - 1) // 2),
            tri_cap=min(PERSIST_TRI_CAP, n * (n - 1) * (n - 2) // 6),
            quad_cap=quad_cap))
        n *= 2
    return tuple(rungs)


def _count_triangles(edge_set) -> int:
    """Host-side triangle count for cap-aware routing: the common
    neighbours of each edge's ends, each triangle seen from its 3 edges."""
    nbrs: dict[int, set] = {}
    for (u, v) in edge_set:
        nbrs.setdefault(u, set()).add(v)
        nbrs.setdefault(v, set()).add(u)
    return sum(len(nbrs[u] & nbrs[v]) for (u, v) in edge_set) // 3


class TopoServe:
    """Bucketed batch scheduler over the plan cache.

    >>> server = TopoServe()
    >>> fut = server.submit(edges=[(0, 1), (1, 2), (2, 0)], n_vertices=3)
    >>> server.drain()
    1
    >>> int(fut.result().betti(0))
    1
    """

    def __init__(self, config: TopoServeConfig | None = None, mesh=None):
        self.config = config or TopoServeConfig()
        if not self.config.buckets:
            raise ValueError("TopoServeConfig.buckets must be non-empty")
        if self.config.repack not in ("off", "on"):
            raise ValueError(
                f"repack must be 'off' or 'on', got {self.config.repack!r}")
        if self.config.repack == "on" and mesh is not None:
            raise ValueError(
                "repack='on' is not supported under a mesh (the repack "
                "phase boundary is host-driven); use repack='off'")
        self.mesh = mesh
        self._buckets = tuple(sorted(self.config.buckets))
        self._single = tuple(b for b in self._buckets if not b.order_only)
        self._order_only = tuple(b for b in self._buckets if b.order_only)
        self._repack_ladder = (
            repack_ladder_for(self._buckets, self.config.quad_cap)
            if self.config.repack == "on" else None)
        self._order_only_ladder = order_only_ladder_for(
            self._buckets, self.config.quad_cap)
        pad = max(1, self.config.pad_batch_to)
        if mesh is not None:
            # executed batches must DIVIDE the mesh (shard_map contract), so
            # round pad up to the next multiple of the mesh size
            n_dev = int(mesh.devices.size)
            pad = -(-pad // n_dev) * n_dev
        self._pad_batch_to = pad
        self._lock = threading.Lock()
        self._queues: dict[Bucket, deque] = {b: deque() for b in self._buckets}
        self._stopped = threading.Event()
        # (bucket, requests, futures) per executed batch when record_batches
        self.executed_batches: list[tuple] = []
        self._obs_instance = obs.next_instance("topo")
        # bucket -> stable label ("n32"); n_pad collisions disambiguate by
        # caps so per-bucket registry series stay distinct
        self._bucket_label: dict[Bucket, str] = {}
        for b in self._buckets:
            lbl = f"n{b.n_pad}"
            if lbl in self._bucket_label.values():
                lbl = f"n{b.n_pad}e{b.edge_cap}"
            if lbl in self._bucket_label.values():
                lbl = f"n{b.n_pad}e{b.edge_cap}t{b.tri_cap}"
            self._bucket_label[b] = lbl

    @property
    def stats(self) -> dict:
        """Dict-shaped view over the TopoScope registry (backward compat:
        the pre-TopoScope ad-hoc ``stats`` dict, same keys and key types).
        Mutating the returned dict has no effect — counters live in
        ``repro.obs``."""
        inst = self._obs_instance
        per_bucket = {}
        for b in self._buckets:
            lbl = self._bucket_label[b]
            per_bucket[b] = {
                "submitted": int(_C_SUBMITTED.value(instance=inst,
                                                    bucket=lbl)),
                "served": int(_C_SERVED.value(instance=inst, bucket=lbl)),
                "batches": int(_C_BATCHES.value(instance=inst, bucket=lbl)),
            }
        rungs = {}
        for key, v in _C_RUNGS.series().items():
            d = dict(key)
            if d.get("instance") != inst:
                continue
            rungs[(int(d["bucket"][1:]), int(d["rung"][1:]))] = int(v)
        return {
            "submitted": sum(pb["submitted"] for pb in per_bucket.values()),
            "served": sum(pb["served"] for pb in per_bucket.values()),
            "failed": int(_C_FAILED.value(instance=inst)),
            # per-bucket series summed over this instance
            "deadline_exceeded": int(_C_DEADLINE.total(instance=inst)),
            "cancelled": int(_C_CANCELLED.total(instance=inst)),
            "batches": sum(pb["batches"] for pb in per_bucket.values()),
            "padded_rows": int(_C_PADDED.value(instance=inst)),
            # two-phase: {(bucket n_pad, persist rung n_pad): graphs} —
            # rungs keyed by >1 bucket are shared compiled persist plans
            "repack_rungs": rungs,
            "per_bucket": per_bucket,
        }

    # ------------------------------------------------------------- routing

    def bucket_for(self, n_vertices: int, n_edges: int,
                   n_triangles: int = 0) -> Bucket:
        """Deterministic bucket assignment: the smallest single-phase rung
        (buckets are totally ordered by (n_pad, edge_cap, tri_cap)) whose
        capacities hold every simplex of the graph — exactness requires
        caps >= the true counts (docs/ARCHITECTURE.md §GraphBatch
        invariants), so a triangle-dense graph is promoted past rungs its
        edge count fits — else the smallest order-only rung that holds its
        order."""
        for b in self._single:
            if (n_vertices <= b.n_pad and n_edges <= b.edge_cap
                    and n_triangles <= b.tri_cap):
                return b
        for b in self._order_only:
            if n_vertices <= b.n_pad:
                return b
        raise ValueError(
            f"graph with {n_vertices} vertices / {n_edges} edges / "
            f"{n_triangles} triangles exceeds every bucket "
            f"(largest: {self._buckets[-1]})")

    def plan_for(self, bucket: Bucket) -> TopoPlan:
        """The bucket's compiled pipeline, via the process-wide plan cache.

        Order-only buckets get a two-phase plan on the order-only ladder,
        and with ``repack="on"`` every other bucket one on the serve-wide
        persist ladder; either way the buckets' reduced-size persist plans
        coincide in the plan cache whenever reductions land on the same
        rung.  The two-phase plan runs on one device: its phase boundary is
        host-driven.
        """
        c = self.config
        if bucket.order_only:
            ladder = self._order_only_ladder
        else:
            ladder = self._repack_ladder
        return make_topo_plan(
            dim=c.dim, method=c.method, sublevel=c.sublevel,
            edge_cap=bucket.edge_cap, tri_cap=bucket.tri_cap,
            quad_cap=c.quad_cap, reducer=c.reducer,
            mesh=None if ladder is not None else self.mesh,
            repack="off" if ladder is None else "on", ladder=ladder,
        )

    # ------------------------------------------------------------- ingest

    def submit(self, edges: Sequence[tuple[int, int]], n_vertices: int,
               f: Sequence[float] | None = None, *,
               request_id: Optional[str] = None,
               deadline_s: Optional[float] = None) -> TopoFuture:
        """Enqueue one graph; returns a future resolved by a later drain.

        Malformed requests are rejected HERE (ValueError) so they can never
        poison a batch and fail co-batched clients' futures at drain time.

        Every request gets an id (explicit ``request_id``, the ambient
        ``obs.request_context()`` id, or a fresh mint) and an optional
        deadline: ``deadline_s`` is relative seconds-from-now, clamped to
        any ambient context deadline.  Expired requests are failed with
        :class:`~repro.obs.DeadlineExceeded` by the drain sweep instead of
        executing late for nobody; cancelled futures are skipped the same
        way.
        """
        req = TopoRequest(
            edges=tuple((int(u), int(v)) for (u, v) in edges),
            n_vertices=int(n_vertices),
            f=None if f is None else tuple(float(x) for x in f),
        )
        if req.n_vertices < 1:
            raise ValueError(f"n_vertices must be >= 1, got {req.n_vertices}")
        for (u, v) in req.edges:
            if not (0 <= u < req.n_vertices and 0 <= v < req.n_vertices):
                raise ValueError(
                    f"edge ({u}, {v}) out of range for n_vertices="
                    f"{req.n_vertices}")
        if req.f is not None and len(req.f) != req.n_vertices:
            raise ValueError(
                f"f has {len(req.f)} values for {req.n_vertices} vertices")
        edge_set = {(min(u, v), max(u, v)) for (u, v) in req.edges if u != v}
        # triangles matter only where a single-phase rung holds the order
        # and the edges; any other graph is routed by its order alone
        n_tri = (_count_triangles(edge_set)
                 if any(req.n_vertices <= b.n_pad
                        and len(edge_set) <= b.edge_cap for b in self._single)
                 else 0)
        bucket = self.bucket_for(req.n_vertices, len(edge_set), n_tri)
        if bucket.order_only and not passes_for_method(self.config.method):
            # nothing reduces, so the graph persists as it came: admit it
            # only where a rung of the persist ladder holds it
            n_tri = _count_triangles(edge_set)
            if select_classes(self._order_only_ladder, [req.n_vertices],
                              [len(edge_set)], [n_tri])[0] < 0:
                raise ValueError(
                    f"graph with {req.n_vertices} vertices / "
                    f"{len(edge_set)} edges / {n_tri} triangles fits no "
                    f"persist rung (largest "
                    f"{max(self._order_only_ladder, default=None)}) and "
                    f"method={self.config.method!r} reduces nothing")
        rid, deadline = resolve_submit(request_id, deadline_s)
        fut = TopoFuture(bucket, request_id=rid, deadline=deadline)
        with self._lock:
            self._queues[bucket].append((req, fut))
        _C_SUBMITTED.inc(instance=self._obs_instance,
                         bucket=self._bucket_label[bucket])
        return fut

    def pending(self) -> int:
        with self._lock:
            return sum(len(q) for q in self._queues.values())

    # ------------------------------------------------------------- drain

    def drain(self) -> int:
        """Execute every queued request, bucket by bucket; returns #served.

        Bucket queues are flushed in submission order, chunked at
        ``max_batch`` and padded (with empty graphs, dropped after execution)
        to a multiple of ``pad_batch_to`` so sharded plans always see a batch
        that divides the mesh.  Buckets are swept round-robin — one chunk per
        bucket per sweep — so sustained traffic into one bucket cannot starve
        requests queued in the others.

        Before each chunk executes, the TopoWatch sweep drops cancelled
        futures and fails expired ones with ``DeadlineExceeded`` — both
        counted per bucket — so the batch only carries requests somebody is
        still waiting for."""
        if not self.pending():
            return 0  # keep idle poll loops out of the trace
        with obs.span("serve.drain", frontend="topo") as sp:
            served = 0
            while True:
                progressed = False
                for b in self._buckets:
                    with self._lock:
                        q = self._queues[b]
                        items = [q.popleft()
                                 for _ in range(min(len(q),
                                                    self.config.max_batch))]
                        queued = len(q)
                    if items:
                        progressed = True
                        items = self._sweep(b, items)
                    if items:
                        served += self._execute(b, items, queued)
                if not progressed:
                    sp.set(served=served)
                    return served

    def _sweep(self, bucket: Bucket, items: list) -> list:
        """Drop cancelled requests and fail expired ones (deadline sweep)."""
        inst = self._obs_instance
        lbl = self._bucket_label[bucket]
        now = time.monotonic()
        live = []
        for (req, fut) in items:
            if fut.cancelled():
                _C_CANCELLED.inc(instance=inst, bucket=lbl)
                _flight.record("serve", "cancelled_skip", frontend="topo",
                               bucket=lbl, rid=fut.request_id or "")
                continue
            if fut.expired(now):
                if fut._fail(DeadlineExceeded(
                        f"request {fut.request_id or '?'} expired "
                        f"{now - fut.deadline:.3f}s before drain pickup "
                        f"(bucket {lbl})")):
                    _C_DEADLINE.inc(instance=inst, bucket=lbl)
                    _flight.record("serve", "deadline_exceeded",
                                   frontend="topo", bucket=lbl,
                                   rid=fut.request_id or "",
                                   late_s=round(now - fut.deadline, 4))
                    _flight.auto_dump("deadline_exceeded")
                continue
            live.append((req, fut))
        return live

    def _execute(self, bucket: Bucket, items: list, queued: int) -> int:
        """Run one batch and resolve its futures; returns how many it
        answered.  ``queued`` is how many requests the bucket's queue still
        held after the batch was cut."""
        inst = self._obs_instance
        lbl = self._bucket_label[bucket]
        reqs = tuple(r for (r, _) in items)
        futs = [f for (_, f) in items]
        now = time.perf_counter()
        for f in futs:
            f.picked_at = now
            _H_QWAIT.observe(f.picked_at - f.submitted_at, instance=inst)
        repack_info = None
        with obs.span("serve.batch", frontend="topo", bucket=lbl,
                      graphs=len(items), queued=queued):
            try:
                n_pad_rows = (-len(reqs)) % self._pad_batch_to
                with obs.span("serve.gather", bucket=lbl, pad=n_pad_rows):
                    g = pack_requests(reqs, bucket, len(reqs) + n_pad_rows)
                plan = self.plan_for(bucket)
                if plan.key.repack == "on":
                    # two-phase drain: reduce → measure → repack → persist;
                    # the report carries each request's persist-rung
                    # assignment (plan.* spans nest here)
                    d, repack_info = plan.execute_info(g)
                else:
                    d = plan.execute(g)
                with obs.span("serve.sync"):
                    jax.block_until_ready(d.birth)
            except Exception as e:  # resolve, don't wedge waiting clients
                n_failed = sum(1 for f in futs if f._fail(e))
                if n_failed:
                    _C_FAILED.inc(n_failed, instance=inst)
                _flight.record("serve", "batch_failed", frontend="topo",
                               bucket=lbl, graphs=len(futs), error=repr(e))
                return 0
            if self.config.record_batches:
                self.executed_batches.append((bucket, reqs, tuple(futs)))
            served = n_failed = 0
            with obs.span("serve.resolve") as sp:
                # one device->host transfer per leaf for the whole batch
                # (pad rows included), then a host-owned copy of each row
                # so a client holding one answer does not pin the batch
                host = jax.device_get(d)
                n_bytes = sum(x.nbytes for x in jax.tree.leaves(host))
                sp.set(bytes=n_bytes)
                for i, f in enumerate(futs):
                    err = None
                    if repack_info is not None:
                        f.repack_class = repack_info.shape_class(i)
                        err = repack_info.misfit(i)
                    if err is not None:
                        # a graph no persist rung holds fails alone
                        n_failed += f._fail(err)
                        continue
                    served += 1
                    if f._resolve(jax.tree.map(lambda x: np.array(x[i]),
                                               host)):
                        _H_LATENCY.observe(f.latency_s(),
                                           instance=inst, bucket=lbl)
        _C_RESOLVE_BYTES.inc(n_bytes, instance=inst, bucket=lbl)
        _C_SERVED.inc(served, instance=inst, bucket=lbl)
        _C_BATCHES.inc(instance=inst, bucket=lbl)
        _flight.record("serve", "batch", frontend="topo", bucket=lbl,
                       graphs=len(futs))
        if n_failed:
            _C_FAILED.inc(n_failed, instance=inst)
        if n_pad_rows:
            _C_PADDED.inc(n_pad_rows, instance=inst)
        if repack_info is not None:
            k = len(futs)
            _C_REDUCED_V.inc(int(repack_info.n_vertices[:k].sum()),
                             instance=inst, bucket=lbl)
            _C_REDUCED_E.inc(int(repack_info.n_edges[:k].sum()),
                             instance=inst, bucket=lbl)
            _C_REDUCED_T.inc(int(repack_info.n_triangles[:k].sum()),
                             instance=inst, bucket=lbl)
            _C_PERSIST_CALLS.inc(repack_info.persist_calls,
                                 instance=inst, bucket=lbl)
            for i in range(k):
                sc = repack_info.shape_class(i)
                if sc is not None:
                    _C_RUNGS.inc(instance=inst, bucket=f"n{bucket.n_pad}",
                                 rung=f"n{sc.n_pad}")
        return served

    # ------------------------------------------------------------- loops

    def warmup(self) -> None:
        """Build every bucket's plan through the process-wide plan cache.

        Called by ``serve_forever`` before raising ``serve.ready`` so
        ``/readyz`` flipping to 200 means plan construction cost is paid —
        the first live request will not eat it.
        """
        for b in self._buckets:
            self.plan_for(b)

    def _loop_enter(self) -> None:
        inst = self._obs_instance
        _flight.record("serve", "loop_start", frontend="topo", instance=inst)
        self.warmup()
        _G_HEARTBEAT.set(time.time(), frontend="topo", instance=inst)
        _G_READY.set(1, frontend="topo", instance=inst)

    def _loop_exit(self) -> None:
        inst = self._obs_instance
        _G_READY.set(0, frontend="topo", instance=inst)
        _flight.record("serve", "loop_stop", frontend="topo", instance=inst)

    def _drain_guarded(self) -> int:
        """One loop iteration: heartbeat + drain; flight-dump on escape.

        ``drain`` fails co-batched futures on per-batch errors, so anything
        escaping here is a scheduler bug — dump the flight ring before the
        loop dies so the wreckage is on disk even with tracing off.
        """
        _G_HEARTBEAT.set(time.time(), frontend="topo",
                         instance=self._obs_instance)
        try:
            return self.drain()
        except BaseException as e:
            _flight.record("serve", "drain_exception", frontend="topo",
                           error=repr(e))
            _flight.auto_dump("drain_exception")
            raise

    def serve_forever(self, poll_s: float = 1e-3) -> None:
        """Blocking drain loop (run on a dedicated thread); stop() exits it.

        Warms the bucket plans then raises ``serve.ready`` (readiness) and
        stamps ``serve.heartbeat_ts`` every iteration (liveness) — the
        gauges behind ``/readyz`` and ``/healthz``.
        """
        self._loop_enter()
        try:
            while not self._stopped.is_set():
                if self._drain_guarded() == 0:
                    self._stopped.wait(poll_s)
        finally:
            self._loop_exit()

    async def serve_forever_async(self, poll_s: float = 1e-3) -> None:
        """Same loop for an asyncio host.  Each drain (jit dispatch +
        block_until_ready, potentially hundreds of ms per batch) runs on a
        worker thread so request-ingestion / health-check coroutines keep
        interleaving on the event loop."""
        import asyncio

        await asyncio.to_thread(self._loop_enter)
        try:
            while not self._stopped.is_set():
                if await asyncio.to_thread(self._drain_guarded) == 0:
                    await asyncio.sleep(poll_s)
        finally:
            self._loop_exit()

    def stop(self) -> None:
        self._stopped.set()

