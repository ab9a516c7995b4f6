"""High-level TopoPipe API: reduce -> repack -> persist, batched & shardable.

This is the paper's contribution packaged as a composable JAX module: feed a
GraphBatch, choose a reduction (a legacy method name or an explicit pass
tuple from the :mod:`repro.core.reduction` registry), get exact persistence
diagrams.  All functions are jit/vmap/pjit friendly; the launch layer shards
batches over the ("pod", "data") mesh axes.

Compilation is organised as an explicit **plan -> execute** split (see
docs/ARCHITECTURE.md §Plan/Execute): ``make_topo_plan(...)`` returns a
``TopoPlan`` — one compiled pipeline per distinct ``TopoPlanKey``, held in a
process-wide LRU cache — and ``topological_signature`` is a thin wrapper
over it.  The serve layer (repro/serve/topo_serve.py), the feature pipeline
(repro/topo/features.py) and the benchmarks all go through this one path, so
a given pipeline shape is compiled exactly once per process.

Plans execute in one of two modes (docs/ARCHITECTURE.md §ReductionEngine):

* ``repack="off"`` (default) — the historical single-phase path: one jitted
  (or shard_mapped) reduce→persist body compiled at the *input* caps.  This
  is the parity oracle for everything below.
* ``repack="on"`` — two-phase: a jitted **reduce plan** (fixpoint pass
  iteration + vertex compaction + simplex-count measurement) runs at input
  caps; the host then re-buckets every reduced graph into the smallest
  :class:`~repro.core.repack.ShapeClass` of a bounded ladder and executes a
  **persist plan** (``passes=()``) per rung — so the expensive GF(2) stage
  compiles and runs at *reduced* size.  Persist plans live in the same plan
  cache, keyed only by their rung, and are therefore shared by every caller
  (serve buckets, stream sessions) whose reductions land on the same rung.
"""
from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import threading
from collections import OrderedDict
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.graph import GraphBatch
from repro.core.persistence_jax import Diagrams, persistence_diagrams_counted
from repro.core.reduction import (
    apply_passes,
    engine_exact_from_dim,
    method_for_passes,
    passes_for_method,
    run_reduction,
    validate_passes,
)
from repro.core.repack import (
    RepackReport,
    ShapeClass,
    compact_batch,
    default_ladder,
    diagram_size,
    holds_every_graph,
    measure_counts,
    select_classes,
    slice_to,
)


REDUCTIONS = ("none", "coral", "prunit", "both")
REPACK_MODES = ("off", "on")


def reduce_graphs(g: GraphBatch, dim: int, method: str = "both",
                  sublevel: bool = True) -> GraphBatch:
    """Apply the paper's reduction(s) for computing PD_dim (one sweep).

    Thin wrapper over the pass engine: ``method`` maps to a pass tuple
    (``"both"`` → ``("prunit", "kcore")``) applied once in order — the
    single-phase reduction every ``repack="off"`` plan compiles.
    """
    if method not in REDUCTIONS:
        raise ValueError(f"unknown reduction {method!r}; want one of {REDUCTIONS}")
    return apply_passes(g, passes_for_method(method), dim, sublevel)


@dataclasses.dataclass(frozen=True)
class TopoPlanKey:
    """Hashable identity of one compiled TDA pipeline (the plan-cache key).

    Two calls that agree on every field share one ``TopoPlan`` and therefore
    one jit cache; anything not in this key (batch size, padded order) is a
    jit shape specialization *inside* the plan, not a new plan.

    ``passes`` replaces the former ``method`` string (legacy names still
    accepted at ``make_topo_plan``); ``repack`` selects single- vs two-phase
    execution, ``fixpoint`` whether the pass list iterates to its joint
    fixpoint or runs one sweep, and ``ladder`` optionally pins the persist
    shape classes (``None`` derives the default ladder from the input shape
    at execute time — see repro/core/repack.py).
    """

    dim: int
    passes: tuple[str, ...]
    sublevel: bool
    edge_cap: int
    tri_cap: int
    quad_cap: int
    reducer: str
    mesh: Any = None  # jax.sharding.Mesh (hashable) or None for single-host
    repack: str = "off"
    fixpoint: bool = False
    ladder: Optional[tuple[ShapeClass, ...]] = None

    def caps(self) -> tuple[int, int, int]:
        return (self.edge_cap, self.tri_cap, self.quad_cap)

    @property
    def method(self) -> str:
        return method_for_passes(self.passes)


@dataclasses.dataclass(frozen=True)
class TopoPlan:
    """A compiled reduce->persist pipeline plus its static metadata.

    ``execute`` (alias ``__call__``) maps a GraphBatch to Diagrams.  With
    ``repack="off"`` that is a single jitted (or shard_mapped, when the plan
    carries a mesh) program; with ``repack="on"`` it is the two-phase driver
    — ``reduce_plan`` (jitted) → host repack → per-rung ``persist_plan``
    execution — and ``execute_info`` additionally returns the
    :class:`~repro.core.repack.RepackReport` of rung assignments.
    Inside ``collect_columns()`` each persist program executed also
    reports the column steps its GF(2) loops ran.
    ``executor`` is the program a batch enters first: the whole pipeline
    single-phase, the reduce plan two-phase.  The plan
    object is safe to hold across requests — re-executing with the same
    (B, N) shape never recompiles.
    """

    key: TopoPlanKey
    executor: Optional[Callable[[GraphBatch], Diagrams]] = None
    reduce_executor: Optional[Callable] = None
    _ladders: dict = dataclasses.field(
        default_factory=dict, compare=False, repr=False)
    _warmed: set = dataclasses.field(
        default_factory=set, compare=False, repr=False)

    def execute(self, g: GraphBatch) -> Diagrams:
        if self.key.repack == "on":
            d, info = self.execute_info(g)
            bad = np.flatnonzero(info.class_index < 0)
            if bad.size:
                raise info.misfit(int(bad[0]))
            return d
        # dispatch is async: this span covers trace/dispatch, not device
        # time — callers that block (serve) wrap the sync in serve.sync
        with obs.span("plan.execute", graphs=g.batch, n=g.n):
            d, cols = self.executor(g)
        sink = _COLUMNS.get()
        if sink is not None:
            sink.append(cols)
        return d

    def __call__(self, g: GraphBatch) -> Diagrams:
        return self.execute(g)

    @property
    def dim(self) -> int:
        return self.key.dim

    @property
    def method(self) -> str:
        return self.key.method

    @property
    def passes(self) -> tuple[str, ...]:
        return self.key.passes

    @property
    def sublevel(self) -> bool:
        return self.key.sublevel

    def exact_from_dim(self) -> int:
        """Lowest homology dimension this plan's reduction preserves."""
        return engine_exact_from_dim(self.key.passes, self.key.dim)

    # --------------------------------------------------------- two-phase

    @property
    def reduce_plan(self) -> Optional[Callable]:
        """Phase 1 (``repack="on"``): jitted fixpoint-reduce + compact +
        measure; ``reduce_plan(g) -> (compacted GraphBatch, (nv, ne, nt))``.
        """
        return self.reduce_executor

    def persist_plan(self, sc: ShapeClass) -> "TopoPlan":
        """Phase 2: the compiled no-reduction persist pipeline of one rung.

        Keyed only by ``(dim, (), sublevel, rung caps, reducer)`` in the
        process-wide cache — every caller whose reduced graphs land on this
        rung shares the same compiled executable.
        """
        return make_topo_plan(
            dim=self.key.dim, passes=(), sublevel=self.key.sublevel,
            edge_cap=sc.edge_cap, tri_cap=sc.tri_cap, quad_cap=sc.quad_cap,
            reducer=self.key.reducer)

    def ladder_for(self, n: int) -> tuple[ShapeClass, ...]:
        """The persist ladder used for input padded order ``n``.

        Custom ladders (``key.ladder``) are sanitized per input shape: rungs
        wider than the input order or with caps above the plan's caps are
        dropped — they can never be *needed* (every graph fits the input
        shape, and a wider rung would emit more diagram rows than the
        single-phase row count the output is padded to) — and a top rung at
        exactly the input shape is appended so first-fit always lands.  This
        keeps one ladder shareable across serve buckets whose plans differ
        in caps (non-monotone bucket configs included).
        """
        k = self.key
        lad = self._ladders.get(n)
        if lad is not None:
            return lad
        if k.ladder is not None:
            top = ShapeClass(n_pad=n, edge_cap=k.edge_cap,
                             tri_cap=k.tri_cap, quad_cap=k.quad_cap)
            # tetrahedra are never measured (the one count that does not
            # pay for itself), so when quads are live (dim >= 2) a rung
            # must carry the plan's quad_cap verbatim — smaller would
            # silently truncate, larger would overflow the row budget
            quads_live = k.dim >= 2 and k.quad_cap
            fits = {c for c in k.ladder
                    if (c.n_pad <= n and c.edge_cap <= k.edge_cap
                        and c.tri_cap <= k.tri_cap
                        and (c.quad_cap == k.quad_cap if quads_live
                             else c.quad_cap <= k.quad_cap))}
            if not self.order_only(n):
                fits.add(top)
            lad = tuple(sorted(fits))
        else:
            lad = default_ladder(
                n, k.edge_cap, k.tri_cap if k.dim >= 1 else 0,
                k.quad_cap if k.dim >= 2 else 0)
        return self._ladders.setdefault(n, lad)

    def order_only(self, n: int) -> bool:
        """Whether this two-phase plan's caps hold every graph of padded
        order ``n``: it then persists only at the rungs of its ladder."""
        k = self.key
        return (k.repack == "on" and k.ladder is not None
                and holds_every_graph(n, k.edge_cap, k.tri_cap))

    def execute_info(self, g: GraphBatch
                     ) -> tuple[Diagrams, Optional[RepackReport]]:
        """Execute, also returning the repack report (``None`` when off).

        Two-phase driver: reduce/compact/measure under one jitted program,
        fetch the per-graph counts to the host (the one phase-boundary
        sync), group graphs by first-fit shape class, run each group —
        padded to 8, 32, 128, ... rows and at most the batch, so jit
        signatures stay few — through its rung's persist plan, and
        scatter the rows into an input-order Diagrams tensor as wide as
        the ladder's widest rung (rows past a rung's capacity are invalid
        padding, so downstream masked arithmetic and canonical-pair
        extraction see one shape).  A graph reduced to no vertex has an
        empty diagram and runs no persist program; a graph that fits no
        rung keeps an invalid row and index -1 in the report
        (``RepackReport.misfit``).  An order-only plan compiles, on its
        first batch of a shape, every persist program that shape can form,
        so no later batch compiles.
        """
        if self.key.repack != "on":
            return self.execute(g), None
        k = self.key
        with obs.span("plan.reduce", graphs=g.batch, n=g.n):
            gc, counts = self.reduce_executor(g)
        with obs.span("plan.measure"):  # the one phase-boundary host sync
            nv, ne, nt = (np.asarray(c) for c in counts)
        with obs.span("plan.repack"):
            ladder = self.ladder_for(g.n)
            cls_idx = select_classes(ladder, nv, ne, nt)
        s_out = max((diagram_size(sc.n_pad, k.dim, sc.edge_cap, sc.tri_cap,
                                  sc.quad_cap) for sc in ladder), default=0)
        out = _invalid_diagrams(g.batch, s_out)
        if self.order_only(g.n) and (g.batch, g.n) not in self._warmed:
            # set-up: its column steps are not collected
            with obs.span("plan.warm", graphs=g.batch, n=g.n), \
                    collect_columns():
                for sc in ladder:
                    for r in _group_sizes(g.batch):
                        self._persist_into(out, gc, np.zeros(r, np.int32),
                                           sc)
            self._warmed.add((g.batch, g.n))
        live = (cls_idx >= 0) & (nv > 0)
        calls = 0
        for ci in sorted(set(cls_idx[live].tolist())):
            sc = ladder[ci]
            idx = np.nonzero(live & (cls_idx == ci))[0].astype(np.int32)
            r = _group_rows(len(idx), g.batch)
            with obs.span("plan.persist", rung=f"n{sc.n_pad}",
                          graphs=len(idx)):
                idx_p = np.concatenate(
                    [idx, np.full(r - len(idx), idx[0], np.int32)])
                out = self._persist_into(out, gc, idx_p, sc)
            calls += 1
        report = RepackReport(ladder=ladder, class_index=cls_idx,
                              n_vertices=nv, n_edges=ne, n_triangles=nt,
                              persist_calls=calls)
        return out, report

    def _persist_into(self, out: Diagrams, gc: GraphBatch, idx: np.ndarray,
                      sc: ShapeClass) -> Diagrams:
        """Persist compacted graphs ``idx`` at rung ``sc`` and write their
        rows into ``out``.  Padding entries repeat a real index, so their
        rows are that graph's and the scatter stays exact."""
        sub = _take_rows(gc, idx, n_pad=sc.n_pad)
        return _put_rows(out, self.persist_plan(sc).execute(sub), idx)


_COLUMNS: contextvars.ContextVar[Optional[list]] = contextvars.ContextVar(
    "topo_plan_columns", default=None)


@contextlib.contextmanager
def collect_columns():
    """Collect the GF(2) column steps of the plan programs this thread
    executes inside the block.

    Yields a list that gains one device array per persist program run, (2,)
    i32 [column steps run, column slots of the caps], or (devices, 2) under
    a mesh (``persistence_diagrams_counted``).  The arrays stay on the
    device, so the caller fetches them with the diagrams in one transfer.
    """
    sink: list = []
    token = _COLUMNS.set(sink)
    try:
        yield sink
    finally:
        _COLUMNS.reset(token)


def _group_rows(n: int, batch: int) -> int:
    """Rows a persist group of ``n`` graphs runs at: 8, 32, 128, ...,
    at most the batch."""
    r = 8
    while r < n:
        r *= 4
    return min(r, batch)


def _group_sizes(batch: int) -> list[int]:
    """Every row count ``_group_rows`` gives for a batch of ``batch``."""
    return sorted({_group_rows(n, batch) for n in range(1, batch + 1)})


@partial(jax.jit, static_argnames=("n_pad",))
def _take_rows(g: GraphBatch, idx: jax.Array, n_pad: int) -> GraphBatch:
    return jax.tree.map(lambda x: x[idx], slice_to(g, n_pad))


@jax.jit
def _put_rows(out: Diagrams, d: Diagrams, idx: jax.Array) -> Diagrams:
    d = _pad_diagram_rows(d, out.birth.shape[-1])
    return jax.tree.map(lambda o, x: o.at[idx].set(x), out, d)


def _invalid_diagrams(b: int, s: int) -> Diagrams:
    """An all-invalid Diagrams tensor matching pairs_to_diagrams sentinels,
    built on the host and uploaded (no device program to compile)."""
    return jax.device_put(Diagrams(
        birth=np.full((b, s), np.nan, np.float32),
        death=np.full((b, s), np.nan, np.float32),
        dim=np.full((b, s), -1, np.int32),
        valid=np.zeros((b, s), bool),
    ))


def _pad_diagram_rows(d: Diagrams, s: int) -> Diagrams:
    """Pad a (B, S_r) Diagrams to (B, s) with invalid sentinel rows."""
    pad = s - d.birth.shape[-1]
    if pad <= 0:
        return d
    cfg = ((0, 0), (0, pad))
    return Diagrams(
        birth=jnp.pad(d.birth, cfg, constant_values=jnp.nan),
        death=jnp.pad(d.death, cfg, constant_values=jnp.nan),
        dim=jnp.pad(d.dim, cfg, constant_values=-1),
        valid=jnp.pad(d.valid, cfg, constant_values=False),
    )


def _pipeline(g: GraphBatch, key: TopoPlanKey) -> tuple[Diagrams, jax.Array]:
    """The one reduce->persist body every single-phase execution compiles;
    returns the diagrams and the GF(2) loops' column steps
    (``persistence_diagrams_counted``).

    The two phases carry the names of the two-phase path's host spans
    (``plan.reduce``, ``plan.persist``) as named scopes, so each device op's
    ``op_name`` metadata, and with it the profiler trace, says which phase
    it belongs to."""
    with jax.named_scope("plan.reduce"):
        gr = run_reduction(g, key.passes, key.dim, key.sublevel,
                           key.fixpoint)
    with jax.named_scope("plan.persist"):
        return persistence_diagrams_counted(
            gr, max_dim=key.dim, edge_cap=key.edge_cap,
            tri_cap=key.tri_cap, quad_cap=key.quad_cap,
            sublevel=key.sublevel, reducer=key.reducer,
        )


def _plan_name(key: TopoPlanKey, kind: str = "plan") -> str:
    """Stable name of a plan's jitted program (``jit_<name>`` is its HLO
    module in a profiler trace), e.g. ``topo_plan_prunit_e64_t96_d1``."""
    method = key.method.replace("+", "_")
    return (f"topo_{kind}_{method}_e{key.edge_cap}_t{key.tri_cap}"
            f"_d{key.dim}")


def _named(fn: Callable, name: str) -> Callable:
    fn.__name__ = fn.__qualname__ = name
    return fn


def _build_executor(key: TopoPlanKey
                    ) -> Callable[[GraphBatch], tuple[Diagrams, jax.Array]]:
    if key.mesh is None:
        return jax.jit(_named(lambda g: _pipeline(g, key), _plan_name(key)))

    # shard_map pins the whole pipeline per-device (zero collectives — under
    # plain pjit GSPMD cannot partition the vmapped scatter/gather/top-k ops
    # and inserts 0.6-3 GB/device batch all-gathers on a 256-chip mesh,
    # §Perf iteration 5).  The global batch must divide the mesh size; the
    # serve layer pads bucket batches to guarantee this.
    from jax.sharding import PartitionSpec as P

    mesh = key.mesh
    spec = P(tuple(mesh.axis_names))

    def per_device(adj, mask, f):
        # each device bounds its own loops: one column-steps row a device
        d, cols = _pipeline(GraphBatch(adj=adj, mask=mask, f=f), key)
        return d, cols[None]

    sharded = jax.shard_map(
        per_device, mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=(Diagrams(birth=spec, death=spec, dim=spec, valid=spec),
                   spec),
        check_vma=False,
    )

    def executor(g: GraphBatch) -> tuple[Diagrams, jax.Array]:
        return sharded(g.adj, g.mask, g.f)

    return executor


def _build_reduce_executor(key: TopoPlanKey) -> Callable:
    """Phase 1 of a two-phase plan: reduce + compact + measure.

    Honors ``key.fixpoint`` like the single-phase body: the default for
    ``repack="on"`` is fixpoint iteration, but ``fixpoint=False`` keeps the
    one-sweep reduction (useful for benchmarking sweep vs fixpoint through
    the identical two-phase machinery).
    """
    count_tris = key.dim >= 1 and key.tri_cap > 0

    def reduce_phase(g: GraphBatch):
        with jax.named_scope("plan.reduce"):
            gr = run_reduction(g, key.passes, key.dim, key.sublevel,
                               key.fixpoint)
            gc, _ = compact_batch(gr)
            return gc, measure_counts(gc, count_triangles=count_tris)

    return jax.jit(_named(reduce_phase, _plan_name(key, "reduce")))


_PLAN_CACHE: "OrderedDict[TopoPlanKey, TopoPlan]" = OrderedDict()
_PLAN_CACHE_MAXSIZE = 64
_PLAN_CACHE_LOCK = threading.Lock()
_PLAN_CACHE_STATS = {"hits": 0, "misses": 0, "evictions": 0}

# TopoScope mirrors of the cache counters (always on; reset by
# clear_plan_cache alongside _PLAN_CACHE_STATS so the two never drift)
_OBS_PC_EVENTS = obs.counter(
    "plancache.events", help="TopoPlan cache hits/misses/evictions")


def make_topo_plan(
    dim: int = 1,
    method: str = "both",
    sublevel: bool = True,
    edge_cap: int = 256,
    tri_cap: int = 512,
    quad_cap: int = 0,
    reducer: str = "jnp",
    mesh=None,
    passes: Optional[tuple] = None,
    repack: str = "off",
    fixpoint: Optional[bool] = None,
    ladder: Optional[tuple] = None,
) -> TopoPlan:
    """Plan step of the plan->execute split: build or fetch a compiled pipeline.

    Returns the process-wide ``TopoPlan`` for this key (LRU-cached, thread
    safe).  Callers that execute many batches — TopoServe buckets, training
    epochs, benchmark sweeps — should hold the plan and call it directly.

    ``passes`` (a tuple of registry names, see repro/core/reduction.py)
    overrides the legacy ``method`` string.  ``repack="on"`` selects
    two-phase execution (reduce → repack → persist at reduced shape
    classes); ``fixpoint`` defaults to True exactly then, so the reduce
    phase extracts everything the theorems allow before sizing the persist
    phase.  ``ladder`` pins the persist shape classes (e.g. a serve bucket
    ladder); ``None`` derives the default pow2 ladder from the input shape.
    """
    if passes is None:
        if method not in REDUCTIONS:
            raise ValueError(
                f"unknown reduction {method!r}; want one of {REDUCTIONS}")
        passes = passes_for_method(method)
    else:
        passes = validate_passes(passes)
    if repack not in REPACK_MODES:
        raise ValueError(f"repack must be one of {REPACK_MODES}, got {repack!r}")
    if repack == "on" and mesh is not None:
        raise ValueError(
            "repack='on' is host-driven at the phase boundary and is not "
            "supported under a mesh; shard the single-phase plan instead "
            "(repack='off') or drive per-host two-phase plans")
    if fixpoint is None:
        fixpoint = repack == "on"
    key = TopoPlanKey(dim=dim, passes=passes, sublevel=bool(sublevel),
                      edge_cap=int(edge_cap), tri_cap=int(tri_cap),
                      quad_cap=int(quad_cap), reducer=reducer, mesh=mesh,
                      repack=repack, fixpoint=bool(fixpoint),
                      ladder=None if ladder is None else tuple(ladder))
    with _PLAN_CACHE_LOCK:
        plan = _PLAN_CACHE.get(key)
        if plan is not None:
            _PLAN_CACHE.move_to_end(key)
            _PLAN_CACHE_STATS["hits"] += 1
            _OBS_PC_EVENTS.inc(event="hit")
            return plan
        _PLAN_CACHE_STATS["misses"] += 1
        _OBS_PC_EVENTS.inc(event="miss")
        with obs.span("plan.build", repack=repack):
            if repack == "on":
                reduce_exec = _build_reduce_executor(key)
                plan = TopoPlan(key=key, executor=reduce_exec,
                                reduce_executor=reduce_exec)
            else:
                plan = TopoPlan(key=key, executor=_build_executor(key))
        _PLAN_CACHE[key] = plan
        while len(_PLAN_CACHE) > _PLAN_CACHE_MAXSIZE:
            _PLAN_CACHE.popitem(last=False)
            _PLAN_CACHE_STATS["evictions"] += 1
            _OBS_PC_EVENTS.inc(event="eviction")
    return plan


def plan_cache_info() -> dict:
    """Snapshot of the plan cache: hits/misses/evictions/currsize/maxsize."""
    with _PLAN_CACHE_LOCK:
        return dict(_PLAN_CACHE_STATS, currsize=len(_PLAN_CACHE),
                    maxsize=_PLAN_CACHE_MAXSIZE)


def clear_plan_cache() -> None:
    """Drop every cached plan and reset the counters (tests/benchmarks)."""
    with _PLAN_CACHE_LOCK:
        _PLAN_CACHE.clear()
        for k in _PLAN_CACHE_STATS:
            _PLAN_CACHE_STATS[k] = 0
        _OBS_PC_EVENTS.clear()


def topological_signature(
    g: GraphBatch,
    dim: int = 1,
    method: str = "both",
    sublevel: bool = True,
    edge_cap: int = 256,
    tri_cap: int = 512,
    quad_cap: int = 0,
    reducer: str = "jnp",
    repack: str = "off",
) -> Diagrams:
    """End-to-end: reduce with the paper's algorithms, then exact PDs.

    Thin wrapper over ``make_topo_plan(...).execute(g)`` — one-shot callers
    and the serve/train/bench layers all share the same compiled pipelines.

    The returned Diagrams cover dimensions 0..dim.  (Coral reduction is only
    exact for dimensions >= dim's core level, so when ``method`` includes
    coral, read out only dimension ``dim`` — or use method="prunit" for all
    dims at once.)  ``repack="on"`` selects the two-phase path; the valid
    persistence pairs are identical, row positions are not (compare
    canonically, e.g. via ``diagrams_to_numpy``).
    """
    plan = make_topo_plan(dim=dim, method=method, sublevel=sublevel,
                          edge_cap=edge_cap, tri_cap=tri_cap,
                          quad_cap=quad_cap, reducer=reducer, repack=repack)
    return plan.execute(g)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ReductionStats:
    """Per-graph reduction accounting (the paper's evaluation metric)."""

    v_before: jax.Array
    v_after: jax.Array
    e_before: jax.Array
    e_after: jax.Array

    def v_reduction_pct(self) -> jax.Array:
        v0 = jnp.maximum(self.v_before, 1)
        return 100.0 * (self.v_before - self.v_after) / v0

    def e_reduction_pct(self) -> jax.Array:
        e0 = jnp.maximum(self.e_before, 1)
        return 100.0 * (self.e_before - self.e_after) / e0


def topological_signature_sharded(
    g: GraphBatch,
    mesh,
    dim: int = 1,
    method: str = "both",
    sublevel: bool = True,
    edge_cap: int = 256,
    tri_cap: int = 512,
    quad_cap: int = 0,
    reducer: str = "jnp",
) -> Diagrams:
    """``topological_signature`` under shard_map over every mesh axis.

    Thin wrapper over ``make_topo_plan(..., mesh=mesh)``; see _build_executor
    for why shard_map beats plain pjit here.  The global batch must divide
    the mesh size.
    """
    plan = make_topo_plan(dim=dim, method=method, sublevel=sublevel,
                          edge_cap=edge_cap, tri_cap=tri_cap,
                          quad_cap=quad_cap, reducer=reducer, mesh=mesh)
    return plan.execute(g)


@partial(jax.jit, static_argnames=("dim", "method", "sublevel"))
def reduction_stats(g: GraphBatch, dim: int, method: str = "both",
                    sublevel: bool = True) -> ReductionStats:
    gr = reduce_graphs(g, dim, method, sublevel)
    return ReductionStats(
        v_before=g.n_vertices(), v_after=gr.n_vertices(),
        e_before=g.n_edges(), e_after=gr.n_edges(),
    )
