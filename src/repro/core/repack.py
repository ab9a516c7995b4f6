"""Repack step of the two-phase plan: compact, measure, re-bucket.

The reductions shrink graphs by up to ~95% (paper Figs 4-6), but a fused
reduce→persist pipeline compiles the boundary-matrix stage at the *input*
graph's padded caps — the expensive stage never sees the smaller graph.  The
repack step sits between an explicit reduce phase and persist phase
(repro/core/api.py, ``repack="on"``):

1. ``compact_batch`` — permute every graph's surviving vertices to the front
   of the padded axis (a jitted gather via the rank-by-mask permutation), so
   a reduced graph occupies a contiguous ``n' x n'`` prefix;
2. ``measure_counts`` — per-graph vertex / edge / triangle counts of the
   reduced graphs (cheap masked linear algebra, one batched einsum);
3. ``select_classes`` — first-fit each graph into the smallest
   :class:`ShapeClass` of a bounded ladder whose caps hold its counts, so
   ``pack_boundary``/``reduce_packed`` (and the Pallas ``gf2_reduce`` path,
   which is fully caps-polymorphic — it reads its (S, W) shape from the
   refs) compile and run at *reduced* size.

The ladder is what keeps jit signatures bounded: persist plans exist only at
ladder rungs, never at per-graph exact sizes, and the rungs are shared
process-wide through the plan cache — two serve buckets whose reduced graphs
land on the same rung execute the same compiled persist pipeline.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.graph import GraphBatch


@dataclasses.dataclass(frozen=True, order=True)
class ShapeClass:
    """One persist-phase shape rung: padded order + simplex caps.

    The persist analogue of the serve layer's ``Bucket`` — a jit signature
    class.  Total order (n_pad, edge_cap, tri_cap, quad_cap) gives the
    deterministic first-fit used by ``select_classes``.
    """

    n_pad: int
    edge_cap: int
    tri_cap: int
    quad_cap: int = 0


def diagram_size(n: int, dim: int, edge_cap: int, tri_cap: int,
                 quad_cap: int = 0) -> int:
    """Rows of the Diagrams tensor a plan with these caps emits.

    Mirrors ``build_filtered_complex``: triangles only enter for dim >= 1,
    tetrahedra only for dim >= 2.
    """
    s = n + edge_cap
    if dim >= 1 and tri_cap:
        s += tri_cap
    if dim >= 2 and quad_cap:
        s += quad_cap
    return s


def holds_every_graph(n: int, edge_cap: int, tri_cap: int) -> bool:
    """Whether caps at padded order ``n`` hold every graph of that order:
    the complete graph's edges and triangles.  Such a shape is an
    admission class, never a persist shape: its diagram would have on the
    order of ``n**3 / 6`` rows."""
    return (edge_cap >= n * (n - 1) // 2
            and tri_cap >= n * (n - 1) * (n - 2) // 6)


def compact_batch(g: GraphBatch) -> tuple[GraphBatch, jax.Array]:
    """Permute surviving vertices to the front of the padded axis.

    Returns ``(compacted, order)`` where ``order[b, i]`` is the original
    index of compacted vertex ``i`` (live vertices first, original order
    preserved — the stable rank-by-mask permutation).  Pure gather, jit/vmap
    friendly; row ``i < n_vertices[b]`` of the result is always live, so a
    graph whose counts fit a :class:`ShapeClass` can be *sliced* to it.

    Diagram invariance: persistence pairs are a multiset invariant of the
    filtration ``(G, f)`` — relabelling vertices permutes simplex slots but
    never the (birth, death) value multiset — so persisting the compacted
    graph yields the same pairs as the uncompacted one (the repack
    round-trip property, tests/test_reduction_engine.py).
    """
    order = jnp.argsort(~g.mask, axis=-1, stable=True).astype(jnp.int32)
    mask_c = jnp.take_along_axis(g.mask, order, axis=-1)
    f_c = jnp.where(mask_c, jnp.take_along_axis(g.f, order, axis=-1), jnp.inf)
    adj_r = jnp.take_along_axis(g.adj, order[:, :, None], axis=1)
    adj_c = jnp.take_along_axis(adj_r, order[:, None, :], axis=2)
    adj_c = adj_c & mask_c[:, None, :] & mask_c[:, :, None]
    return GraphBatch(adj=adj_c, mask=mask_c, f=f_c), order


def measure_counts(g: GraphBatch, count_triangles: bool = True
                   ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Per-graph (n_vertices, n_edges, n_triangles) of a (reduced) batch.

    Triangle counts via trace(A^3)/6 as one batched f32 einsum (exact below
    2^24, far above any cap this system pads to).
    """
    nv = g.n_vertices()
    ne = g.n_edges()
    if count_triangles:
        a = (g.adj & g.mask[:, None, :] & g.mask[:, :, None]).astype(jnp.float32)
        nt = (jnp.einsum("bij,bjk,bki->b", a, a, a) / 6.0).astype(jnp.int32)
    else:
        nt = jnp.zeros_like(nv)
    return nv, ne, nt


def _ceil_pow2(x: int) -> int:
    return 1 << max(0, int(x) - 1).bit_length()


def default_ladder(n: int, edge_cap: int, tri_cap: int, quad_cap: int = 0,
                   min_n: int = 8) -> tuple[ShapeClass, ...]:
    """The default repack ladder for an input shape ``(n, caps)``.

    Power-of-two vertex rungs from ``min_n`` up to ``n``; each rung's caps
    are the input caps scaled by the vertex fraction (rounded up to a power
    of two) and clamped by both the input caps and the complete-graph counts
    at that order.  The top rung is exactly the input shape, so a fitting
    rung always exists (reduction only removes simplices).  ``quad_cap`` is
    carried unscaled: 4-clique counting is the one measurement that does not
    pay for itself, and caps only need to stay >= the true counts.
    """
    n = int(n)
    rungs = []
    m = min_n
    while m < n:
        rungs.append(m)
        m *= 2
    classes = []
    for m in rungs:
        frac = m / n
        e = min(edge_cap, m * (m - 1) // 2,
                _ceil_pow2(max(m, int(edge_cap * frac))))
        if tri_cap:
            t = min(tri_cap, m * (m - 1) * (m - 2) // 6,
                    _ceil_pow2(max(m, int(tri_cap * frac))))
        else:
            t = 0
        classes.append(ShapeClass(n_pad=m, edge_cap=e, tri_cap=t,
                                  quad_cap=quad_cap))
    classes.append(ShapeClass(n_pad=n, edge_cap=edge_cap, tri_cap=tri_cap,
                              quad_cap=quad_cap))
    return tuple(classes)


def select_classes(ladder: tuple[ShapeClass, ...], nv, ne, nt) -> np.ndarray:
    """First-fit rung index per graph (host-side, vectorized).

    A graph lands on the first rung holding all its measured counts —
    deterministic, like TopoServe's bucket routing.  A graph that fits no
    rung (impossible for ``default_ladder``; a custom ladder must keep a
    top rung at least as large as the input shape) gets index -1;
    ``RepackReport.misfit`` names its counts.
    """
    nv = np.asarray(nv)
    ne = np.asarray(ne)
    nt = np.asarray(nt)
    out = np.full(nv.shape, -1, np.int64)
    for i, c in enumerate(ladder):
        # nt is 0 when triangles were not measured (dim-0 plans), so the
        # gate is inert there; when they WERE measured, a zero-tri rung
        # must reject triangle-bearing graphs like any other overflow
        fit = ((out < 0) & (nv <= c.n_pad) & (ne <= c.edge_cap)
               & (nt <= c.tri_cap))
        out[fit] = i
    return out


def slice_to(g: GraphBatch, n_pad: int) -> GraphBatch:
    """Slice a *compacted* batch down to its first ``n_pad`` vertex slots."""
    return GraphBatch(adj=g.adj[:, :n_pad, :n_pad],
                      mask=g.mask[:, :n_pad], f=g.f[:, :n_pad])


@dataclasses.dataclass(frozen=True)
class RepackReport:
    """Host-side account of one two-phase execution's repack decisions."""

    ladder: tuple[ShapeClass, ...]
    class_index: np.ndarray   # (B,) rung index into ladder
    n_vertices: np.ndarray    # (B,) post-reduction counts
    n_edges: np.ndarray
    n_triangles: np.ndarray
    persist_calls: int = 0    # persist programs the execution ran

    def shape_class(self, i: int) -> ShapeClass | None:
        """Graph ``i``'s persist rung, or None where it fits none."""
        ci = int(self.class_index[i])
        return self.ladder[ci] if ci >= 0 else None

    def misfit(self, i: int) -> ValueError | None:
        """The error of graph ``i`` if its reduced counts fit no rung."""
        if self.class_index[i] >= 0:
            return None
        return ValueError(
            f"graph {i} reduced to {int(self.n_vertices[i])} vertices / "
            f"{int(self.n_edges[i])} edges / {int(self.n_triangles[i])} "
            f"triangles, which fit no persist rung (largest "
            f"{max(self.ladder, default=None)})")

    def rung_histogram(self) -> dict[int, int]:
        """{rung n_pad: graph count} over the graphs that fit a rung."""
        out: dict[int, int] = {}
        for ci in self.class_index.tolist():
            if ci < 0:
                continue
            n = self.ladder[ci].n_pad
            out[n] = out.get(n, 0) + 1
        return out
