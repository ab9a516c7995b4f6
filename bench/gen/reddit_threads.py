"""Discussion threads at a corpus's published means: reply trees grown by
preferential attachment, with repeat interactions between their users.

A thread's graph has one vertex per user and an edge between two users who
replied to each other.  Each graph is built in two steps:

* the reply tree: user ``i`` (``i >= 1``) replies to an earlier user drawn
  with probability proportional to ``degree + 1``, so the opening post and
  the busiest users gather most replies;
* repeat interactions: further edges between two users, each drawn the same
  way, until a graph of ``n`` users holds ``round(n * avg_edges /
  avg_vertices)`` edges, so that the pool averages the published edges.

Orders are the ``graphs`` quantiles of a lognormal, rounded and clipped to
``[min_vertices, max_vertices]``, whose location is solved so that they
average ``avg_vertices`` (``ws_dataset.orders``); the seed only orders them.
``f`` is degree centrality ``deg / (n - 1)`` in float32.
"""
from __future__ import annotations

import numpy as np

from bench.gen.ws_dataset import orders
from bench.pool import Graph


def thread_graph(rng, n: int, n_edges: int) -> np.ndarray:
    """(m, 2) edges, u < v, each pair once: a reply tree of ``n`` users,
    then repeat interactions up to ``n_edges`` edges (fewer only where the
    graph is complete)."""
    ends = [0]                  # vertex v appears degree(v) + 1 times
    edges = set()
    pick = rng.random(n)
    for v in range(1, n):
        u = ends[int(pick[v] * len(ends))]
        edges.add((u, v))
        ends += [u, v, v]
    want = min(n_edges, n * (n - 1) // 2)
    while len(edges) < want:
        a, b = rng.random(2)
        u, v = ends[int(a * len(ends))], ends[int(b * len(ends))]
        if u != v:
            edges.add((min(u, v), max(u, v)))
    return np.array(sorted(edges), dtype=np.int32).reshape(-1, 2)


def make_pool(params: dict, seed: int):
    rng = np.random.default_rng([seed, 0x7EDD])
    count = int(params["graphs"])
    mean_n = float(params["avg_vertices"])
    per_vertex = float(params["avg_edges"]) / mean_n
    nv = rng.permutation(orders(count, mean_n, float(params["sigma"]),
                                int(params["min_vertices"]),
                                int(params["max_vertices"])))
    graphs = []
    for n in nv.tolist():
        edges = thread_graph(rng, n, int(round(n * per_vertex)))
        deg = np.bincount(edges.ravel(), minlength=n)
        f = (deg / np.float64(max(n - 1, 1))).astype(np.float32)
        graphs.append(Graph(n=n, edges=edges, f=f))
    sizes = np.array([len(g.edges) for g in graphs])
    report = {"graphs": count, "avg_vertices": float(nv.mean()),
              "avg_edges": float(sizes.mean()),
              "max_vertices": int(nv.max()),
              "over_128_vertices": int((nv > 128).sum())}
    return graphs, report
