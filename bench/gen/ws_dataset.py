"""A TU-style corpus of Watts-Strogatz graphs at a dataset's published means.

The family is the program's Table-2 surrogate (``data/graphs.py``:
``load_dataset`` with the ``ws`` family): lognormal graph orders (``sigma``)
and ring lattices of ``k_ring`` nearest neighbours, rewired with probability
``p_rewire``.  Unlike the surrogate, the pool meets the dataset's published
averages:

* orders are the ``count`` quantiles of the lognormal, rounded and clipped
  to ``[min_vertices, max_vertices]``, whose location is solved so that
  they average ``avg_vertices``; the seed only orders them;
* each ring edge is rewired with probability ``p_rewire`` to a pair drawn
  uniformly from the pairs off the ring, then a uniform share of the
  edges is dropped so that a graph of ``n`` vertices keeps
  ``round(n * avg_edges / avg_vertices)`` of them (a k=4 ring alone holds
  2 edges per vertex).

``f`` is degree centrality ``deg / (n - 1)`` in float32.
"""
from __future__ import annotations

import functools
from statistics import NormalDist

import numpy as np

from bench.pool import Graph


def orders(count: int, mean: float, sigma: float, lo: int,
           hi: int) -> np.ndarray:
    """``count`` lognormal quantiles, rounded and clipped, averaging
    ``mean``, in increasing order."""
    inv = NormalDist().inv_cdf
    z = np.array([inv((j + 0.5) / count) for j in range(count)])

    def at(mu):
        return np.clip(np.rint(np.exp(mu + sigma * z)), lo, hi)

    a, b = np.log(lo), np.log(hi)
    for _ in range(60):
        mid = (a + b) / 2
        a, b = (mid, b) if at(mid).mean() < mean else (a, mid)
    return at((a + b) / 2).astype(np.int64)


@functools.lru_cache(maxsize=None)
def _pairs(n: int, k_ring: int):
    """Vertex pairs u < v of an ``n``-graph, and which are ring edges."""
    pu, pv = np.triu_indices(n, 1)
    d = np.minimum(pv - pu, n - (pv - pu))
    return pu, pv, np.flatnonzero(d <= k_ring // 2)


def ws_graph(rng, n: int, k_ring: int, p_rewire: float,
             n_edges: int) -> np.ndarray:
    """(m, 2) edges, u < v: the rewired ring, thinned to ``n_edges``."""
    pu, pv, ring = _pairs(n, k_ring)
    on = np.zeros(len(pu), dtype=bool)
    on[ring] = True
    free = np.flatnonzero(~on)
    moved = ring[rng.random(len(ring)) < p_rewire]
    on[moved] = False
    on[rng.choice(free, size=min(len(moved), len(free)),
                  replace=False)] = True
    edges = np.flatnonzero(on)
    keep = np.sort(rng.choice(edges, size=min(n_edges, len(edges)),
                              replace=False))
    return np.stack([pu[keep], pv[keep]], axis=1).astype(np.int32)


def make_pool(params: dict, seed: int):
    rng = np.random.default_rng([seed, 0x7E57])
    count = int(params["graphs"]) * int(params["copies"])
    mean_n = float(params["avg_vertices"])
    per_vertex = float(params["avg_edges"]) / mean_n
    nv = rng.permutation(orders(count, mean_n, float(params["sigma"]),
                                int(params["min_vertices"]),
                                int(params["max_vertices"])))
    graphs = []
    for n in nv.tolist():
        edges = ws_graph(rng, n, int(params["k_ring"]),
                         float(params["p_rewire"]),
                         int(round(n * per_vertex)))
        deg = np.bincount(edges.ravel(), minlength=n)
        f = (deg / np.float64(max(n - 1, 1))).astype(np.float32)
        graphs.append(Graph(n=n, edges=edges, f=f))
    sizes = np.array([len(g.edges) for g in graphs])
    report = {"graphs": count, "avg_vertices": float(nv.mean()),
              "avg_edges": float(sizes.mean()),
              "max_vertices": int(nv.max()),
              "over_64_vertices": int((nv > 64).sum())}
    return graphs, report
