"""The graphs a cell submits: the pool, its exclusion rule and relabelling.

A configuration's generator returns a list of :class:`Graph` from the
configuration's own ``graph_seed``: the deployment's data set.  Graphs above
the configuration's caps (vertices, edges, triangles: the top rung as
numbers, so the pool does not move when the program's buckets change) are
dropped and counted.  The run's seed orders the pool, a fresh order per
pass, and every submission of graph ``i`` in pass ``p`` carries a vertex
permutation drawn from ``(seed, p, i)``: the same diagram, different bytes,
so no result cache can answer a later pass from an earlier one.
"""
from __future__ import annotations

import dataclasses
import functools

import numpy as np


@dataclasses.dataclass(frozen=True)
class Graph:
    n: int
    edges: np.ndarray   # (m, 2) int32, u != v, each pair once
    f: np.ndarray       # (n,) float32 vertex filtering values

    @functools.cached_property
    def triangles(self) -> int:
        a = np.zeros((self.n, self.n), dtype=np.float32)
        a[self.edges[:, 0], self.edges[:, 1]] = 1
        a[self.edges[:, 1], self.edges[:, 0]] = 1
        return int(round(float(np.sum((a @ a) * a)) / 6))


def apply_caps(graphs, caps: dict):
    """(kept graphs, number dropped) under the configuration's exclusion."""
    kept, dropped = [], 0
    for g in graphs:
        if (g.n > caps["max_vertices"] or len(g.edges) > caps["max_edges"]
                or g.triangles > caps["max_triangles"]):
            dropped += 1
        else:
            kept.append(g)
    return kept, dropped


def relabel(g: Graph, seed: int, pass_no: int, index: int) -> Graph:
    """Graph ``index`` of pass ``pass_no`` under its seeded vertex
    permutation."""
    perm = np.random.default_rng([seed, pass_no, index]).permutation(g.n)
    f = np.empty_like(g.f)
    f[perm] = g.f
    return Graph(n=g.n, edges=perm[g.edges].astype(np.int32), f=f)


class Stream:
    """Seeded endless order over the pool: a fresh shuffle per pass."""

    def __init__(self, graphs, seed: int):
        self.graphs = graphs
        self.seed = seed
        self.passes = 0
        self._order = np.random.default_rng([seed, 0]).permutation(
            len(graphs))
        self._at = 0

    def next(self):
        """(pool index, graph as submitted)."""
        if self._at == len(self._order):
            self.passes += 1
            self._order = np.random.default_rng(
                [self.seed, self.passes]).permutation(len(self.graphs))
            self._at = 0
        i = int(self._order[self._at])
        self._at += 1
        return i, relabel(self.graphs[i], self.seed, self.passes, i)


def largest_per_bucket(pool, labels, k: int) -> set:
    """Pool indices of the ``k`` graphs with most edges in each bucket: the
    longest requests, always among those checked."""
    out = set()
    for b in sorted(set(labels)):
        idx = sorted((i for i, lb in enumerate(labels) if lb == b),
                     key=lambda i: -len(pool[i].edges))
        out.update(idx[:k])
    return out
