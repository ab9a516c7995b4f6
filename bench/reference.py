"""Plain reference for the served diagrams: exact PD_k over GF(2).

A straightforward copy of the oracle's method (the program's
``core/persistence_ref.py``), kept here so that no change to the program can
move it: enumerate the cliques of the graph up to size ``dim + 2``, order
them by (filtration value, dimension, vertex tuple), reduce the boundary
matrix column by column over GF(2), and read off the pairs.  A simplex
enters at the largest ``f`` of its vertices (sublevel filtration); pairs of
zero persistence are dropped and essential classes die at ``+inf``.

``dtype`` is the precision the filtering values are held in; the control
computes the same diagrams with ``f`` rounded to bfloat16.
"""
from __future__ import annotations

import numpy as np


def cliques(n: int, edges: np.ndarray, max_size: int) -> list[tuple]:
    nbrs = [set() for _ in range(n)]
    for u, w in edges.tolist():
        nbrs[u].add(w)
        nbrs[w].add(u)
    out = [(v,) for v in range(n)]
    frontier = list(out)
    for _ in range(2, max_size + 1):
        nxt = []
        for c in frontier:
            cand = {w for w in nbrs[c[-1]] if w > c[-1]}
            for v in c[:-1]:
                cand &= nbrs[v]
            nxt.extend(c + (w,) for w in sorted(cand))
        out.extend(nxt)
        frontier = nxt
    return out


def diagram(n: int, edges: np.ndarray, f: np.ndarray, dims,
            dtype=np.float32) -> list[tuple[int, float, float]]:
    """Sorted (dimension, birth, death) of the pairs of PD_k, k in
    ``dims``, of the sublevel clique filtration of one graph."""
    dims = set(dims)
    fv = np.asarray(f).astype(dtype).astype(np.float64)
    simplices = cliques(n, edges, max(dims) + 2)
    value = {s: max(fv[v] for v in s) for s in simplices}
    simplices.sort(key=lambda s: (value[s], len(s), s))
    index = {s: i for i, s in enumerate(simplices)}
    pivot_of: dict[int, int] = {}
    cols: list[set] = []
    paired: set[int] = set()
    out = []
    for j, s in enumerate(simplices):
        col = ({index[s[:i] + s[i + 1:]] for i in range(len(s))}
               if len(s) > 1 else set())
        while col:
            low = max(col)
            p = pivot_of.get(low)
            if p is None:
                break
            col ^= cols[p]
        cols.append(col)
        if col:
            low = max(col)
            pivot_of[low] = j
            paired.add(low)
            b = simplices[low]
            if len(b) - 1 in dims and value[b] != value[s]:
                out.append((len(b) - 1, value[b], value[s]))
    for j, s in enumerate(simplices):
        if len(s) - 1 in dims and j not in paired and not cols[j]:
            out.append((len(s) - 1, value[s], float("inf")))
    return sorted(out)
