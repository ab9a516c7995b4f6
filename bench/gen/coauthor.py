"""Seeded co-authorship network and the 1-hop ego net of every vertex.

A co-authorship graph is a union of cliques: every paper joins all of its
authors pairwise.  The host graph is grown paper by paper until it holds
exactly ``n_vertices`` authors and ``n_edges`` distinct co-author pairs:

* team sizes follow a discrete power law ``P(k) ~ k^-team_alpha`` on
  ``2..team_max`` (single-author papers add no edge);
* each slot of a team is a new author with a probability that keeps new
  authors and new edges running out together, and otherwise an existing
  author drawn by productivity: with probability ``uniform_share``
  uniformly, else proportionally to the papers already written (an urn of
  authorships, Price's model);
* the last few edges are closed by two-author papers, so |V| and |E| are
  met exactly.

Requests are the ego nets: vertex ``v`` first, then its neighbours in
increasing id, the induced edges among them, and ``f`` = the host graph's
degree centrality ``deg/(N-1)`` in float32, kept on the ego net (paper
Remark 1).
"""
from __future__ import annotations

import numpy as np

from bench.pool import Graph


def coauthor_graph(n_vertices: int, n_edges: int, team_alpha: float,
                   team_max: int, uniform_share: float, closure_share: float,
                   seed: int):
    """Undirected edge array (E, 2) with u < v, of exactly the asked size."""
    rng = np.random.default_rng([seed, 0xC0A])
    ks = np.arange(2, team_max + 1)
    p_team = ks.astype(np.float64) ** -team_alpha
    p_team /= p_team.sum()
    adj = [set() for _ in range(n_vertices)]
    urn: list[int] = []        # one entry per authorship
    n_auth = 0
    n_e = 0

    def existing():
        if not urn or rng.random() < uniform_share:
            return int(rng.integers(n_auth))
        return urn[int(rng.integers(len(urn)))]

    def add_paper(team):
        nonlocal n_e
        for i, u in enumerate(team):
            for w in team[i + 1:]:
                if w not in adj[u]:
                    adj[u].add(w)
                    adj[w].add(u)
                    n_e += 1
        urn.extend(team)

    while True:
        left_v, left_e = n_vertices - n_auth, n_edges - n_e
        if left_e <= left_v:
            break
        k = int(rng.choice(ks, p=p_team))
        p_new = min(1.0, left_v / left_e * (k - 1) / 2.0)
        team, fresh = [], 0
        for _ in range(k):
            if n_auth + fresh < n_vertices and (n_auth == 0 or
                                                rng.random() < p_new):
                team.append(n_auth + fresh)
                fresh += 1
            else:
                u = existing()
                known = [w for w in team if w < n_auth and adj[w]]
                if known and rng.random() < closure_share:
                    lead = known[int(rng.integers(len(known)))]
                    u = list(adj[lead])[int(rng.integers(len(adj[lead])))]
                if u not in team:
                    team.append(u)
        if len(team) < 2:
            continue
        new_pairs = sum(1 for i, u in enumerate(team) for w in team[i + 1:]
                        if u >= n_auth or w >= n_auth or w not in adj[u])
        if n_e + new_pairs > n_edges - (left_v - fresh):
            continue       # would leave too few edges for the unseen authors
        n_auth += fresh
        add_paper(team)
    # close the gap exactly: pairs among existing authors, then one paper
    # per author not yet seen, each with an existing co-author
    while n_edges - n_e > n_vertices - n_auth:
        u, w = existing(), existing()
        if u != w and w not in adj[u]:
            add_paper([u, w])
    while n_auth < n_vertices:
        w = existing()
        n_auth += 1
        add_paper([n_auth - 1, w])
    edges = np.array([(u, w) for u in range(n_vertices) for w in adj[u]
                      if u < w], dtype=np.int32)
    return edges


def _csr(n: int, edges: np.ndarray):
    both = np.concatenate([edges, edges[:, ::-1]])
    order = np.lexsort((both[:, 1], both[:, 0]))
    both = both[order]
    indptr = np.zeros(n + 1, dtype=np.int64)
    np.add.at(indptr, both[:, 0] + 1, 1)
    return np.cumsum(indptr), both[:, 1].astype(np.int64)


def graph_stats(n: int, edges: np.ndarray) -> dict:
    """|V|, |E|, average local clustering and the degree tail."""
    indptr, nbr = _csr(n, edges)
    deg = np.diff(indptr)
    nbrs = [set(nbr[indptr[v]:indptr[v + 1]].tolist()) for v in range(n)]
    tri = np.zeros(n, dtype=np.int64)
    for u, w in edges.tolist():
        c = len(nbrs[u] & nbrs[w])
        tri[u] += c
        tri[w] += c
    tri //= 2
    pairs = deg * (deg - 1) / 2
    local = np.where(pairs > 0, tri / np.maximum(pairs, 1), 0.0)
    return {"vertices": int(n), "edges": int(len(edges)),
            "avg_clustering": float(local.mean()),
            "max_degree": int(deg.max()),
            "degree_p50_p90_p99": [float(x) for x in
                                   np.percentile(deg, [50, 90, 99])],
            "isolated": int((deg == 0).sum())}


def ego_nets(n: int, edges: np.ndarray, max_vertices: int):
    """Yield (centre, n_members, local edge array, member ids) for every
    vertex whose ego net has at most ``max_vertices`` vertices; larger ones
    yield ``None`` in their place (counted by the caller, never built)."""
    indptr, nbr = _csr(n, edges)
    pos = np.full(n, -1, dtype=np.int64)
    for v in range(n):
        lo, hi = indptr[v], indptr[v + 1]
        if hi - lo + 1 > max_vertices:
            yield None
            continue
        members = np.concatenate([[v], nbr[lo:hi]])
        pos[members] = np.arange(len(members))
        starts, lens = indptr[members], indptr[members + 1] - indptr[members]
        rows = np.repeat(np.arange(len(members)), lens)
        flat = np.concatenate([nbr[s:s + l] for s, l in zip(starts, lens)])
        cols = pos[flat]
        keep = (cols > rows)
        local = np.stack([rows[keep], cols[keep]], axis=1).astype(np.int32)
        pos[members] = -1
        yield v, len(members), local, members


def make_pool(params: dict, seed: int):
    """The ego-net pool of one seeded host graph, and its report."""
    n = int(params["n_vertices"])
    edges = coauthor_graph(n, int(params["n_edges"]),
                           float(params["team_alpha"]),
                           int(params["team_max"]),
                           float(params["uniform_share"]),
                           float(params["closure_share"]), seed)
    deg = np.bincount(edges.ravel(), minlength=n)
    centrality = (deg / np.float64(n - 1)).astype(np.float32)
    graphs, too_big = [], 0
    for item in ego_nets(n, edges, int(params["ego_max_vertices"])):
        if item is None:
            too_big += 1
            continue
        _, nv, local, members = item
        graphs.append(Graph(n=nv, edges=local, f=centrality[members]))
    report = {"host": graph_stats(n, edges), "ego_nets": n,
              "ego_over_vertex_cap": too_big}
    return graphs, report
