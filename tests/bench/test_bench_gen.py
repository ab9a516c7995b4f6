"""The benchmark's traffic generators (bench/gen/)."""
from __future__ import annotations

import json
import os

import networkx as nx
import numpy as np
import pytest

from bench.registry import Registry

ROOT = os.path.join(os.path.dirname(__file__), "..", "..")


def _config(name):
    with open(os.path.join(ROOT, "bench", "configs", name + ".json")) as fh:
        return json.load(fh)


def test_coauthor_graph_reaches_the_published_size():
    cfg = _config("ego-ca-condmat")
    gen = Registry(ROOT).generator(cfg["generator"])
    p = cfg["generator_params"]
    edges = gen.coauthor_graph(p["n_vertices"], p["n_edges"],
                               p["team_alpha"], p["team_max"],
                               p["uniform_share"], p["closure_share"],
                               cfg["graph_seed"])
    stats = gen.graph_stats(p["n_vertices"], edges)
    assert stats["vertices"] == 23133 and stats["edges"] == 93497
    assert stats["isolated"] == 0
    assert len({tuple(e) for e in edges.tolist()}) == len(edges)
    assert np.all(edges[:, 0] < edges[:, 1])
    # co-authorship shape: clustered, with a heavy degree tail
    assert 0.55 <= stats["avg_clustering"] <= 0.7
    assert stats["max_degree"] > 100


@pytest.mark.parametrize("seed", [0, 1])
def test_ego_nets_match_networkx(seed):
    gen = Registry(ROOT).generator("coauthor")
    edges = gen.coauthor_graph(200, 700, 2.9, 12, 0.1, 0.88, seed)
    g = nx.Graph(edges.tolist())
    for item in gen.ego_nets(200, edges, 1000):
        v, n, local, members = item
        ego = nx.ego_graph(g, v)
        assert n == ego.number_of_nodes() and members[0] == v
        got = {frozenset((int(members[a]), int(members[b])))
               for a, b in local.tolist()}
        assert got == {frozenset(e) for e in ego.edges()}


def test_ego_pool_counts_what_it_leaves_out():
    gen = Registry(ROOT).generator("coauthor")
    params = {"n_vertices": 300, "n_edges": 1200, "team_alpha": 2.9,
              "team_max": 20, "uniform_share": 0.1, "closure_share": 0.88,
              "ego_max_vertices": 16}
    graphs, report = gen.make_pool(params, 4)
    assert len(graphs) + report["ego_over_vertex_cap"] == 300
    assert all(g.n <= 16 and g.f.dtype == np.float32 for g in graphs)
    assert all(np.all(g.f > 0) for g in graphs)


def test_ws_dataset_follows_the_surrogate_law():
    """The PROTEINS pool: the surrogate's family at the published means
    (App. Table 2: 39.06 vertices, 72.82 edges), clipped at the top rung."""
    cfg = _config("proteins")
    gen = Registry(ROOT).generator(cfg["generator"])
    graphs, report = gen.make_pool(cfg["generator_params"],
                                   cfg["graph_seed"])
    assert report["graphs"] == len(graphs) == 1113 * 40
    orders = np.array([g.n for g in graphs])
    sizes = np.array([len(g.edges) for g in graphs])
    assert orders.min() >= 4 and orders.max() <= 128
    assert abs(orders.mean() / 39.06 - 1) < 0.01
    assert abs(sizes.mean() / 72.82 - 1) < 0.01
    assert report["avg_vertices"] == orders.mean()
    assert report["avg_edges"] == sizes.mean()
    # lognormal, sigma 0.35: a tail above 64 reaches the n128 bucket
    assert 0.03 <= report["over_64_vertices"] / len(graphs) <= 0.1
    # a thinned ring lattice: nearly every vertex keeps 2 to 6 neighbours
    g = graphs[0]
    assert np.all(g.edges[:, 0] < g.edges[:, 1])
    assert len({tuple(e) for e in g.edges.tolist()}) == len(g.edges)


@pytest.mark.parametrize("seed", [1, 2**31 + 7])
def test_ws_orders_do_not_depend_on_the_seed(seed):
    """The graph seed orders the sizes; it never changes the set of them."""
    gen = Registry(ROOT).generator("ws_dataset")
    params = dict(_config("proteins")["generator_params"], copies=1)
    a, _ = gen.make_pool(params, seed)
    b, _ = gen.make_pool(params, 5)
    assert sorted(g.n for g in a) == sorted(g.n for g in b)
    assert abs(np.mean([g.n for g in a]) / 39.06 - 1) < 0.001
