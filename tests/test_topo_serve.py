"""TopoServe: bucket routing, plan-cache behaviour, served-vs-direct parity."""
import threading

import jax
import networkx as nx
import numpy as np
import pytest

from repro.core import topological_signature
from repro.core.api import clear_plan_cache, make_topo_plan, plan_cache_info
from repro.core.persistence_jax import diagrams_bitwise_equal as _rows_equal
from repro.core.repack import ShapeClass, diagram_size
from repro.serve import Bucket, TopoServe, TopoServeConfig
from repro.serve.topo_serve import (
    DEFAULT_BUCKETS,
    SINGLE_PHASE_BUCKETS,
    order_only_bucket,
    order_only_ladder_for,
    pack_requests,
    repack_ladder_for,
)


def _graph_query(g: nx.Graph):
    nodes = sorted(g.nodes())
    idx = {u: i for i, u in enumerate(nodes)}
    return [(idx[u], idx[v]) for (u, v) in g.edges()], len(nodes)


# ------------------------------------------------------------------ buckets

def test_bucket_assignment_deterministic():
    srv1 = TopoServe()
    srv2 = TopoServe()
    cases = [(3, 3), (16, 64), (17, 10), (16, 65), (40, 200), (100, 700)]
    for nv, ne in cases:
        b1 = srv1.bucket_for(nv, ne)
        b2 = srv2.bucket_for(nv, ne)
        assert b1 == b2
        assert nv <= b1.n_pad and ne <= b1.edge_cap
        # first-fit: no smaller configured bucket also fits
        for smaller in srv1.config.buckets:
            if smaller < b1:
                assert nv > smaller.n_pad or ne > smaller.edge_cap


def test_bucket_boundaries():
    srv = TopoServe()
    assert srv.bucket_for(16, 64).n_pad == 16   # exactly fits the first rung
    assert srv.bucket_for(17, 10).n_pad == 32   # vertex overflow -> next rung
    assert srv.bucket_for(10, 65).n_pad == 32   # edge overflow -> next rung
    with pytest.raises(ValueError):
        srv.bucket_for(10_000, 5)               # beyond the ladder


def test_custom_bucket_ladder():
    cfg = TopoServeConfig(buckets=(Bucket(8, 16, 16), Bucket(24, 96, 128)))
    srv = TopoServe(cfg)
    assert srv.bucket_for(8, 16).n_pad == 8
    assert srv.bucket_for(9, 4).n_pad == 24


# --------------------------------------------------------------- plan cache

def test_plan_cache_hit_miss():
    clear_plan_cache()
    p1 = make_topo_plan(dim=1, method="prunit", edge_cap=64, tri_cap=96)
    info = plan_cache_info()
    assert (info["hits"], info["misses"]) == (0, 1)
    p2 = make_topo_plan(dim=1, method="prunit", edge_cap=64, tri_cap=96)
    assert p2 is p1  # identical key -> same compiled plan object
    assert plan_cache_info()["hits"] == 1
    p3 = make_topo_plan(dim=1, method="prunit", edge_cap=128, tri_cap=96)
    assert p3 is not p1
    assert plan_cache_info()["misses"] == 2


def test_serve_reuses_plans_across_drains():
    clear_plan_cache()
    srv = TopoServe(TopoServeConfig(method="prunit"))
    q = _graph_query(nx.cycle_graph(6))
    srv.submit(edges=q[0], n_vertices=q[1])
    srv.drain()
    misses_after_first = plan_cache_info()["misses"]
    srv.submit(edges=q[0], n_vertices=q[1])
    srv.drain()
    info = plan_cache_info()
    assert info["misses"] == misses_after_first  # second drain: cache hit
    assert info["hits"] >= 1


# -------------------------------------------------------------------- serve

def _assert_host_row(d):
    """A served answer: NumPy leaves of shape (S,), each owning its memory
    (a client holding one answer pins no other row of its batch)."""
    for x in jax.tree.leaves(d):
        assert type(x) is np.ndarray and x.ndim == 1 and x.base is None


def test_served_equals_direct_single_bucket():
    srv = TopoServe(TopoServeConfig(method="prunit", record_batches=True))
    graphs = [nx.cycle_graph(6), nx.petersen_graph(),
              nx.barabasi_albert_graph(12, 2, seed=3)]
    futs = [srv.submit(*_graph_query(g)) for g in graphs]
    assert srv.drain() == len(graphs)
    (bucket, reqs, bfuts), = srv.executed_batches
    direct = topological_signature(
        pack_requests(reqs, bucket), dim=srv.config.dim,
        method=srv.config.method, sublevel=srv.config.sublevel,
        edge_cap=bucket.edge_cap, tri_cap=bucket.tri_cap,
    )
    for i, fut in enumerate(bfuts):
        _assert_host_row(fut.result())
        assert _rows_equal(fut.result(), jax.tree.map(lambda x: x[i], direct))


def test_served_equals_direct_across_buckets_and_padding():
    # odd request count + pad_batch_to forces padded rows; mixed sizes force
    # multiple buckets; served rows must still match the direct computation
    srv = TopoServe(TopoServeConfig(method="prunit", pad_batch_to=4,
                                    record_batches=True))
    graphs = [nx.cycle_graph(5), nx.complete_graph(7),
              nx.gnp_random_graph(20, 0.2, seed=1),
              nx.gnp_random_graph(40, 0.1, seed=2),
              nx.path_graph(3)]
    futs = [srv.submit(*_graph_query(g)) for g in graphs]
    assert srv.drain() == len(graphs)
    assert len({f.bucket for f in futs}) >= 2
    for bucket, reqs, bfuts in srv.executed_batches:
        direct = topological_signature(
            pack_requests(reqs, bucket), dim=srv.config.dim,
            method=srv.config.method, sublevel=srv.config.sublevel,
            edge_cap=bucket.edge_cap, tri_cap=bucket.tri_cap,
        )
        for i, fut in enumerate(bfuts):
            _assert_host_row(fut.result())
            assert _rows_equal(fut.result(), jax.tree.map(lambda x: x[i], direct))


_PARITY_GRAPHS = [nx.cycle_graph(6), nx.petersen_graph(),
                  nx.barabasi_albert_graph(12, 2, seed=3), nx.path_graph(4)]


@pytest.mark.parametrize("n_graphs,repack", [
    (3, "off"),   # partly filled: one pad row executed, never handed out
    (4, "off"),   # full batch
    (3, "on"),    # two-phase plan, pad row included
])
def test_served_rows_are_host_rows_of_plan_execute(n_graphs, repack):
    from repro.core.graph import GraphBatch

    srv = TopoServe(TopoServeConfig(method="prunit", max_batch=4,
                                    pad_batch_to=4, repack=repack,
                                    record_batches=True))
    graphs = _PARITY_GRAPHS[:n_graphs]
    futs = [srv.submit(*_graph_query(g)) for g in graphs]
    assert srv.drain() == n_graphs
    assert srv.stats["padded_rows"] == 4 - n_graphs
    (bucket, reqs, bfuts), = srv.executed_batches
    assert list(bfuts) == futs
    g = pack_requests(reqs, bucket)
    k = 4 - n_graphs   # pad rows: empty graphs, +inf filtration
    g = GraphBatch(
        adj=np.concatenate([g.adj, np.zeros((k,) + g.adj.shape[1:], bool)]),
        mask=np.concatenate([g.mask, np.zeros((k, g.n), bool)]),
        f=np.concatenate([g.f, np.full((k, g.n), np.inf, np.float32)]))
    padded = pack_requests(reqs, bucket, 4)
    for a, b in zip(jax.tree.leaves(padded), jax.tree.leaves(g)):
        assert np.array_equal(np.asarray(a), b)
    plan = srv.plan_for(bucket)
    direct, info = plan.execute_info(g)
    for i, fut in enumerate(futs):
        _assert_host_row(fut.result())
        assert _rows_equal(fut.result(), jax.tree.map(lambda x: x[i], direct))
        if repack == "on":
            assert fut.repack_class == info.shape_class(i)
        else:
            assert fut.repack_class is None


def test_batch_resolves_with_one_transfer_and_no_per_graph_programs(
        monkeypatch):
    from repro import obs

    array_type = type(jax.numpy.zeros(()))

    srv = TopoServe(TopoServeConfig(method="prunit", pad_batch_to=4))
    q = _graph_query(nx.cycle_graph(6))
    srv.submit(*q)
    srv.drain()  # compile the bucket plan and the pad outside the count

    gets, slices = [], []
    real_get, real_item = jax.device_get, array_type.__getitem__

    def device_get(x):
        out = real_get(x)
        gets.append(sum(a.nbytes for a in jax.tree.leaves(out)))
        return out

    def getitem(self, idx):
        slices.append(idx)
        return real_item(self, idx)

    monkeypatch.setattr(jax, "device_get", device_get)
    monkeypatch.setattr(array_type, "__getitem__", getitem)
    before = obs.get_instrument("serve.resolve_bytes").value(
        instance=srv._obs_instance, bucket="n16")
    futs = [srv.submit(*_graph_query(g)) for g in _PARITY_GRAPHS[:3]]
    obs.configure(enabled=True)
    obs.clear_trace()
    try:
        assert srv.drain() == 3
        spans = [e for e in obs.trace_events() if e["name"] == "serve.resolve"]
    finally:
        obs.configure(enabled=False)
        obs.clear_trace()
    assert len(gets) == 1 and slices == []
    assert [e["args"]["bytes"] for e in spans] == gets
    after = obs.get_instrument("serve.resolve_bytes").value(
        instance=srv._obs_instance, bucket="n16")
    # the whole padded batch moved: 4 rows of each leaf, 3 handed out
    row_bytes = sum(x.nbytes for x in jax.tree.leaves(futs[0].result()))
    assert after - before == gets[0] == 4 * row_bytes


def test_served_diagram_values():
    srv = TopoServe(TopoServeConfig(method="none"))
    fut_c6 = srv.submit(*_graph_query(nx.cycle_graph(6)))
    fut_k5 = srv.submit(*_graph_query(nx.complete_graph(5)))
    srv.drain()
    assert int(fut_c6.result().betti(0)) == 1
    assert int(fut_c6.result().betti(1)) == 1
    assert int(fut_k5.result().betti(1)) == 0


def test_background_serve_forever_thread():
    srv = TopoServe(TopoServeConfig(method="prunit"))
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    try:
        futs = [srv.submit(*_graph_query(nx.cycle_graph(4 + i)))
                for i in range(5)]
        results = [f.result(timeout=120) for f in futs]
        assert all(int(d.betti(1)) == 1 for d in results)
    finally:
        srv.stop()
        t.join(timeout=10)
    assert not t.is_alive()
    assert srv.stats["served"] >= 5


def test_oversize_request_rejected_at_submit():
    # above the largest order-only rung (2,048 vertices) no bucket holds it
    srv = TopoServe()
    with pytest.raises(ValueError, match="exceeds every bucket"):
        srv.submit(edges=[(i, i + 1) for i in range(2100)], n_vertices=2101)


def test_malformed_requests_rejected_at_submit():
    # rejected at ingress so they can never fail co-batched futures at drain
    srv = TopoServe()
    with pytest.raises(ValueError, match="out of range"):
        srv.submit(edges=[(0, 500)], n_vertices=5)
    with pytest.raises(ValueError, match="out of range"):
        srv.submit(edges=[(-1, 0)], n_vertices=5)
    with pytest.raises(ValueError, match="f has"):
        srv.submit(edges=[(0, 1)], n_vertices=3, f=[1.0])
    with pytest.raises(ValueError, match="n_vertices"):
        srv.submit(edges=[], n_vertices=0)


def test_duplicate_edges_degree_invariant_under_cobatching():
    # a request with duplicate/bidirectional edge entries and f=None must get
    # the same diagram whether co-batched with f-carrying requests (per-
    # request _degree_f path) or not (from_edge_lists vectorized path)
    dup_edges = [(0, 1), (1, 0), (1, 2), (1, 2), (2, 0)]

    srv_alone = TopoServe(TopoServeConfig(method="none"))
    fut_alone = srv_alone.submit(edges=dup_edges, n_vertices=3)
    srv_alone.drain()

    srv_mixed = TopoServe(TopoServeConfig(method="none"))
    fut_mixed = srv_mixed.submit(edges=dup_edges, n_vertices=3)
    srv_mixed.submit(edges=[(0, 1)], n_vertices=2, f=[5.0, 7.0])
    srv_mixed.drain()

    assert _rows_equal(fut_alone.result(), fut_mixed.result())


def test_mesh_pad_rounds_up_to_mesh_multiple():
    class _FakeDevices:
        size = 4

    class _FakeMesh:
        devices = _FakeDevices()

    srv = TopoServe(TopoServeConfig(pad_batch_to=6), mesh=_FakeMesh())
    assert srv._pad_batch_to == 8  # next multiple of the 4-device mesh
    srv2 = TopoServe(TopoServeConfig(pad_batch_to=1), mesh=_FakeMesh())
    assert srv2._pad_batch_to == 4


def test_signature_features_matches_feature_vector():
    from repro.topo.features import feature_vector, signature_features

    plan = make_topo_plan(dim=1, method="prunit", edge_cap=64, tri_cap=96)
    g = pack_requests(
        [srv_req for srv_req in _requests([nx.cycle_graph(6),
                                           nx.petersen_graph()])],
        Bucket(16, 64, 96))
    direct = feature_vector(plan.execute(g), max_dim=plan.dim, res=4)
    shared = signature_features(g, plan, res=4)
    assert np.array_equal(np.asarray(direct), np.asarray(shared))


def _requests(graphs):
    from repro.serve.topo_serve import TopoRequest

    out = []
    for g in graphs:
        edges, n = _graph_query(g)
        out.append(TopoRequest(edges=tuple(edges), n_vertices=n))
    return out


def test_triangle_dense_graph_promoted_past_tri_cap():
    # K13: 78 edges fit the n32 rung (edge_cap 160) but its 286 triangles
    # exceed tri_cap 256 -> must promote to n64 so the diagrams stay exact
    srv = TopoServe(TopoServeConfig(method="none"))
    fut = srv.submit(*_graph_query(nx.complete_graph(13)))
    assert fut.bucket.n_pad == 64 and fut.bucket.tri_cap >= 286
    srv.drain()
    d = fut.result()
    assert int(d.betti(0)) == 1 and int(d.betti(1)) == 0  # K13 contractible


def test_failed_batch_resolves_futures_with_error():
    # an unexecutable bucket config must fail the future, not hang result()
    srv = TopoServe(TopoServeConfig(method="nonsense"))  # invalid reduction
    fut = srv.submit(*_graph_query(nx.cycle_graph(4)))
    assert srv.drain() == 0
    assert fut.done()
    with pytest.raises(ValueError):
        fut.result(timeout=1)


# ------------------------------------------------------------ observability

def test_futures_stamp_pickup_between_submit_and_resolve():
    srv = TopoServe(TopoServeConfig(method="prunit", max_batch=2))
    graphs = [nx.cycle_graph(5), nx.petersen_graph(), nx.path_graph(4),
              nx.gnp_random_graph(20, 0.2, seed=1), nx.complete_graph(6)]
    futs = [srv.submit(*_graph_query(g)) for g in graphs]
    assert all(f.picked_at is None for f in futs)
    assert srv.drain() == len(graphs)
    for f in futs:
        f.result()
        assert f.submitted_at <= f.picked_at <= f.resolved_at
    # batches of one bucket are cut in submission order
    small = [f for f in futs if f.bucket.n_pad == 16]
    assert [f.picked_at for f in small] == sorted(f.picked_at for f in small)


@pytest.mark.parametrize("repack", ["off", "on"])
def test_bucket_plan_names_its_program_and_phases(repack):
    from repro.core.graph import GraphBatch

    plan = make_topo_plan(dim=1, method="prunit", edge_cap=64, tri_cap=96,
                          repack=repack)
    g = GraphBatch(adj=jax.ShapeDtypeStruct((4, 16, 16), bool),
                   mask=jax.ShapeDtypeStruct((4, 16), bool),
                   f=jax.ShapeDtypeStruct((4, 16), "float32"))
    if repack == "off":
        text = plan.executor.lower(g).compile().as_text()
        assert text.startswith("HloModule jit_topo_plan_prunit_e64_t96_d1,")
        assert "/plan.persist/" in text
    else:
        text = plan.reduce_plan.lower(g).compile().as_text()
        assert text.startswith("HloModule jit_topo_reduce_prunit_e64_t96_d1,")
        assert "/plan.persist/" not in text
    assert "/plan.reduce/" in text


def test_served_diagrams_match_reference():
    from repro.core.persistence_jax import diagrams_to_numpy
    from repro.core.persistence_ref import diagrams_equal, persistence_diagrams

    srv = TopoServe(TopoServeConfig(method="prunit", pad_batch_to=4))
    graphs = [nx.cycle_graph(6), nx.petersen_graph(),
              nx.barabasi_albert_graph(12, 2, seed=3), nx.complete_graph(7),
              nx.gnp_random_graph(40, 0.1, seed=2)]
    queries = [_graph_query(g) for g in graphs]
    futs = [srv.submit(e, n) for e, n in queries]
    srv.drain()
    for (edges, n), fut in zip(queries, futs):
        adj = np.zeros((n, n), bool)
        for u, v in edges:
            adj[u, v] = adj[v, u] = True
        ref = persistence_diagrams(adj, adj.sum(1).astype(float), max_dim=1)
        got = diagrams_to_numpy(jax.tree.map(lambda x: x[None], fut.result()),
                                0, 1)
        assert diagrams_equal(ref, got), (ref, got)


# ---------------------------------------------------------- order-only rungs

# a small single-phase ladder with order-only rungs above it, so that
# graphs of 9 to 64 vertices take the two-phase path; their persist ladder
# is (8, 16, 56), (16, 32, 64), (32, 64, 64)
_SMALL = (Bucket(8, 16, 16), order_only_bucket(16), order_only_bucket(32),
          order_only_bucket(64))


def _small_server(**kw):
    return TopoServe(TopoServeConfig(buckets=_SMALL, method="both",
                                     max_batch=4, pad_batch_to=4, **kw))


def _thread(n: int, extra: int, seed: int) -> nx.Graph:
    """A reply tree grown by preferential attachment, plus ``extra`` repeat
    interactions: a discussion thread in miniature."""
    g = nx.barabasi_albert_graph(n, 1, seed=seed)
    rng = np.random.default_rng(seed)
    while extra:
        u, v = (int(x) for x in rng.integers(0, n, 2))
        if u != v and not g.has_edge(u, v):
            g.add_edge(u, v)
            extra -= 1
    return g


def _pd1_matches_reference(fut, g: nx.Graph) -> bool:
    """PD_1 of the served answer against the oracle on the UNREDUCED
    graph (CoralTDA and PrunIT are exact for PD_1)."""
    from repro.core.persistence_jax import diagrams_to_numpy
    from repro.core.persistence_ref import diagrams_equal, persistence_diagrams

    edges, n = _graph_query(g)
    adj = np.zeros((n, n), bool)
    for u, v in edges:
        adj[u, v] = adj[v, u] = True
    ref = persistence_diagrams(adj, adj.sum(1).astype(float), max_dim=1)
    got = diagrams_to_numpy(jax.tree.map(lambda x: x[None], fut.result()),
                            0, 1)
    return diagrams_equal({1: ref.get(1, [])}, {1: got[1]})


def _counter(name, srv, bucket):
    from repro import obs

    return obs.get_instrument(name).value(instance=srv._obs_instance,
                                          bucket=bucket)


def _cocktail_party(k: int) -> nx.Graph:
    """K_{2,...,2}: no vertex dominates another and every vertex is in the
    2-core, so the reductions keep it whole (2k vertices, 4k(k-1)(k-2)/3
    triangles)."""
    return nx.complement(nx.from_edgelist([(2 * i, 2 * i + 1)
                                           for i in range(k)]))


def test_default_ladder_keeps_order_only_rungs_apart():
    single = [b for b in DEFAULT_BUCKETS if not b.order_only]
    order_only = [b for b in DEFAULT_BUCKETS if b.order_only]
    assert [b.n_pad for b in single] == [16, 32, 64, 128]
    assert [b.n_pad for b in order_only] == [256, 512, 1024, 2048]
    # one harness label per rung: no order-only n_pad repeats another's
    assert len({b.n_pad for b in DEFAULT_BUCKETS}) == len(DEFAULT_BUCKETS)
    # no persist ladder holds an order-only rung's caps
    assert repack_ladder_for(DEFAULT_BUCKETS) == repack_ladder_for(
        SINGLE_PHASE_BUCKETS)
    ladder = order_only_ladder_for(DEFAULT_BUCKETS)
    assert [c.n_pad for c in ladder] == [8, 16, 32, 64, 128, 256, 512, 1024]
    assert ladder[-1] == ShapeClass(1024, 2048, 64)
    assert not any(c in repack_ladder_for(DEFAULT_BUCKETS) for c in ladder)


@pytest.mark.parametrize("n,ne,nt,want", [
    (8, 16, 16, 8),      # fits the single-phase rung exactly
    (9, 8, 0, 16),       # order over the single-phase rungs
    (8, 17, 0, 16),      # edges over them: routed by order
    (8, 10, 17, 16),     # triangles over them
    (17, 20, 0, 32),
    (32, 496, 4960, 32),  # the complete graph of the rung's order
])
def test_order_only_rungs_route_by_order(n, ne, nt, want):
    assert _small_server().bucket_for(n, ne, nt).n_pad == want


def test_order_only_rung_rejects_a_larger_order():
    with pytest.raises(ValueError, match="exceeds every bucket"):
        _small_server().bucket_for(65, 70, 0)


@pytest.mark.parametrize("graph,admitted", [
    (lambda: _thread(20, 3, 0), True),    # fits the n32 persist rung as is
    (lambda: _thread(40, 3, 0), False),   # above the ladder's top order
    (lambda: _cocktail_party(8), False),  # 16 vertices, 112 edges
])
def test_order_only_rung_without_reduction_admits_what_a_rung_holds(
        graph, admitted):
    """With method='none' nothing reduces, so submit knows whether a
    persist rung will hold the graph: it rejects one that none holds and
    serves the rest."""
    g = graph()
    srv = TopoServe(TopoServeConfig(buckets=_SMALL, method="none",
                                    max_batch=4, pad_batch_to=4))
    if not admitted:
        with pytest.raises(ValueError, match="reduces nothing"):
            srv.submit(*_graph_query(g))
        assert srv.pending() == 0
        return
    fut = srv.submit(*_graph_query(g))
    assert fut.bucket.order_only and srv.drain() == 1
    assert _pd1_matches_reference(fut, g)


def test_single_phase_rungs_keep_their_plans():
    srv = TopoServe(TopoServeConfig(method="prunit"))
    for b in SINGLE_PHASE_BUCKETS:
        plan = srv.plan_for(b)
        assert plan is make_topo_plan(dim=1, method="prunit",
                                      edge_cap=b.edge_cap,
                                      tri_cap=b.tri_cap)
        assert plan.key.repack == "off" and plan.key.ladder is None
    for b in DEFAULT_BUCKETS[len(SINGLE_PHASE_BUCKETS):]:
        plan = srv.plan_for(b)
        assert plan.key.repack == "on" and plan.order_only(b.n_pad)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_order_only_threads_match_the_reference(seed):
    srv = _small_server()
    graphs = [_thread(n, extra, seed * 10 + j) for j, (n, extra) in
              enumerate([(6, 1), (12, 3), (14, 2), (20, 4), (26, 5),
                         (30, 6), (31, 0)])]
    futs = [srv.submit(*_graph_query(g)) for g in graphs]
    assert {f.bucket.n_pad for f in futs} == {8, 16, 32}
    assert srv.drain() == len(graphs)
    for g, fut in zip(graphs, futs):
        assert _pd1_matches_reference(fut, g)
        if fut.bucket.order_only:
            assert fut.repack_class is not None
            assert fut.repack_class.n_pad <= fut.bucket.n_pad


def test_order_only_batch_fails_a_misfit_alone_and_counts_the_rest():
    srv = _small_server(record_batches=True)
    mates = [_thread(n, 3, n) for n in (18, 24, 28)]
    graphs = mates[:2] + [_cocktail_party(16)] + mates[2:]
    futs = [srv.submit(*_graph_query(g)) for g in graphs]
    assert all(f.bucket == _SMALL[2] for f in futs)
    assert srv.drain() == 3
    # 32 vertices, 480 edges, 4,480 triangles: no persist rung holds it
    with pytest.raises(ValueError, match="4480 triangles"):
        futs[2].result(timeout=1)
    assert futs[2].repack_class is None and srv.stats["failed"] == 1
    for g, fut in zip(mates, futs[:2] + futs[3:]):
        assert _pd1_matches_reference(fut, g)
    # the counters advance by the batch's post-reduction counts
    (bucket, reqs, _), = srv.executed_batches
    d, info = srv.plan_for(bucket).execute_info(pack_requests(reqs, bucket))
    assert list(info.class_index < 0) == [False, False, True, False]
    for name, want in [("reduced_vertices", info.n_vertices.sum()),
                       ("reduced_edges", info.n_edges.sum()),
                       ("reduced_triangles", info.n_triangles.sum()),
                       ("persist_calls", info.persist_calls)]:
        assert _counter(f"serve.{name}", srv, "n32") == want > 0
    # one row width for the batch: the widest rung of its persist ladder
    assert d.birth.shape == (4, max(diagram_size(c.n_pad, 1, c.edge_cap,
                                                 c.tri_cap)
                                    for c in info.ladder))
    assert not np.asarray(d.valid)[2].any()
    # called directly, the plan refuses the batch rather than pad it
    with pytest.raises(ValueError, match="fit no persist rung"):
        srv.plan_for(bucket).execute(pack_requests(reqs, bucket))


def test_order_only_rung_compiles_nothing_after_its_first_batch():
    import jax.monitoring as mon

    compiles = []

    def listen(event, duration, **_):
        if (event == "/jax/core/compile/backend_compile_duration"
                and listen.on):
            compiles.append(duration)

    listen.on = False
    mon.register_event_duration_secs_listener(listen)
    srv = _small_server()
    seeds = iter(range(100))
    # the first batch holds trees alone: reduced to nothing, they run no
    # persist program, so every later one was compiled up front
    batches = [[(20, 0), (24, 0), (30, 0), (32, 0)],
               [(20, 2), (24, 3), (30, 5), (32, 1)],
               [(17, 0), (28, 6), (19, 4), (25, 2)],
               [(31, 7), (22, 1), (29, 3), (18, 5)]]
    rungs = set()
    for j, batch in enumerate(batches):
        listen.on = j > 0
        futs = [srv.submit(*_graph_query(_thread(n, e, next(seeds))))
                for n, e in batch]
        assert srv.drain() == 4
        if j == 0:
            assert _counter("serve.persist_calls", srv, "n32") == 0
        else:
            rungs |= {f.repack_class for f in futs}
    assert compiles == [] and len(rungs) >= 2
    listen.on = False


@pytest.mark.parametrize("n,batch,rows", [
    (1, 32, 8), (8, 32, 8), (9, 32, 32), (32, 32, 32), (3, 4, 4),
    (33, 256, 128), (129, 256, 256)])
def test_persist_groups_run_at_few_row_counts(n, batch, rows):
    from repro.core.api import _group_rows, _group_sizes

    assert _group_rows(n, batch) == rows
    assert rows in _group_sizes(batch)
    assert _group_sizes(32) == [8, 32]


@pytest.mark.parametrize("seed", range(4))
def test_triangle_count_matches_dense_trace(seed):
    from repro.serve.topo_serve import _count_triangles

    g = nx.gnp_random_graph(40, 0.1 + 0.1 * seed, seed=seed)
    edge_set = {(min(u, v), max(u, v)) for (u, v) in g.edges()}
    a = nx.to_numpy_array(g, nodelist=range(40), dtype=np.int64)
    assert _count_triangles(edge_set) == int(np.trace(a @ a @ a)) // 6
