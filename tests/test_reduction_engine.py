"""ReductionEngine + repack: fixpoint properties, two-phase parity, ladders.

The contracts under test (docs/ARCHITECTURE.md §ReductionEngine):

* pass scheduler — iterating registered passes reaches a fixpoint that is
  idempotent, and the final *diagrams* are pass-order invariant in every
  guaranteed dimension;
* repack — vertex compaction is a pure permutation (round-trips exactly),
  and two-phase execution (``repack="on"``) yields persistence pairs
  bit-identical to single-phase (``"off"``, the oracle) across methods ×
  sublevel/superlevel;
* ladder — first-fit shape-class selection is deterministic and always
  lands (default ladder), and serve/stream surfaces share reduced-size
  persist plans through the process-wide cache.
"""
import networkx as nx
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import from_edge_lists, topological_signature
from repro.core.api import make_topo_plan, plan_cache_info
from repro.core.persistence_jax import diagrams_to_numpy
from repro.core.reduction import (
    PASS_REGISTRY,
    ReductionEngine,
    ReductionPass,
    apply_passes,
    engine_exact_from_dim,
    get_pass,
    method_for_passes,
    passes_for_method,
    reduce_fixpoint,
    register_pass,
)
from repro.core.repack import (
    ShapeClass,
    compact_batch,
    default_ladder,
    diagram_size,
    measure_counts,
    select_classes,
)

CAPS = dict(edge_cap=96, tri_cap=160)


def _batch(n_pad=24, seed=0, with_f=False):
    graphs = [nx.cycle_graph(6), nx.petersen_graph(), nx.star_graph(9),
              nx.barabasi_albert_graph(18, 2, seed=seed),
              nx.gnp_random_graph(20, 0.2, seed=seed + 1),
              nx.complete_graph(6)]
    edge_lists, nvs = [], []
    for g in graphs:
        nodes = sorted(g.nodes())
        idx = {u: i for i, u in enumerate(nodes)}
        edge_lists.append([(idx[u], idx[v]) for (u, v) in g.edges()])
        nvs.append(len(nodes))
    f_values = None
    if with_f:
        rng = np.random.default_rng(seed)
        f_values = [[float(rng.integers(0, 4)) for _ in range(nv)]
                    for nv in nvs]
    return from_edge_lists(edge_lists, nvs, n_pad=n_pad, f_values=f_values)


def _pairs(d, b, k):
    return diagrams_to_numpy(d, b, max_dim=k)[k]


# ----------------------------------------------------------------- registry

def test_registry_methods_and_contracts():
    assert passes_for_method("both") == ("prunit", "kcore")
    assert passes_for_method("none") == ()
    assert method_for_passes(("prunit", "kcore")) == "both"
    assert method_for_passes(("strong_collapse",)) == "strong_collapse"
    with pytest.raises(ValueError, match="unknown reduction"):
        passes_for_method("bogus")
    with pytest.raises(ValueError, match="unknown reduction pass"):
        get_pass("bogus")
    # exactness contract: coral restricts to >= dim, prunit preserves all
    assert engine_exact_from_dim(("prunit",), 1) == 0
    assert engine_exact_from_dim(("prunit", "kcore"), 1) == 1
    assert engine_exact_from_dim(("kcore",), 0) == 0  # dim-0 kcore: identity


def test_register_pass_extension_point():
    noop = ReductionPass(name="_test_noop",
                         apply_mask=lambda adj, mask, f, dim, sublevel: mask,
                         exact_from_dim=lambda d: 0)
    try:
        register_pass(noop)
        with pytest.raises(ValueError, match="already registered"):
            register_pass(noop)
        g = _batch()
        g2 = apply_passes(g, ("_test_noop",), dim=1)
        assert np.array_equal(np.asarray(g.mask), np.asarray(g2.mask))
    finally:
        PASS_REGISTRY.pop("_test_noop", None)


# ----------------------------------------------------------------- fixpoint

def test_fixpoint_idempotent():
    g = _batch(with_f=True)
    for passes in [("prunit",), ("prunit", "kcore"), ("strong_collapse",)]:
        r1 = reduce_fixpoint(g, passes, dim=1)
        r2 = reduce_fixpoint(r1, passes, dim=1)
        assert np.array_equal(np.asarray(r1.mask), np.asarray(r2.mask)), passes


def test_fixpoint_removes_at_least_single_sweep():
    g = _batch(with_f=True)
    sweep = apply_passes(g, ("prunit", "kcore"), dim=1)
    fix = reduce_fixpoint(g, ("prunit", "kcore"), dim=1)
    # fixpoint mask is a subset of the single-sweep mask (monotone passes)
    assert not np.any(np.asarray(fix.mask) & ~np.asarray(sweep.mask))


def test_pass_order_invariance_of_diagrams():
    # masks may differ between orderings; the guaranteed diagrams may not
    g = _batch(with_f=True)
    d_a = make_topo_plan(dim=1, passes=("prunit", "kcore"), fixpoint=True,
                         **CAPS).execute(g)
    d_b = make_topo_plan(dim=1, passes=("kcore", "prunit"), fixpoint=True,
                         **CAPS).execute(g)
    for b in range(g.batch):
        assert _pairs(d_a, b, 1) == _pairs(d_b, b, 1), b


def test_strong_collapse_exact_all_dims_both_orientations():
    # equal-f twins: satellites sharing a hub and an f value collapse
    g = _batch(with_f=True)
    for sublevel in (True, False):
        d_red = make_topo_plan(dim=1, passes=("strong_collapse",),
                               sublevel=sublevel, fixpoint=True,
                               **CAPS).execute(g)
        d_ref = make_topo_plan(dim=1, passes=(), sublevel=sublevel,
                               **CAPS).execute(g)
        for b in range(g.batch):
            for k in (0, 1):
                assert _pairs(d_red, b, k) == _pairs(d_ref, b, k), (b, k)


# ------------------------------------------------------------------- repack

def test_compact_batch_roundtrip():
    g = _batch(with_f=True)
    gr = reduce_fixpoint(g, ("prunit",), dim=1)
    gc, order = compact_batch(gr)
    m = np.asarray(gc.mask)
    # live vertices are front-packed
    for b in range(g.batch):
        nv = int(m[b].sum())
        assert m[b, :nv].all() and not m[b, nv:].any()
    # the permutation round-trips: scattering back restores the original
    order = np.asarray(order)
    adj, f, mask = (np.asarray(x) for x in (gr.adj, gr.f, gr.mask))
    for b in range(g.batch):
        inv = np.argsort(order[b])
        assert np.array_equal(np.asarray(gc.adj)[b][inv][:, inv], adj[b])
        assert np.array_equal(np.asarray(gc.mask)[b][inv], mask[b])
        assert np.array_equal(np.asarray(gc.f)[b][inv], f[b])


def test_measure_counts():
    g = _batch()
    nv, ne, nt = measure_counts(g)
    assert np.array_equal(np.asarray(nv), np.asarray(g.n_vertices()))
    assert np.array_equal(np.asarray(ne), np.asarray(g.n_edges()))
    # petersen: 0 triangles; K6: 20 triangles
    assert int(np.asarray(nt)[1]) == 0
    assert int(np.asarray(nt)[5]) == 20


def test_default_ladder_and_selection():
    lad = default_ladder(64, 320, 512)
    assert lad[-1] == ShapeClass(64, 320, 512, 0)
    assert [c.n_pad for c in lad] == [8, 16, 32, 64]
    assert all(a < b for a, b in zip(lad, lad[1:]))  # sorted, strict
    idx = select_classes(lad, nv=np.array([3, 9, 64, 30]),
                         ne=np.array([3, 20, 300, 100]),
                         nt=np.array([1, 5, 400, 30]))
    assert [lad[i].n_pad for i in idx] == [8, 16, 64, 32]
    # cap overflow promotes past a rung whose vertex budget fits
    idx2 = select_classes(lad, nv=np.array([8]), ne=np.array([28]),
                          nt=np.array([56]))
    assert lad[idx2[0]].n_pad == 8
    idx3 = select_classes(lad, nv=np.array([8]), ne=np.array([29]),
                          nt=np.array([56]))
    assert lad[idx3[0]].n_pad == 16
    # a graph no rung holds gets index -1 (its future fails on its own)
    idx4 = select_classes((ShapeClass(8, 28, 56),), nv=np.array([20, 3]),
                          ne=np.array([10, 2]), nt=np.array([0, 0]))
    assert idx4.tolist() == [-1, 0]


@pytest.mark.slow
def test_two_phase_parity_methods_x_orientations():
    g = _batch(with_f=True)
    for method, dims in [("none", (0, 1)), ("prunit", (0, 1)),
                         ("coral", (1,)), ("both", (1,))]:
        for sublevel in (True, False):
            d_off = topological_signature(g, dim=1, method=method,
                                          sublevel=sublevel, repack="off",
                                          **CAPS)
            d_on = topological_signature(g, dim=1, method=method,
                                         sublevel=sublevel, repack="on",
                                         **CAPS)
            # one output shape: rows padded to the single-phase row count
            assert d_on.birth.shape == d_off.birth.shape
            for b in range(g.batch):
                for k in dims:
                    assert _pairs(d_off, b, k) == _pairs(d_on, b, k), \
                        (method, sublevel, b, k)


def test_two_phase_execute_info_report():
    g = _batch()
    plan = make_topo_plan(dim=1, method="both", repack="on", **CAPS)
    d, info = plan.execute_info(g)
    assert info is not None and len(info.class_index) == g.batch
    assert sum(info.rung_histogram().values()) == g.batch
    assert d.birth.shape[-1] == diagram_size(g.n, 1, CAPS["edge_cap"],
                                             CAPS["tri_cap"])
    # single-phase plans report no repack info
    d2, info2 = make_topo_plan(dim=1, method="both", **CAPS).execute_info(g)
    assert info2 is None


def test_custom_ladder_sanitized_per_input_shape():
    # rungs with caps above the plan's caps (non-monotone bucket configs)
    # or wider than the input order are dropped, and a top rung at the
    # input shape is appended — never an opaque scatter crash
    g = _batch(n_pad=24, with_f=True)
    bad = (ShapeClass(8, 4096, 4096), ShapeClass(16, CAPS["edge_cap"],
                                                 CAPS["tri_cap"]),
           ShapeClass(128, 4096, 8192))
    plan = make_topo_plan(dim=1, method="both", repack="on", ladder=bad,
                          **CAPS)
    d_on, info = plan.execute_info(g)
    assert all(c.n_pad <= g.n and c.edge_cap <= CAPS["edge_cap"]
               and c.tri_cap <= CAPS["tri_cap"] for c in info.ladder)
    assert info.ladder[-1] == ShapeClass(g.n, CAPS["edge_cap"],
                                         CAPS["tri_cap"])
    d_off = make_topo_plan(dim=1, method="both", **CAPS).execute(g)
    for b in range(g.batch):
        assert _pairs(d_on, b, 1) == _pairs(d_off, b, 1), b


def test_two_phase_sweep_vs_fixpoint_reduce_executor():
    # repack='on' honors fixpoint=False: the reduce phase runs one sweep,
    # whose surviving mask is a superset of the fixpoint's
    g = _batch(with_f=True)
    p_fix = make_topo_plan(dim=1, method="both", repack="on", **CAPS)
    p_swp = make_topo_plan(dim=1, method="both", repack="on",
                           fixpoint=False, **CAPS)
    assert p_fix is not p_swp
    _, (nv_f, _, _) = p_fix.reduce_executor(g)
    _, (nv_s, _, _) = p_swp.reduce_executor(g)
    assert (np.asarray(nv_f) <= np.asarray(nv_s)).all()
    # and both yield the oracle's pairs in the guaranteed dimension
    d_off = make_topo_plan(dim=1, method="both", **CAPS).execute(g)
    for plan in (p_fix, p_swp):
        d = plan.execute(g)
        for b in range(g.batch):
            assert _pairs(d, b, 1) == _pairs(d_off, b, 1), b


def test_repack_plan_validation():
    with pytest.raises(ValueError, match="repack"):
        make_topo_plan(dim=1, method="both", repack="sideways")

    class _FakeDevices:
        size = 4

    class _FakeMesh:
        devices = _FakeDevices()
        axis_names = ("data",)

    with pytest.raises(ValueError, match="mesh"):
        make_topo_plan(dim=1, method="both", repack="on", mesh=_FakeMesh())


# ------------------------------------------------------------- serve/stream

def test_serve_repack_parity_and_rung_sharing():
    from repro.serve import TopoServe, TopoServeConfig

    srv = TopoServe(TopoServeConfig(method="both", repack="on"))
    graphs = [nx.star_graph(8), nx.star_graph(25), nx.cycle_graph(6),
              nx.gnp_random_graph(30, 0.12, seed=4)]
    futs = [None] * len(graphs)
    for i, gnx in enumerate(graphs):
        nodes = sorted(gnx.nodes())
        idx = {u: j for j, u in enumerate(nodes)}
        futs[i] = srv.submit(
            edges=[(idx[u], idx[v]) for (u, v) in gnx.edges()],
            n_vertices=len(nodes))
    assert srv.drain() == len(graphs)
    assert len({f.bucket for f in futs}) >= 2
    for gnx, f in zip(graphs, futs):
        assert f.repack_class is not None
        assert f.repack_class.n_pad <= f.bucket.n_pad
        nodes = sorted(gnx.nodes())
        idx = {u: j for j, u in enumerate(nodes)}
        direct = topological_signature(
            from_edge_lists([[(idx[u], idx[v]) for (u, v) in gnx.edges()]],
                            [len(nodes)], n_pad=f.bucket.n_pad),
            dim=1, method="both", edge_cap=f.bucket.edge_cap,
            tri_cap=f.bucket.tri_cap)
        got = f.result()
        want = jax.tree.map(lambda x: x[0], direct)
        for k in (1,):
            got_pairs = sorted(zip(
                np.asarray(got.birth)[np.asarray(got.valid)
                                      & (np.asarray(got.dim) == k)].tolist(),
                np.asarray(got.death)[np.asarray(got.valid)
                                      & (np.asarray(got.dim) == k)].tolist()))
            assert got_pairs == _pairs_row(want, k)
    # the two star buckets both land on the small shared rung
    rungs_by_bucket: dict[int, set] = {}
    for (bn, rn) in srv.stats["repack_rungs"]:
        rungs_by_bucket.setdefault(rn, set()).add(bn)
    assert any(len(bs) > 1 for bs in rungs_by_bucket.values())


def _pairs_row(d, k):
    """Sorted (birth, death) pairs of one per-graph Diagrams slice."""
    b = np.asarray(d.birth)
    de = np.asarray(d.death)
    dm = np.asarray(d.dim)
    v = np.asarray(d.valid)
    sel = v & (dm == k)
    return sorted(zip(b[sel].tolist(), de[sel].tolist()))


def test_serve_and_similarity_share_one_ladder():
    from repro.serve.similarity import SimilarityServe
    from repro.serve.topo_serve import TopoServeConfig, repack_ladder_for

    sim = SimilarityServe(repack="on")
    srv_cfg = TopoServeConfig(repack="on")
    assert sim.server._repack_ladder == repack_ladder_for(
        tuple(sorted(srv_cfg.buckets)), srv_cfg.quad_cap)
    # the shared ladder flows end to end: add + query + rung accounting
    sim.add(edges=[(0, 1), (0, 2), (0, 3)], n_vertices=4, gid="star")
    fut = sim.submit(edges=[(0, 1), (0, 2)], n_vertices=3, k=1)
    sim.drain()
    assert fut.result().ids == ("star",)
    assert sim.repack_rungs()  # rung accounting flows through


def test_stream_repack_parity():
    from repro.core.delta import EDGE_DELETE, EDGE_INSERT, delta_from_lists
    from repro.stream import TopoStream, TopoStreamConfig, dim_pairs

    g = from_edge_lists([[(0, 1), (1, 2), (2, 3), (3, 0)]] * 2, [4, 4],
                        n_pad=16)
    cfg = TopoStreamConfig(dim=1, method="both", edge_cap=48, tri_cap=96,
                           repack="on")
    s = TopoStream(g, cfg)
    for ops in ([[(0, 2, EDGE_INSERT)], []],
                [[(0, 1, EDGE_DELETE)], [(1, 3, EDGE_INSERT)]]):
        d = s.apply(delta_from_lists(ops, edge_slots=1))
        ref = topological_signature(s.graph, dim=1, method="both",
                                    edge_cap=48, tri_cap=96)
        for b in range(2):
            assert dim_pairs(d, b, 1) == dim_pairs(ref, b, 1), b
    assert s.stats["recomputes"] > 0
    assert s.last_repack is not None
