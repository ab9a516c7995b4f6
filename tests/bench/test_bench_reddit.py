"""The REDDIT-BINARY configuration, its two cells and its two readers."""
from __future__ import annotations

import json
import os

import numpy as np
import pytest

from bench import reference
from bench.registry import Registry

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))


def _config():
    with open(os.path.join(ROOT, "bench", "configs",
                           "reddit-binary.json")) as fh:
        return json.load(fh)


def _pool(seed):
    cfg = _config()
    gen = Registry(ROOT).generator(cfg["generator"])
    return gen.make_pool(cfg["generator_params"], seed)


@pytest.mark.parametrize("seed", [1, 2**31 + 7])
def test_threads_meet_the_published_means(seed):
    """App. Table 2: 429.6 vertices and 497.8 edges on average, clipped at
    2,048; the graph seed orders the sizes and never changes them."""
    graphs, report = _pool(seed)
    orders = np.array([g.n for g in graphs])
    sizes = np.array([len(g.edges) for g in graphs])
    assert len(graphs) == report["graphs"] == 2000
    assert abs(orders.mean() / 429.6 - 1) < 0.01
    assert abs(sizes.mean() / 497.8 - 1) < 0.01
    assert 6 <= orders.min() and orders.max() <= 2048
    assert sorted(orders) == sorted(g.n for g in _pool(5)[0])
    for g in graphs[:50]:
        assert np.all(g.edges[:, 0] < g.edges[:, 1])
        assert len({tuple(e) for e in g.edges.tolist()}) == len(g.edges)
        # a reply tree holds every user: one component
        assert np.all(np.bincount(g.edges.ravel(), minlength=g.n) > 0)
        assert g.f.dtype == np.float32


def test_both_cells_resolve_with_their_metrics():
    reg = Registry(ROOT)
    reddit = reg.cell("reddit-binary.offline")
    assert (reddit.chips, reddit.traffic_name) == (1, "backlog")
    assert {m["name"] for m in reddit.end_to_end} == {"graphs_per_s",
                                                      "setup_s"}
    assert {m["name"] for m in reddit.per_layer} == {
        "measure_ms_per_batch.reddit", "persist_calls_per_batch.reddit"}
    proteins = reg.cell("proteins.offline")
    assert (proteins.chips, proteins.config_name) == (1, "proteins")
    assert {m["name"] for m in proteins.end_to_end} == {"graphs_per_s",
                                                        "setup_s"}
    assert [m["name"] for m in proteins.per_layer] == [
        "device_ms_per_batch.proteins"]
    for m in reddit.per_layer + proteins.per_layer:
        assert callable(reg.metric(m["name"]).read)


class _Run:
    def __init__(self, spans):
        self.spans = spans


@pytest.mark.parametrize("spans,measure_ms,calls", [
    (None, None, None),                                  # untraced run
    ([(0.0, 0.1, "serve.batch")], None, None),           # single phase only
    ([(0.0, 1.0, "serve.batch"), (0.1, 0.3, "plan.measure"),
      (0.3, 0.4, "plan.persist"), (0.4, 0.5, "plan.persist"),
      (1.0, 2.0, "serve.batch"), (1.1, 1.2, "plan.measure"),
      (1.2, 1.3, "plan.persist"), (2.0, 2.1, "serve.batch")], 150.0, 1.5),
])
def test_two_phase_readers(spans, measure_ms, calls):
    reg = Registry(ROOT)
    run = _Run(spans)
    got = reg.metric("measure_ms_per_batch.reddit").read(run)
    assert got == pytest.approx(measure_ms) if measure_ms else got is None
    assert reg.metric("persist_calls_per_batch.reddit").read(run) == calls


def test_threads_above_the_top_rung_are_served_exactly():
    """Four pool threads of 129-256 users through the program's default
    ladder: routed by order to the n256 rung, reduced, persisted at the
    rungs below, and equal in PD_1 to the reference on the whole graph."""
    from repro.serve import TopoServe, TopoServeConfig

    graphs, _ = _pool(1)
    sample = [g for g in graphs if 128 < g.n <= 256][:4]
    srv = TopoServe(TopoServeConfig(method="both", max_batch=4,
                                    pad_batch_to=4))
    futs = [srv.submit(g.edges.tolist(), g.n, g.f.tolist()) for g in sample]
    assert all(f.bucket.n_pad == 256 and f.bucket.order_only for f in futs)
    assert srv.drain() == 4
    for g, fut in zip(sample, futs):
        d = fut.result()
        sel = d.valid & (d.dim == 1)
        got = sorted(zip([1] * int(sel.sum()), d.birth[sel].tolist(),
                         d.death[sel].tolist()))
        assert got == reference.diagram(g.n, g.edges, g.f, [1])
        assert fut.repack_class.n_pad <= 128
