"""The benchmark's plain reference, its relabelling and its control."""
from __future__ import annotations

import numpy as np
import pytest
from ml_dtypes import bfloat16

from bench import reference
from bench.pool import Graph, Stream, apply_caps, relabel
from bench_fixtures import fixture_tree  # noqa: F401


def _random_graph(rng, n, p):
    a = np.triu(rng.random((n, n)) < p, 1)
    iu, iv = np.nonzero(a)
    edges = np.stack([iu, iv], axis=1).astype(np.int32)
    deg = np.bincount(edges.ravel(), minlength=n)
    return Graph(n=n, edges=edges, f=(deg / (n - 1)).astype(np.float32))


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_reference_equals_the_program_oracle(seed):
    """Same semantics as the program's own oracle, on random graphs."""
    from repro.core.persistence_ref import persistence_diagrams

    rng = np.random.default_rng(seed)
    g = _random_graph(rng, 14, 0.35)
    a = np.zeros((g.n, g.n), bool)
    a[g.edges[:, 0], g.edges[:, 1]] = True
    a |= a.T
    want = persistence_diagrams(a, g.f, max_dim=1)
    got = reference.diagram(g.n, g.edges, g.f, [0, 1])
    assert got == sorted((k, b, d) for k, pts in want.items() for b, d in pts)


@pytest.mark.parametrize("seed", [0, 5, 2**31 + 11])
def test_relabelling_leaves_the_diagrams_unchanged(seed):
    rng = np.random.default_rng(seed % 1000)
    for _ in range(5):
        g = _random_graph(rng, 12, 0.4)
        h = relabel(g, seed, 3, 17)
        assert sorted(map(tuple, np.sort(h.edges, 1))) != sorted(
            map(tuple, g.edges)) or g.n < 3 or len(g.edges) == 0
        assert h.triangles == g.triangles
        assert (reference.diagram(h.n, h.edges, h.f, [0, 1])
                == reference.diagram(g.n, g.edges, g.f, [0, 1]))


def test_stream_passes_reshuffle_and_relabel():
    rng = np.random.default_rng(0)
    pool = [_random_graph(rng, 8, 0.5) for _ in range(5)]
    s = Stream(pool, 42)
    first = [s.next() for _ in range(5)]
    second = [s.next() for _ in range(5)]
    assert s.passes == 1
    assert sorted(i for i, _ in first) == sorted(i for i, _ in second) == \
        list(range(5))
    i, g = second[0]
    j = next(k for k, (ii, _) in enumerate(first) if ii == i)
    assert not np.array_equal(first[j][1].f, g.f) or \
        np.unique(pool[i].f).size == 1


def test_caps_drop_and_count():
    rng = np.random.default_rng(3)
    gs = [_random_graph(rng, 10, 0.9), _random_graph(rng, 6, 0.2)]
    kept, dropped = apply_caps(gs, {"max_vertices": 128, "max_edges": 768,
                                    "max_triangles": 20})
    assert dropped == 1 and kept == [gs[1]]


def test_bfloat16_control_changes_the_diagrams():
    """The control computes the reference with f in bfloat16: on degree
    centralities it must disagree with float32 on most graphs."""
    rng = np.random.default_rng(7)
    bad = 0
    for _ in range(20):
        g = _random_graph(rng, 12, 0.4)
        g = Graph(n=g.n, edges=g.edges, f=g.f / np.float32(23132 / 11))
        bad += (reference.diagram(g.n, g.edges, g.f, [0, 1], dtype=bfloat16)
                != reference.diagram(g.n, g.edges, g.f, [0, 1]))
    assert bad >= 15


def test_control_reads_every_checked_graph_as_changed(fixture_tree):
    """``bench/control.py`` on a fixture cell: a whole run with the
    reference in bfloat16 in the program's place has to fail the check
    that decides ``correct``, on most graphs it compares."""
    from bench import control

    for r in control.readings(fixture_tree, "tiny.flood", [1, 2**31 + 3],
                              seconds=1.0):
        assert r["correct"] is False
        c = r["checks"]
        assert c["checked"]["value"] >= c["checked"]["min"] >= 1
        assert c["pd_mismatched"]["value"] > c["checked"]["value"] // 2
        assert c["unanswered"]["value"] == 0
