"""JAX bit-packed persistence vs the exact NumPy oracle."""
import networkx as nx
import numpy as np
import pytest

from tests.conftest import hypothesis_or_stub

given, settings, st = hypothesis_or_stub()

from repro.core import GraphBatch, persistence_diagrams_batched
from repro.core.persistence_jax import diagrams_to_numpy
from repro.core.persistence_ref import (
    betti_numbers,
    diagrams_equal,
    persistence_diagrams,
)
from tests.conftest import graphs_to_batch, random_graphs


def _check_batch(gs, g, max_dim=1, **caps):
    d = persistence_diagrams_batched(g, max_dim=max_dim, **caps)
    for i in range(len(gs)):
        ref = persistence_diagrams(
            np.asarray(g.adj[i]), np.asarray(g.f[i]), np.asarray(g.mask[i]),
            max_dim=max_dim,
        )
        ours = diagrams_to_numpy(d, i, max_dim)
        assert diagrams_equal(ref, ours), (i, ref, ours)


@pytest.mark.parametrize("kind", ["er", "ba", "plc", "complete"])
def test_jax_pd_matches_oracle(kind):
    gs = random_graphs(kind, 5, seed=hash(kind) % 1000)
    g = graphs_to_batch(gs)
    _check_batch(gs, g, max_dim=1, edge_cap=128, tri_cap=512)


def test_known_diagrams_cycle():
    # C_6, constant f=0: one essential H0 class, one essential H1 class.
    g = graphs_to_batch([nx.cycle_graph(6)])
    g = GraphBatch(adj=g.adj, mask=g.mask, f=g.f * 0.0)
    d = persistence_diagrams_batched(g, max_dim=1, edge_cap=16, tri_cap=16)
    assert int(d.betti(0)[0]) == 1
    assert int(d.betti(1)[0]) == 1


def test_known_diagrams_complete():
    # K_5 is contractible as a clique complex: Betti = (1, 0).
    g = graphs_to_batch([nx.complete_graph(5)])
    d = persistence_diagrams_batched(g, max_dim=1, edge_cap=16, tri_cap=16)
    assert int(d.betti(0)[0]) == 1
    assert int(d.betti(1)[0]) == 0


def test_two_components():
    G = nx.disjoint_union(nx.cycle_graph(4), nx.path_graph(3))
    g = graphs_to_batch([G])
    d = persistence_diagrams_batched(g, max_dim=1, edge_cap=16, tri_cap=16)
    assert int(d.betti(0)[0]) == 2
    assert int(d.betti(1)[0]) == 1


def test_pd2_with_quads():
    # The octahedron's clique complex is S^2: Betti = (1, 0, 1).  Its PD_2
    # needs tetrahedra columns (quad_cap > 0).
    G = nx.octahedral_graph()
    g = graphs_to_batch([G])
    d = persistence_diagrams_batched(
        g, max_dim=2, edge_cap=16, tri_cap=16, quad_cap=8
    )
    assert int(d.betti(0)[0]) == 1
    assert int(d.betti(1)[0]) == 0
    assert int(d.betti(2)[0]) == 1
    ref = betti_numbers(np.asarray(g.adj[0]), max_dim=2)
    assert ref == {0: 1, 1: 0, 2: 1}


@settings(max_examples=15, deadline=None)
@given(st.integers(4, 12), st.floats(0.2, 0.7), st.integers(0, 2**31 - 1),
       st.booleans())
def test_jax_pd_random_f(n, p, seed, sublevel):
    G = nx.gnp_random_graph(n, p, seed=seed)
    rng = np.random.default_rng(seed)
    f = rng.integers(0, 6, size=n).astype(np.float32)
    g = graphs_to_batch([G])
    import jax.numpy as jnp

    g = GraphBatch(adj=g.adj, mask=g.mask,
                   f=jnp.where(g.mask, jnp.asarray(f)[None, :], jnp.inf))
    d = persistence_diagrams_batched(
        g, max_dim=1, edge_cap=128, tri_cap=512, sublevel=sublevel
    )
    ref = persistence_diagrams(
        np.asarray(g.adj[0]), f, np.asarray(g.mask[0]), max_dim=1,
        sublevel=sublevel,
    )
    ours = diagrams_to_numpy(d, 0, 1)
    assert diagrams_equal(ref, ours), (ref, ours)


def test_pallas_reducer_path():
    gs = random_graphs("er", 3, seed=5)
    g = graphs_to_batch(gs)
    d1 = persistence_diagrams_batched(g, max_dim=1, edge_cap=96, tri_cap=256,
                                      reducer="jnp")
    d2 = persistence_diagrams_batched(g, max_dim=1, edge_cap=96, tri_cap=256,
                                      reducer="pallas")
    for i in range(len(gs)):
        assert diagrams_equal(diagrams_to_numpy(d1, i, 1), diagrams_to_numpy(d2, i, 1))


# --------------------------------------------- the column loop's bound

def _full_cap_diagrams(g, max_dim, edge_cap, tri_cap, quad_cap, sublevel):
    """The per-dimension block reduction with every column slot run."""
    import jax
    from functools import partial

    from repro.core.filtration import build_filtered_complex
    from repro.core.persistence_jax import (
        _block_caps, pairs_to_diagrams, reduce_packed_blocks)

    caps = _block_caps(g.n, edge_cap, tri_cap, quad_cap)

    @jax.jit
    def full(g):
        fc = jax.vmap(partial(
            build_filtered_complex, max_dim=max_dim, edge_cap=edge_cap,
            tri_cap=tri_cap, quad_cap=quad_cap, sublevel=sublevel,
        ))(g.adj, g.mask, g.f)
        owner, positive = jax.vmap(
            lambda c: reduce_packed_blocks(c, caps))(fc)
        return fc, jax.vmap(partial(pairs_to_diagrams, max_dim=max_dim,
                                    sublevel=sublevel))(fc, owner, positive)

    fc, d = full(g)
    counts = [int(np.asarray(fc.dims == k).sum(-1).max(initial=0))
              for k in range(1, len(caps))]
    return d, counts, sum(caps[1:])


def _empty_batch(b, n):
    import jax.numpy as jnp

    return GraphBatch(adj=jnp.zeros((b, n, n), bool),
                      mask=jnp.zeros((b, n), bool),
                      f=jnp.full((b, n), jnp.inf, jnp.float32))


_BOUND_CASES = {
    # name: (batch, max_dim, edge_cap, tri_cap, quad_cap)
    "all_padding": (lambda: _empty_batch(3, 8), 1, 16, 16, 0),
    "batch_of_one": (lambda: graphs_to_batch(
        [nx.petersen_graph()], f_mode="random", seed=1), 1, 32, 32, 0),
    # K5: 10 edges and 10 triangles, the caps exactly
    "counts_at_caps": (lambda: graphs_to_batch(
        [nx.complete_graph(5), nx.cycle_graph(4)], f_mode="random",
        seed=2), 1, 10, 10, 0),
    "largest_row_bounds": (lambda: graphs_to_batch(
        [nx.path_graph(3), nx.gnp_random_graph(14, 0.4, seed=4),
         nx.cycle_graph(5), nx.empty_graph(2)], f_mode="random", seed=3),
        1, 128, 256, 0),
    # the octahedron (S^2) and K5 (five tetrahedra): quad columns
    "quads": (lambda: graphs_to_batch(
        [nx.octahedral_graph(), nx.complete_graph(5)], f_mode="random",
        seed=5), 2, 16, 16, 8),
}


@pytest.mark.parametrize("sublevel", [True, False])
@pytest.mark.parametrize("case", sorted(_BOUND_CASES))
def test_bounded_column_loop_is_bitwise_the_full_cap_loop(case, sublevel):
    import jax

    from repro.core.persistence_jax import (
        diagrams_bitwise_equal, persistence_diagrams_counted)

    make, max_dim, e_cap, t_cap, q_cap = _BOUND_CASES[case]
    g = make()
    full, counts, slots = _full_cap_diagrams(g, max_dim, e_cap, t_cap,
                                             q_cap, sublevel)
    d, cols = jax.jit(persistence_diagrams_counted, static_argnums=range(
        1, 6))(g, max_dim, e_cap, t_cap, q_cap, sublevel)
    assert diagrams_bitwise_equal(d, full)
    # the loop ran the batch's largest count of each dimension, no more
    assert np.asarray(cols).tolist() == [sum(counts), slots]
    if case == "all_padding":
        assert sum(counts) == 0
    if case == "counts_at_caps":
        assert counts == [e_cap, t_cap]


def _gf2_standard_reduction(b):
    """The textbook column reduction on Python-int bitsets (NumPy oracle
    of ``reduce_packed``'s contract)."""
    b = np.asarray(b)
    cols = [sum(int(w) << (32 * k) for k, w in enumerate(row)) for row in b]
    owner = np.full(b.shape[0], -1, np.int32)
    positive = np.zeros(b.shape[0], bool)
    reduced = {}
    for j, c in enumerate(cols):
        while c and (c.bit_length() - 1) in reduced:
            c ^= reduced[c.bit_length() - 1]
        if c:
            low = c.bit_length() - 1
            reduced[low] = c
            owner[low] = j
        else:
            positive[j] = True
    return owner, positive


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_reduce_packed_without_bound_runs_every_column(seed):
    import jax
    import jax.numpy as jnp

    from repro.core.filtration import build_filtered_complex
    from repro.core.persistence_jax import pack_boundary, reduce_packed

    G = nx.gnp_random_graph(12, 0.45, seed=seed)
    g = graphs_to_batch([G], f_mode="random", seed=seed)
    fc = build_filtered_complex(g.adj[0], g.mask[0], g.f[0], 1, 48, 96)
    b = pack_boundary(fc)
    want_owner, want_pos = _gf2_standard_reduction(b)
    owner, positive = jax.jit(reduce_packed)(b)
    assert np.array_equal(np.asarray(owner), want_owner)
    assert np.array_equal(np.asarray(positive), want_pos)
    # a traced bound at the full width is the same loop
    owner_t, pos_t = jax.jit(lambda x, k: reduce_packed(x, n_cols=k))(
        b, jnp.int32(b.shape[0]))
    assert np.array_equal(np.asarray(owner_t), want_owner)
    assert np.array_equal(np.asarray(pos_t), want_pos)
