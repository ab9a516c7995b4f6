"""JAX bit-packed GF(2) persistence (boundary-matrix reduction).

The boundary matrix of the filtered clique complex is packed 32
simplices/`uint32` word so a column XOR is a short vector op, and the standard
reduction (pivot-chase with `low`) runs under `lax.fori_loop`/`while_loop`.
Everything vmaps over a GraphBatch and pjit-shards over the data axis — this
is the paper's workload (millions of small ego-net PDs) as one SPMD program.

Each per-dimension column loop stops at the batch's last real simplex of
that dimension, not at the block's cap: padded columns are all-zero and form
a suffix in rank order, so they reduce to nothing.  The bound is the batch
maximum, taken outside the per-graph ``vmap`` and passed in unmapped: a
per-row bound would make the loop's predicate batched, and ``vmap`` would
then select the whole block carry at every step.

A Pallas kernel with the identical algorithm living entirely in VMEM is in
repro/kernels/gf2_reduce.py; this module is its jnp reference and the default
CPU path.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.filtration import FilteredComplex, build_filtered_complex
from repro.core.graph import GraphBatch

WORD = 32


def pack_boundary(fc: FilteredComplex) -> jax.Array:
    """(S, W) uint32 packed boundary columns in sorted filtration order."""
    s = fc.size
    w = (s + WORD - 1) // WORD
    rows = jnp.repeat(jnp.arange(s), fc.face_pos.shape[1])
    fp = fc.face_pos.reshape(-1)
    ok = fp >= 0
    word = jnp.where(ok, fp // WORD, 0)
    bit = jnp.where(ok, fp % WORD, 0)
    contrib = jnp.where(ok, (jnp.uint32(1) << bit.astype(jnp.uint32)), jnp.uint32(0))
    b = jnp.zeros((s, w), jnp.uint32)
    # distinct faces -> distinct bits, so add == or
    return b.at[rows, word].add(contrib)


def _low(col: jax.Array) -> jax.Array:
    """Index of the highest set bit of a packed column, or -1."""
    w = col.shape[0]
    nz = col != 0
    any_bit = jnp.any(nz)
    # last nonzero word
    widx = (w - 1) - jnp.argmax(nz[::-1])
    word = col[widx]
    bit = 31 - lax.clz(word).astype(jnp.int32)
    return jnp.where(any_bit, widx.astype(jnp.int32) * WORD + bit, -1)


def reduce_packed(b: jax.Array, n_rows: int | None = None,
                  n_cols: jax.Array | None = None
                  ) -> tuple[jax.Array, jax.Array]:
    """Run the standard reduction. Returns (pivot_owner, positive).

    pivot_owner: (n_rows,) i32, pivot_owner[i] = j if column j kills row
    (simplex) i, else -1.  positive: (S,) bool, column reduced to zero (a
    birth).  In the flat square case rows == columns (n_rows = S); the
    per-dimension block path passes rectangular blocks.

    The column loop runs over columns ``0 .. n_cols - 1`` (all S when
    ``n_cols`` is None).  ``n_cols`` may be traced; the caller vouches that
    every later column is zero, and those columns stay ``positive = False``
    and own no row.  Under ``vmap`` pass it unmapped: a batched bound makes
    the loop's predicate batched, and every step then selects the whole
    carried block.
    """
    s = b.shape[0]
    n_rows = s if n_rows is None else n_rows

    def col_body(j, state):
        bm, owner, positive = state

        def w_cond(cs):
            col, done, _ = cs
            return ~done

        def w_body(cs):
            col, _, _ = cs
            l = _low(col)

            def no_bits(_):
                return col, jnp.array(True), jnp.int32(-1)

            def has_bits(_):
                p = owner[l]

                def claim(_):
                    return col, jnp.array(True), l

                def xor(_):
                    return col ^ bm[p], jnp.array(False), jnp.int32(-1)

                return lax.cond(p < 0, claim, xor, None)

            return lax.cond(l < 0, no_bits, has_bits, None)

        col0 = bm[j]
        col, _, claimed = lax.while_loop(
            w_cond, w_body, (col0, jnp.array(False), jnp.int32(-1))
        )
        bm = bm.at[j].set(col)
        owner = lax.cond(
            claimed >= 0, lambda o: o.at[claimed].set(j), lambda o: o, owner
        )
        positive = positive.at[j].set(claimed < 0)
        return bm, owner, positive

    owner0 = jnp.full((n_rows,), -1, jnp.int32)
    pos0 = jnp.zeros((s,), bool)
    stop = s if n_cols is None else n_cols
    _, owner, positive = lax.fori_loop(0, stop, col_body, (b, owner0, pos0))
    return owner, positive


def _block_caps(n: int, edge_cap: int, tri_cap: int,
                quad_cap: int) -> list[int]:
    caps = [n, edge_cap]
    if tri_cap:
        caps.append(tri_cap)
    if quad_cap:
        caps.append(quad_cap)
    return caps


def pack_boundary_blocks(fc: FilteredComplex, caps: list[int]):
    """Per-dimension packed boundary blocks (§Perf iteration 3).

    A dim-d column only has dim-(d-1) rows, so reducing each dimension as its
    own (cap_d, ceil(cap_{d-1}/32)) block shrinks the packed state ~4x vs
    one (S, S/32) matrix and keeps every pivot chase inside its block (the
    standard per-dimension PH reduction, here in bit-packed form).

    Returns (blocks, ranks, pos_of_rank):
      blocks[d]: (caps[d], W_{d-1}) u32 for d >= 1, in within-dim filtration
                 (rank) order;
      ranks: (S,) i32 within-dim rank of each sorted position;
      pos_of_rank[d]: (caps[d],) i32 sorted position of each rank (-1 pad).
    """
    sel = [fc.dims == d for d in range(len(caps))]
    ranks = jnp.zeros(fc.size, jnp.int32)
    pos_of_rank = []
    for d, s_d in enumerate(sel):
        r_d = jnp.cumsum(s_d.astype(jnp.int32)) - 1
        ranks = jnp.where(s_d, r_d, ranks)
        por = jnp.full((caps[d],), -1, jnp.int32)
        pos = jnp.arange(fc.size, dtype=jnp.int32)
        por = por.at[jnp.where(s_d, r_d, caps[d])].set(
            jnp.where(s_d, pos, -1), mode="drop")
        pos_of_rank.append(por)

    blocks = []
    for d in range(1, len(caps)):
        w = (caps[d - 1] + WORD - 1) // WORD
        por = pos_of_rank[d]
        valid_col = por >= 0
        fp = fc.face_pos[jnp.clip(por, 0), : d + 1]  # (cap_d, d+1) positions
        ok = (fp >= 0) & valid_col[:, None]
        r = jnp.where(ok, ranks[jnp.clip(fp, 0)], 0)
        word = jnp.where(ok, r // WORD, 0)
        bit = (r % WORD).astype(jnp.uint32)
        contrib = jnp.where(ok, jnp.uint32(1) << bit, jnp.uint32(0))
        b = jnp.zeros((caps[d], w), jnp.uint32)
        rows = jnp.repeat(jnp.arange(caps[d]), d + 1)
        b = b.at[rows, word.reshape(-1)].add(contrib.reshape(-1))
        blocks.append(b)
    return blocks, ranks, pos_of_rank


def reduce_packed_blocks(fc: FilteredComplex, caps: list[int],
                         inner=reduce_packed, n_cols: jax.Array | None = None):
    """Per-dimension block reduction; returns global (owner, positive).

    ``n_cols[d - 1]`` bounds block d's column loop (``reduce_packed``'s
    ``n_cols``); it must be at least this graph's count of dim-d simplices,
    whose ranks are the block's first columns.  Under ``vmap`` it is the
    batch's maximum, passed unmapped (see ``reduce_packed``).  Columns past
    the bound are padding: their ``positive`` is dropped below with every
    padded column's, so the result does not depend on the bound.  None runs
    every column, and is the only choice for an ``inner`` that takes no
    bound (the Pallas kernel).
    """
    blocks, ranks, pos_of_rank = pack_boundary_blocks(fc, caps)
    owner = jnp.full((fc.size,), -1, jnp.int32)
    positive = (fc.dims == 0)  # vertices: always births
    for d in range(1, len(caps)):
        # rows: dim d-1 ranks
        if n_cols is None:
            own_d, pos_d = inner(blocks[d - 1], caps[d - 1])
        else:
            own_d, pos_d = inner(blocks[d - 1], caps[d - 1],
                                 n_cols=n_cols[d - 1])
        # rows killed by a dim-d column
        killed = own_d >= 0
        row_pos = pos_of_rank[d - 1]
        col_pos = pos_of_rank[d][jnp.clip(own_d, 0)]
        owner = owner.at[jnp.where(killed, row_pos, fc.size)].set(
            jnp.where(killed, col_pos, -1), mode="drop")
        # columns reduced to zero are births of dim d
        cpos = pos_of_rank[d]
        cvalid = cpos >= 0
        positive = positive.at[jnp.where(cvalid, cpos, fc.size)].set(
            jnp.where(cvalid, pos_d, False), mode="drop")
    return owner, positive


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Diagrams:
    """Fixed-size persistence diagram tensor (per graph; vmap for batches).

    Each *birth simplex position* i contributes one row:
      birth/death: (S,) f32 (death = +inf for essential classes),
      dim:  (S,) i32 homology dimension,
      valid:(S,) bool (paired-with-persistence or essential, dim <= max_dim).
    """

    birth: jax.Array
    death: jax.Array
    dim: jax.Array
    valid: jax.Array

    def count(self, k: int) -> jax.Array:
        return jnp.sum(self.valid & (self.dim == k), axis=-1)

    def betti(self, k: int) -> jax.Array:
        return jnp.sum(
            self.valid & (self.dim == k) & jnp.isinf(self.death), axis=-1
        )

    # The one masking convention for downstream arithmetic (features,
    # metrics): invalid rows carry NaN birth/death sentinels and essential
    # classes carry +inf death, so both must be sanitized before any masked
    # sum/sort touches the tensors.
    def finite_birth(self) -> jax.Array:
        """(..., S) birth with invalid-row NaN sentinels replaced by 0."""
        return jnp.where(self.valid, jnp.nan_to_num(self.birth), 0.0)

    def finite_death(self, cap: float) -> jax.Array:
        """(..., S) death with NaN -> 0 and +inf (essential) capped at ``cap``."""
        death = jnp.nan_to_num(self.death, nan=0.0, posinf=cap)
        return jnp.where(self.valid, death, 0.0)

    def finite_points(self, cap: float) -> tuple[jax.Array, jax.Array]:
        """Sanitized ``(birth, death)`` pair; the masked-arithmetic layout
        shared by ``repro.topo.features`` and ``repro.metrics``."""
        return self.finite_birth(), self.finite_death(cap)


def pairs_to_diagrams(
    fc: FilteredComplex, owner: jax.Array, positive: jax.Array, max_dim: int,
    sublevel: bool = True,
) -> Diagrams:
    s = fc.size
    killed = owner >= 0
    death_val = jnp.where(killed, fc.values[jnp.clip(owner, 0)], jnp.inf)
    birth_val = fc.values
    essential = positive & ~killed & fc.valid
    is_birth = (killed | essential) & fc.valid
    nonzero_pers = ~killed | (death_val != birth_val)
    valid = is_birth & nonzero_pers & (fc.dims <= max_dim) & (fc.dims >= 0)
    sign = 1.0 if sublevel else -1.0
    birth = jnp.where(valid, sign * birth_val, jnp.nan)
    death = jnp.where(
        valid, jnp.where(jnp.isinf(death_val), jnp.inf, sign * death_val), jnp.nan
    )
    return Diagrams(birth=birth, death=death, dim=jnp.where(valid, fc.dims, -1), valid=valid)


def persistence_diagrams_counted(
    g: GraphBatch,
    max_dim: int = 1,
    edge_cap: int = 256,
    tri_cap: int = 512,
    quad_cap: int = 0,
    sublevel: bool = True,
    reducer: str = "jnp",
) -> tuple[Diagrams, jax.Array]:
    """Exact PDs of every graph in the batch, and the GF(2) loop's length.

    Returns ``(diagrams, columns)``: ``columns`` is (2,) i32, the column
    steps the batch's GF(2) loops ran and the column slots of its caps.
    With ``reducer="jnp"`` block d's loop stops at the batch's largest
    count of dim-d simplices; the other reducers run every slot.

    reducer: "jnp" (this module) or "pallas" (VMEM kernel, interpret on
    CPU) per-dimension blocks; "jnp-flat" / "pallas-flat" one (S, S/32)
    matrix.
    """
    n = g.adj.shape[-1]
    fc = jax.vmap(partial(
        build_filtered_complex, max_dim=max_dim, edge_cap=edge_cap,
        tri_cap=tri_cap, quad_cap=quad_cap, sublevel=sublevel,
    ))(g.adj, g.mask, g.f)
    if reducer in ("jnp", "pallas"):
        # per-dimension block reduction (§Perf iteration 3, default)
        caps = _block_caps(n, edge_cap, tri_cap, quad_cap)
        slots = sum(caps[1:])
        if reducer == "pallas":
            from repro.kernels import ops as kops

            owner, positive = jax.vmap(lambda c: reduce_packed_blocks(
                c, caps, inner=kops.gf2_reduce))(fc)
            run = slots
        else:
            # the dim-d simplices are ranks 0 .. count - 1 of block d, so
            # the batch's largest count bounds its loop, unmapped
            n_cols = jnp.stack([
                jnp.max(jnp.sum(fc.dims == d, axis=-1), initial=0)
                for d in range(1, len(caps))]).astype(jnp.int32)
            owner, positive = jax.vmap(
                lambda c, k: reduce_packed_blocks(c, caps, n_cols=k),
                in_axes=(0, None))(fc, n_cols)
            run = jnp.sum(n_cols)
    else:  # "jnp-flat" / "pallas-flat": one (S, S/32) matrix
        b = jax.vmap(pack_boundary)(fc)
        if reducer == "pallas-flat":
            from repro.kernels import ops as kops

            owner, positive = jax.vmap(kops.gf2_reduce)(b)
        else:
            owner, positive = jax.vmap(reduce_packed)(b)
        run = slots = fc.size
    d = jax.vmap(partial(pairs_to_diagrams, max_dim=max_dim,
                         sublevel=sublevel))(fc, owner, positive)
    return d, jnp.stack([jnp.int32(run), jnp.int32(slots)])


@partial(
    jax.jit,
    static_argnames=("max_dim", "edge_cap", "tri_cap", "quad_cap", "sublevel", "reducer"),
)
def persistence_diagrams_batched(
    g: GraphBatch,
    max_dim: int = 1,
    edge_cap: int = 256,
    tri_cap: int = 512,
    quad_cap: int = 0,
    sublevel: bool = True,
    reducer: str = "jnp",
) -> Diagrams:
    """Exact PDs of every graph in the batch (vmapped bit-packed reduction;
    ``persistence_diagrams_counted`` without the loop's length)."""
    return persistence_diagrams_counted(
        g, max_dim, edge_cap, tri_cap, quad_cap, sublevel, reducer)[0]


def diagrams_bitwise_equal(a: Diagrams, b: Diagrams) -> bool:
    """Bit-identical Diagrams comparison (NaN == NaN on invalid rows).

    The serving layer's parity contract (benchmarks/serve_bench.py,
    tests/test_topo_serve.py): scheduling must never change numerics.
    """
    import numpy as np

    return (
        np.array_equal(np.asarray(a.birth), np.asarray(b.birth), equal_nan=True)
        and np.array_equal(np.asarray(a.death), np.asarray(b.death), equal_nan=True)
        and np.array_equal(np.asarray(a.dim), np.asarray(b.dim))
        and np.array_equal(np.asarray(a.valid), np.asarray(b.valid))
    )


def diagrams_to_numpy(d: Diagrams, batch_index: int, max_dim: int):
    """Extract a {dim: [(birth, death)]} dict matching persistence_ref."""
    import numpy as np

    out = {}
    b = np.asarray(d.birth[batch_index])
    dd = np.asarray(d.death[batch_index])
    dim = np.asarray(d.dim[batch_index])
    val = np.asarray(d.valid[batch_index])
    for k in range(max_dim + 1):
        sel = val & (dim == k)
        out[k] = sorted(zip(b[sel].tolist(), dd[sel].tolist()))
    return out
