"""Pallas TPU kernel: bit-packed GF(2) boundary-matrix reduction in VMEM.

The persistence pairing itself (the O(S^3)-worst-case stage the paper's
reductions shrink).  Columns are packed 32 simplices per uint32 word; the
whole packed matrix for one complex lives in VMEM (a 2048-simplex complex is
2048×64 u32 = 512 KiB), so the data-dependent pivot-chase never touches HBM.
Grid is a single program per complex; batching is an outer vmap at the ops
layer.

The kernel is fully caps-polymorphic: every dimension (columns S, packed
words W, owner rows) is read from the ref shapes, so one definition serves
any persist shape class — the two-phase repack path (repro/core/repack.py)
relies on this to compile the same kernel at each ladder rung's *reduced*
caps instead of the input caps, and the bounded rung ladder is what keeps
the number of compiled kernel variants small.

Matches repro.core.persistence_jax.reduce_packed bit-for-bit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

WORD = 32


def _low_of(col: jax.Array) -> jax.Array:
    """col: (1, W) i32 packed words -> highest set bit index or -1.

    The per-word top bit is taken elementwise (``clz`` on the vector) so
    the only cross-lane step is one signed max — no unsigned reduction
    and no scalar ``clz``, neither of which Mosaic lowers.
    """
    iota = lax.broadcasted_iota(jnp.int32, col.shape, 1)
    top = (WORD - 1) - lax.clz(col)
    return jnp.max(jnp.where(col != 0, iota * WORD + top, -1))


def _reduce_columns(bm_ref, owner_ref, pos_ref, row):
    """The column reduction loop over VMEM refs.

    ``row(j)`` indexes packed column ``j`` of ``bm_ref``; ``owner_ref``
    ``(.., 1, R)`` and ``pos_ref`` ``(.., 1, S)`` are int32 lane vectors.
    Owner lookups and updates are one-hot selects over the whole lane
    vector: a dynamic single-lane access is what Mosaic refuses, a
    masked select of a few vregs is what it does best.
    """
    s = pos_ref.shape[-1]
    r = owner_ref.shape[-1]
    iota_r = lax.broadcasted_iota(jnp.int32, owner_ref.shape, owner_ref.ndim - 1)
    iota_s = lax.broadcasted_iota(jnp.int32, pos_ref.shape, pos_ref.ndim - 1)

    def col_body(j, carry):
        def w_cond(cs):
            return ~cs[1]

        def w_body(cs):
            col, _, _ = cs
            l = _low_of(col)
            # owner[l], or -1 when l == -1 (owner entries are >= -1)
            p = jnp.max(jnp.where(iota_r == l, owner_ref[...], -1))
            done = (l < 0) | (p < 0)

            def xor(col):
                return col ^ bm_ref[row(p)]

            col = lax.cond(done, lambda c: c, xor, col)
            return col, done, jnp.where(p < 0, l, -1)

        col, _, claimed = lax.while_loop(
            w_cond, w_body, (bm_ref[row(j)], jnp.bool_(False), jnp.int32(-1)))
        bm_ref[row(j)] = col
        # claimed == -1 matches no lane, so a zero column leaves owner as is
        owner_ref[...] = jnp.where(iota_r == claimed, j, owner_ref[...])
        pos_ref[...] = jnp.where(iota_s == j, (claimed < 0).astype(jnp.int32),
                                 pos_ref[...])
        return carry

    lax.fori_loop(0, s, col_body, 0)


def _kernel(b_ref, bm_ref, owner_ref, pos_ref):
    bm_ref[...] = b_ref[...]
    owner_ref[...] = jnp.full(owner_ref.shape, -1, jnp.int32)
    pos_ref[...] = jnp.zeros(pos_ref.shape, jnp.int32)
    _reduce_columns(bm_ref, owner_ref, pos_ref,
                    row=lambda j: (pl.ds(j, 1), slice(None)))


def _batch_kernel(b_ref, bm_ref, owner_ref, pos_ref):
    bm_ref[...] = b_ref[...]
    owner_ref[...] = jnp.full(owner_ref.shape, -1, jnp.int32)
    pos_ref[...] = jnp.zeros(pos_ref.shape, jnp.int32)
    _reduce_columns(bm_ref, owner_ref, pos_ref,
                    row=lambda j: (0, pl.ds(j, 1), slice(None)))


@functools.partial(jax.jit, static_argnames=("interpret", "n_rows"))
def gf2_reduce_pallas(b: jax.Array, *, interpret: bool,
                      n_rows: int | None = None):
    """Reduce one packed boundary matrix.  b: (S, W) uint32.

    Returns (reduced_matrix, owner, positive) — owner[i] = killing column of
    row (simplex) i or -1; positive[j] = column j reduced to zero.  n_rows
    sizes the owner vector for rectangular per-dimension blocks (defaults to
    the square case n_rows = S).  The kernel works on int32 words (a
    bitcast, free) and int32 lane vectors for owner/positive.
    """
    s, w = b.shape
    r = s if n_rows is None else n_rows
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    bm, owner, positive = pl.pallas_call(
        _kernel,
        in_specs=[vmem],
        out_specs=[vmem, vmem, vmem],
        out_shape=[
            jax.ShapeDtypeStruct((s, w), jnp.int32),
            jax.ShapeDtypeStruct((1, r), jnp.int32),
            jax.ShapeDtypeStruct((1, s), jnp.int32),
        ],
        interpret=interpret,
        name="gf2_boundary_reduce",
    )(lax.bitcast_convert_type(b, jnp.int32))
    return (lax.bitcast_convert_type(bm, jnp.uint32), owner[0],
            positive[0] != 0)


@functools.partial(jax.jit, static_argnames=("interpret", "n_rows"))
def gf2_reduce_batch_pallas(b: jax.Array, *, interpret: bool,
                            n_rows: int | None = None):
    """Grid-batched reduction of (B, S, W) packed matrices.

    One grid step per complex (block ``(1, S, W)`` resident in VMEM) —
    the alternative to vmapping :func:`gf2_reduce_pallas` over the batch.
    Which wins is device-dependent; ``python -m repro.perfgate tune``
    times both and pins the winner as the ``gf2_reduce.batch_mode`` tile
    (``repro.kernels.ops.gf2_reduce_batch`` consults it).  owner/positive
    are ``(B, 1, R)`` / ``(B, 1, S)`` inside the kernel so every block's
    last two dims equal the array's.
    """
    bsz, s, w = b.shape
    r = s if n_rows is None else n_rows
    bm, owner, positive = pl.pallas_call(
        _batch_kernel,
        grid=(bsz,),
        in_specs=[pl.BlockSpec((1, s, w), lambda i: (i, 0, 0),
                               memory_space=pltpu.VMEM)],
        out_specs=[
            pl.BlockSpec((1, s, w), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, r), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, s), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, s, w), jnp.int32),
            jax.ShapeDtypeStruct((bsz, 1, r), jnp.int32),
            jax.ShapeDtypeStruct((bsz, 1, s), jnp.int32),
        ],
        interpret=interpret,
        name="gf2_boundary_reduce_batch",
    )(lax.bitcast_convert_type(b, jnp.int32))
    return (lax.bitcast_convert_type(bm, jnp.uint32), owner[:, 0],
            positive[:, 0] != 0)
