"""Pallas kernel: masked Hamming distances over packed LSH codes.

``dist[i, j] = popcount((q[i] ^ c[j]) & mask[i])`` — the coarse stage of
TopoIndex (``repro/index/topo_index.py``) run on-device: query and corpus
hyperplane codes arrive bit-packed into uint32 words (``W = ceil(bits/32)``
per row), each grid step XORs one ``(TQ, W)`` query block against one
``(TN, W)`` corpus block and reduces ``lax.population_count`` over the
word axis into a native int32 ``(TQ, TN)`` output tile.

The per-query ``mask`` is the multi-probe LSH trick from the index layer:
clearing the ``t`` lowest-margin bits of a query's code from the distance
is exactly ``min`` over all ``2^t`` flip-probe codes, so ``probes``
costs one masked scan instead of ``2^t`` scans (pass an all-ones mask for
plain single-probe Hamming).

Word padding is free (packed codes zero-fill bits past ``lsh_bits`` on
both sides, and ``x ^ 0 & 0`` contributes nothing); row padding computes
throwaway rows that are sliced off, like the pairwise Gram kernel.  The
word axis rides *inside* a block (it is a handful of uint32 lanes), so the
grid is 2-D ``(Q/TQ, N/TN)`` with no reduction carry between steps.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def pack_codes_u32(codes_u8: np.ndarray) -> np.ndarray:
    """(B, n_bytes) uint8 packed codes → (B, W) uint32 words (host side).

    Pads the byte axis to a multiple of 4 with zeros before the view, so
    any ``lsh_bits`` multiple of 8 maps onto whole words; both sides of a
    scan must come through here so the (platform-endian) byte→word layout
    cancels out of every XOR.
    """
    codes_u8 = np.ascontiguousarray(codes_u8, dtype=np.uint8)
    b, nbytes = codes_u8.shape
    pad = (-nbytes) % 4
    if pad:
        codes_u8 = np.concatenate(
            [codes_u8, np.zeros((b, pad), np.uint8)], axis=1)
    return codes_u8.view(np.uint32)


def _kernel(q_ref, m_ref, c_ref, out_ref):
    q = q_ref[...]      # (TQ, W) uint32
    m = m_ref[...]      # (TQ, W) uint32
    c = c_ref[...]      # (TN, W) uint32
    x = jnp.bitwise_xor(q[:, None, :], c[None, :, :]) & m[:, None, :]
    out_ref[...] = jnp.sum(
        jax.lax.population_count(x).astype(jnp.int32), axis=-1)


@functools.partial(
    jax.jit, static_argnames=("tile_q", "tile_n", "interpret"))
def hamming_scan_pallas(
    codes_q: jax.Array,
    mask_q: jax.Array,
    codes_db: jax.Array,
    tile_q: int = 8,
    tile_n: int = 128,
    *, interpret: bool,
) -> jax.Array:
    """(Q, W) × (N, W) packed uint32 codes → (Q, N) int32 masked Hamming."""
    q, w = codes_q.shape
    n, w2 = codes_db.shape
    if w != w2:
        raise ValueError(f"code word counts differ: {w} vs {w2}")
    if mask_q.shape != codes_q.shape:
        raise ValueError(
            f"mask shape {mask_q.shape} != query shape {codes_q.shape}")
    qp = -(-q // tile_q) * tile_q
    np_ = -(-n // tile_n) * tile_n
    cq = jnp.pad(codes_q.astype(jnp.uint32), ((0, qp - q), (0, 0)))
    mq = jnp.pad(mask_q.astype(jnp.uint32), ((0, qp - q), (0, 0)))
    cd = jnp.pad(codes_db.astype(jnp.uint32), ((0, np_ - n), (0, 0)))

    grid = (qp // tile_q, np_ // tile_n)
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_q, w), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((tile_q, w), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((tile_n, w), lambda i, j: (j, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((tile_q, tile_n), lambda i, j: (i, j),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((qp, np_), jnp.int32),
        interpret=interpret,
        name="hamming_scan",
    )(cq, mq, cd)
    return out[:q, :n]
