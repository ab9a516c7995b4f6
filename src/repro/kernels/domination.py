"""Pallas TPU kernel: dominated-vertex (violation-count) matrix.

Computes ``dom[u, v] = (|N[u] \\ N[v]| == 0) ∧ u≠v ∧ live(u) ∧ live(v)`` as a
tiled MXU matmul ``viol = Nc @ NotNc^T`` with the comparison fused into the
epilogue — the TPU-native form of the paper's Remark 9 / Algorithm 2 inner
loops (DESIGN.md §3).

Grid: (B, N/TU, N/TV, N/TW), W innermost; a (TU, TV) f32 accumulator lives in
VMEM scratch; all operand tiles are staged HBM→VMEM by BlockSpecs.  Tile
defaults are MXU-aligned (128).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(nc_ref, notc_ref, mask_u_ref, mask_v_ref, out_ref, acc_ref, *, n_w: int):
    iu = pl.program_id(1)
    iv = pl.program_id(2)
    iw = pl.program_id(3)

    @pl.when(iw == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    nc = nc_ref[0]  # (TU, TW) f32
    notc = notc_ref[0]  # (TV, TW) f32
    acc_ref[...] += lax.dot_general(
        nc, notc, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(iw == n_w - 1)
    def _epilogue():
        tu, tv = acc_ref.shape
        gu = iu * tu + lax.broadcasted_iota(jnp.int32, (tu, tv), 0)
        gv = iv * tv + lax.broadcasted_iota(jnp.int32, (tu, tv), 1)
        live = (mask_u_ref[0][:, None] > 0) & (mask_v_ref[0][None, :] > 0)
        dom = (acc_ref[...] == 0.0) & (gu != gv) & live
        out_ref[0] = dom


@functools.partial(jax.jit, static_argnames=("tile_u", "tile_v", "tile_w", "interpret"))
def domination_pallas(
    adj: jax.Array,
    mask: jax.Array,
    tile_u: int = 128,
    tile_v: int = 128,
    tile_w: int = 128,
    *, interpret: bool,
) -> jax.Array:
    """dom[b, u, v] = "v dominates u".  adj (B,N,N) bool, mask (B,N) bool."""
    b, n, _ = adj.shape
    n_pad = max(tile_u, tile_v, tile_w)
    npad = -(-n // n_pad) * n_pad
    pad = npad - n
    adj_p = jnp.pad(adj, ((0, 0), (0, pad), (0, pad)))
    mask_p = jnp.pad(mask, ((0, 0), (0, pad)))

    eye = jnp.eye(npad, dtype=bool)
    live = mask_p[:, None, :] & mask_p[:, :, None]
    nc = ((adj_p | eye) & live & mask_p[:, :, None]).astype(jnp.float32)
    notc = (1.0 - nc) * mask_p[:, None, :].astype(jnp.float32)
    maskf = mask_p.astype(jnp.float32)

    grid = (b, npad // tile_u, npad // tile_v, npad // tile_w)
    out = pl.pallas_call(
        functools.partial(_kernel, n_w=grid[3]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, tile_u, tile_w), lambda b_, u, v, w: (b_, u, w),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, tile_v, tile_w), lambda b_, u, v, w: (b_, v, w),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, tile_u), lambda b_, u, v, w: (b_, u),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, tile_v), lambda b_, u, v, w: (b_, v),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, tile_u, tile_v), lambda b_, u, v, w: (b_, u, v),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((b, npad, npad), jnp.bool_),
        scratch_shapes=[pltpu.VMEM((tile_u, tile_v), jnp.float32)],
        interpret=interpret,
        name="domination_viol_matmul",
    )(nc, notc, maskf, maskf)
    return out[:, :n, :n]
