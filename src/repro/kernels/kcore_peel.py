"""Pallas TPU kernel: one k-core Jacobi peel sweep (CoralTDA inner loop).

``deg[u] = Σ_w A[u, w]·alive[w];  alive'[u] = alive[u] ∧ (deg[u] ≥ k)``

Fused masked mat-vec + threshold: the degree accumulator stays in VMEM
scratch across the W tiles, the threshold is applied in the epilogue, so one
sweep is a single HBM pass over A (the sweep is memory-bound; the fixed point
driver in repro/core/kcore.py calls this until no change).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(adj_ref, alive_w_ref, alive_u_ref, k_ref, out_ref, acc_ref, *, n_w: int):
    iw = pl.program_id(2)

    @pl.when(iw == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    adj = adj_ref[0]  # (TU, TW) f32
    alive = alive_w_ref[0]  # (TW,) f32
    acc_ref[...] += lax.dot_general(
        adj, alive[:, None], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )[:, 0]

    @pl.when(iw == n_w - 1)
    def _epilogue():
        k = k_ref[0]
        out_ref[0] = (alive_u_ref[0] > 0) & (acc_ref[...] >= k)


@functools.partial(jax.jit, static_argnames=("tile_u", "tile_w", "interpret"))
def kcore_peel_pallas(
    adj: jax.Array,
    alive: jax.Array,
    k: jax.Array | int,
    tile_u: int = 128,
    tile_w: int = 128,
    *, interpret: bool,
) -> jax.Array:
    """One peel sweep.  adj (B,N,N) bool, alive (B,N) bool, k scalar."""
    b, n, _ = adj.shape
    t = max(tile_u, tile_w)
    npad = -(-n // t) * t
    pad = npad - n
    adj_p = jnp.pad(adj, ((0, 0), (0, pad), (0, pad))).astype(jnp.float32)
    alive_p = jnp.pad(alive, ((0, 0), (0, pad)))
    alive_f = alive_p.astype(jnp.float32)
    k_arr = jnp.broadcast_to(jnp.asarray(k, jnp.float32), (1,))

    grid = (b, npad // tile_u, npad // tile_w)
    out = pl.pallas_call(
        functools.partial(_kernel, n_w=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, tile_u, tile_w), lambda b_, u, w: (b_, u, w),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, tile_w), lambda b_, u, w: (b_, w),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, tile_u), lambda b_, u, w: (b_, u),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1,), lambda b_, u, w: (0,), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, tile_u), lambda b_, u, w: (b_, u),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((b, npad), jnp.bool_),
        scratch_shapes=[pltpu.VMEM((tile_u,), jnp.float32)],
        interpret=interpret,
        name="kcore_peel_sweep",
    )(adj_p, alive_f, alive_p, k_arr)
    return out[:, :n]
