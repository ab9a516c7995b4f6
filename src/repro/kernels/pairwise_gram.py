"""Pallas TPU kernel: tiled pairwise-L1 Gram matrix over SW embeddings.

``gram[i, j] = Σ_d |x[i, d] − y[j, d]|`` — with ``x``/``y`` the pre-sorted
sliced-Wasserstein projection embeddings of ``repro.metrics.sw_embedding``
this *is* the diagram distance matrix TopoIndex ranks against (the sorting
already solved each direction's 1-D transport; what is left is a masked L1).

L1 cannot ride the MXU, so the kernel is a VPU reduction: grid
``(M/TM, N/TN, D/TD)`` with the feature axis innermost, a ``(TM, TN)`` f32
accumulator in VMEM scratch, and each step materializing one
``(TM, TN, TD)`` broadcast-difference block in registers/VMEM — tile
defaults ``(8, 128, 128)`` keep that block at 512 KB and the output tile at
the native f32 (8, 128) layout.  Rows are zero-padded to tile multiples and
sliced off afterwards (|0 − 0| contributes nothing, so feature padding is
free; row padding only computes throwaway rows).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, y_ref, out_ref, acc_ref, *, n_d: int):
    i_d = pl.program_id(2)

    @pl.when(i_d == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]  # (TM, TD)
    y = y_ref[...]  # (TN, TD)
    acc_ref[...] += jnp.sum(jnp.abs(x[:, None, :] - y[None, :, :]), axis=-1)

    @pl.when(i_d == n_d - 1)
    def _epilogue():
        out_ref[...] = acc_ref[...]


@functools.partial(
    jax.jit, static_argnames=("tile_m", "tile_n", "tile_d", "interpret"))
def pairwise_l1_pallas(
    x: jax.Array,
    y: jax.Array,
    tile_m: int = 8,
    tile_n: int = 128,
    tile_d: int = 128,
    *, interpret: bool,
) -> jax.Array:
    """(M, D) × (N, D) → (M, N) f32 pairwise-L1 distance (Gram) matrix."""
    m, d = x.shape
    n, d2 = y.shape
    if d != d2:
        raise ValueError(f"embedding widths differ: {d} vs {d2}")
    mp = -(-m // tile_m) * tile_m
    np_ = -(-n // tile_n) * tile_n
    dp = -(-d // tile_d) * tile_d
    xp = jnp.pad(x.astype(jnp.float32), ((0, mp - m), (0, dp - d)))
    yp = jnp.pad(y.astype(jnp.float32), ((0, np_ - n), (0, dp - d)))

    grid = (mp // tile_m, np_ // tile_n, dp // tile_d)
    out = pl.pallas_call(
        functools.partial(_kernel, n_d=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_m, tile_d), lambda i, j, k: (i, k),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((tile_n, tile_d), lambda i, j, k: (j, k),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((tile_m, tile_n), lambda i, j, k: (i, j),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        scratch_shapes=[pltpu.VMEM((tile_m, tile_n), jnp.float32)],
        interpret=interpret,
        name="pairwise_l1_gram",
    )(xp, yp)
    return out[:m, :n]
