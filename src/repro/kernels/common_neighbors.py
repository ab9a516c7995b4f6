"""Pallas TPU kernel: common-neighbor counts on edges, ``(A @ A) ⊙ A``.

Backs the clustering-coefficient stage of the paper's §D.2 conjecture
(Figs 2/10) and triangle/2-simplex counting.  Standard tiled MXU matmul with
the elementwise edge-restriction fused into the epilogue (saves one full
(N, N) HBM round trip vs computing A@A then masking).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(a_uw_ref, a_wv_ref, a_uv_ref, out_ref, acc_ref, *, n_w: int):
    iw = pl.program_id(3)

    @pl.when(iw == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += lax.dot_general(
        a_uw_ref[0], a_wv_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(iw == n_w - 1)
    def _epilogue():
        out_ref[0] = (acc_ref[...] * a_uv_ref[0]).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def common_neighbors_pallas(
    adj: jax.Array, tile: int = 128, *, interpret: bool
) -> jax.Array:
    """cn[b, u, v] = |N(u) ∩ N(v)| on edges.  adj (B,N,N) bool -> (B,N,N) i32."""
    b, n, _ = adj.shape
    npad = -(-n // tile) * tile
    pad = npad - n
    a = jnp.pad(adj, ((0, 0), (0, pad), (0, pad))).astype(jnp.float32)

    grid = (b, npad // tile, npad // tile, npad // tile)
    out = pl.pallas_call(
        functools.partial(_kernel, n_w=grid[3]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, tile, tile), lambda b_, u, v, w: (b_, u, w),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, tile, tile), lambda b_, u, v, w: (b_, w, v),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, tile, tile), lambda b_, u, v, w: (b_, u, v),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, tile, tile), lambda b_, u, v, w: (b_, u, v),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((b, npad, npad), jnp.int32),
        scratch_shapes=[pltpu.VMEM((tile, tile), jnp.float32)],
        interpret=interpret,
        name="common_neighbors_fused",
    )(a, a, a)
    return out[:, :n, :n]
