"""Pallas TPU kernels: blocked log-sum-exp Sinkhorn updates, cost on the fly.

The dense Sinkhorn path (``repro.metrics.distances._DenseSinkhornOps``)
materializes the (M, N) squared-Euclidean cost between the two
diagonal-augmented diagram clouds — an O(S²) allocation per pair that caps
how dense a diagram the entropic distance can handle (the reason
``sinkhorn_w2`` compacts clouds to ``n_points``).  These kernels lift that
ceiling: the cost block ``c_ij = (xb_i − yb_j)² + (xd_i − yd_j)²`` (zeroed
on diagonal↔diagonal slot pairs) is rebuilt inside VMEM for each
``(tile_m, tile_n)`` tile from the coordinate planes, in the style of
``pairwise_gram.py``, so per-pair memory is O(tile²) however large the
diagram tensor is.

Two reductions cover everything one Sinkhorn iteration needs:

* ``sinkhorn_lse_pallas`` — per x-row online log-sum-exp over the y side:
  ``out_i = LSE_j(logw_j + (dual_j − c_ij)/ε)`` with the classic running
  (max, shifted-sum) merge across column tiles.  Both potential updates use
  it (the g-update swaps the x/y operands; the cost is symmetric).
* ``sinkhorn_pair_sum_pallas`` — masked scalar reduction over all pairs:
  ``mode="plan"`` accumulates ``⟨P, C⟩ = Σ exp(log plan)·c`` and
  ``mode="cost"`` accumulates ``Σ c`` (the ε scale statistic).  Pair
  validity is carried by the −inf slots of the log-weight planes.

Consistency contract: for a single column tile the online merge
degenerates to exactly ``m + log Σ exp(z − m)`` — the same expression, in
the same op order, that ``distances._lse`` computes — so at tile-fitting
sizes the blocked and dense paths run identical accumulation algebra and
agree to float32 roundoff (≤ ~1 ulp per update; XLA fusion decisions keep
strict bit equality out of reach).  Tests and ``metrics_bench`` assert
this tolerance, and that blocked runs at full-tensor sizes whose dense
cost matrix would blow the previous ``n_points²`` working-set ceiling.

Cloud planes are ``(B, 8, M)`` f32: plane 0/1 birth/death coordinate,
plane 2 the diagonal-slot flag, planes 3..7 zero (sublane padding to the
native f32 tile height).  Grid is ``(B, M/tile_m, N/tile_n)`` with the
column axis innermost; accumulators live in VMEM scratch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _safe_exp(t: jax.Array) -> jax.Array:
    """exp with −inf−(−inf)=NaN exponents treated as exp(−inf)=0.

    Finite exponents pass through untouched (``where`` returns them
    verbatim), preserving the single-tile bitwise contract.
    """
    return jnp.exp(jnp.where(jnp.isnan(t), -jnp.inf, t))


def _cost_block(x, y):
    """(TM, TN) squared-Euclidean cost from two coordinate-plane blocks,
    diagonal↔diagonal pairs free."""
    xb, xd, xf = x[0], x[1], x[2]
    yb, yd, yf = y[0], y[1], y[2]
    c = (xb[:, None] - yb[None, :]) ** 2 + (xd[:, None] - yd[None, :]) ** 2
    return jnp.where((xf[:, None] > 0) & (yf[None, :] > 0), 0.0, c)


def _lse_kernel(xp_ref, yp_ref, dual_ref, logw_ref, e_ref, out_ref,
                m_ref, s_ref, *, n_j: int):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        s_ref[...] = jnp.zeros_like(s_ref)

    c = _cost_block(xp_ref[0], yp_ref[0])
    e = e_ref[0]                                                  # (1, 1)
    # identical op order to the dense path: logw + (dual − c)/ε
    z = logw_ref[0] + (dual_ref[0] - c) / e
    m_blk = jnp.max(z, axis=-1)                                   # (TM,)
    s_blk = jnp.sum(_safe_exp(z - m_blk[:, None]), axis=-1)
    m_old, s_old = m_ref[0], s_ref[0]
    m_new = jnp.maximum(m_old, m_blk)
    s_new = (s_old * _safe_exp(m_old - m_new)
             + s_blk * _safe_exp(m_blk - m_new))
    m_ref[...] = m_new[None]
    s_ref[...] = s_new[None]

    @pl.when(j == n_j - 1)
    def _fin():
        out_ref[...] = jnp.where(jnp.isfinite(m_new),
                                 m_new + jnp.log(s_new), -jnp.inf)[None, None]


@functools.partial(jax.jit,
                   static_argnames=("tile_m", "tile_n", "interpret"))
def sinkhorn_lse_pallas(xp: jax.Array, yp: jax.Array, dual: jax.Array,
                        logw: jax.Array, e_t: jax.Array,
                        tile_m: int = 128, tile_n: int = 128,
                        *, interpret: bool) -> jax.Array:
    """(B, M) online-LSE: ``out[b, i] = LSE_j(logw[b,j] + (dual[b,j] − c_ij)/ε_b)``.

    ``xp``/``yp``: (B, 8, M)/(B, 8, N) coordinate planes; ``dual``/``logw``:
    (B, N); ``e_t``: (B, 1) per-pair ε.  Padding slots must carry
    ``logw = −inf`` (they then contribute exp(−inf) = 0).
    """
    b, _, m = xp.shape
    _, _, n = yp.shape
    mp = -(-m // tile_m) * tile_m
    np_ = -(-n // tile_n) * tile_n
    xpp = jnp.pad(xp, ((0, 0), (0, 0), (0, mp - m)))
    ypp = jnp.pad(yp, ((0, 0), (0, 0), (0, np_ - n)))
    # per-pair rows ride as (B, 1, N): every block's last two dims are then
    # (1, tile) of a (1, N) array, the layout Mosaic accepts
    dualp = jnp.pad(dual, ((0, 0), (0, np_ - n)))[:, None]
    logwp = jnp.pad(logw, ((0, 0), (0, np_ - n)),
                    constant_values=-jnp.inf)[:, None]

    grid = (b, mp // tile_m, np_ // tile_n)
    out = pl.pallas_call(
        functools.partial(_lse_kernel, n_j=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 8, tile_m), lambda b, i, j: (b, 0, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 8, tile_n), lambda b, i, j: (b, 0, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, tile_n), lambda b, i, j: (b, 0, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, tile_n), lambda b, i, j: (b, 0, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, 1), lambda b, i, j: (b, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, 1, tile_m), lambda b, i, j: (b, 0, i),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((b, 1, mp), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, tile_m), jnp.float32),
                        pltpu.VMEM((1, tile_m), jnp.float32)],
        interpret=interpret,
        name="sinkhorn_lse_blocked",
    )(xpp.astype(jnp.float32), ypp.astype(jnp.float32),
      dualp.astype(jnp.float32), logwp.astype(jnp.float32),
      e_t.astype(jnp.float32)[:, None])
    return out[:, 0, :m]


def _pair_sum_kernel(xp_ref, yp_ref, f_ref, g_ref, la_ref, lb_ref, e_ref,
                     out_ref, acc_ref, *, n_i: int, n_j: int, plan: bool):
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when((i == 0) & (j == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    c = _cost_block(xp_ref[0], yp_ref[0])
    la_col = la_ref[0].T                                           # (TM, 1)
    lb_row = lb_ref[0]                                             # (1, TN)
    pair = jnp.isfinite(la_col) & jnp.isfinite(lb_row)
    if plan:
        e = e_ref[0]                                               # (1, 1)
        z = la_col + lb_row + (f_ref[0].T + g_ref[0] - c) / e
        add = jnp.where(pair, jnp.exp(z) * c, 0.0)
    else:
        add = jnp.where(pair, c, 0.0)
    acc_ref[...] += jnp.sum(add, axis=(0, 1), keepdims=True)

    @pl.when((i == n_i - 1) & (j == n_j - 1))
    def _fin():
        out_ref[...] = acc_ref[...][None]


@functools.partial(jax.jit,
                   static_argnames=("mode", "tile_m", "tile_n", "interpret"))
def sinkhorn_pair_sum_pallas(xp: jax.Array, yp: jax.Array, f: jax.Array,
                             g: jax.Array, log_a: jax.Array,
                             log_b: jax.Array, e_t: jax.Array,
                             mode: str = "plan", tile_m: int = 128,
                             tile_n: int = 128,
                             *, interpret: bool) -> jax.Array:
    """(B,) masked pair reduction over the on-the-fly cost.

    ``mode="plan"``: Σ over valid pairs of ``exp(log_a + log_b +
    (f + g − c)/ε)·c`` (the transport cost ⟨P, C⟩).  ``mode="cost"``:
    Σ over valid pairs of ``c`` (the ε scale statistic; ``f``/``g``/``e_t``
    ignored).  Validity = finiteness of the log weights.
    """
    if mode not in ("plan", "cost"):
        raise ValueError(f"unknown pair-sum mode {mode!r}")
    b, _, m = xp.shape
    _, _, n = yp.shape
    mp = -(-m // tile_m) * tile_m
    np_ = -(-n // tile_n) * tile_n
    xpp = jnp.pad(xp, ((0, 0), (0, 0), (0, mp - m)))
    ypp = jnp.pad(yp, ((0, 0), (0, 0), (0, np_ - n)))
    fp = jnp.pad(f, ((0, 0), (0, mp - m)))[:, None]
    gp = jnp.pad(g, ((0, 0), (0, np_ - n)))[:, None]
    lap = jnp.pad(log_a, ((0, 0), (0, mp - m)),
                  constant_values=-jnp.inf)[:, None]
    lbp = jnp.pad(log_b, ((0, 0), (0, np_ - n)),
                  constant_values=-jnp.inf)[:, None]

    grid = (b, mp // tile_m, np_ // tile_n)
    out = pl.pallas_call(
        functools.partial(_pair_sum_kernel, n_i=grid[1], n_j=grid[2],
                          plan=(mode == "plan")),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 8, tile_m), lambda b, i, j: (b, 0, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 8, tile_n), lambda b, i, j: (b, 0, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, tile_m), lambda b, i, j: (b, 0, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, tile_n), lambda b, i, j: (b, 0, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, tile_m), lambda b, i, j: (b, 0, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, tile_n), lambda b, i, j: (b, 0, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, 1), lambda b, i, j: (b, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, 1, 1), lambda b, i, j: (b, 0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((b, 1, 1), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, 1), jnp.float32)],
        interpret=interpret,
        name=f"sinkhorn_pair_sum_{mode}",
    )(xpp.astype(jnp.float32), ypp.astype(jnp.float32),
      fp.astype(jnp.float32), gp.astype(jnp.float32),
      lap.astype(jnp.float32), lbp.astype(jnp.float32),
      e_t.astype(jnp.float32)[:, None])
    return out[:, 0, 0]
