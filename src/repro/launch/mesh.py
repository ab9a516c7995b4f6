"""Production meshes.  Functions, not module constants: importing this module
never touches jax device state (the dry-run forces 512 host devices *before*
any jax import; tests/benches see the single real device).
"""
from __future__ import annotations

import jax
import numpy as np


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod ("data","model"); 2 pods when multi_pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_serve_mesh(n_devices: int | None = None, *, multi_pod: bool = False):
    """Data-only mesh for TopoServe bucket execution.

    The TDA serve path is embarrassingly parallel over graphs, so it shards
    over ("pod", "data") only — no "model" axis — and TopoServe pads every
    bucket batch to a multiple of the mesh size (see
    repro/serve/topo_serve.py).  Default: every visible device on one axis.
    """
    n = n_devices if n_devices is not None else len(jax.devices())
    # Auto axes: the shard_map output is then an ordinary sharded array that
    # the serve layer can index per graph (``jax.make_mesh`` defaults to
    # Explicit axes, whose arrays refuse an unannotated ``x[i]``)
    if multi_pod:
        assert n % 2 == 0, f"multi_pod serve mesh needs even device count, got {n}"
        shape, axes = (2, n // 2), ("pod", "data")
    else:
        shape, axes = (n,), ("data",)
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_index_mesh(n_devices: int | None = None, rows: int | None = None):
    """2D ("row", "col") mesh for ShardedIndex retrieval.

    Corpus rows (embeddings, packed LSH codes, stored clouds) shard over
    the *flattened* ("row", "col") axes for the coarse Hamming scan, while
    the SUMMA-style distributed Gram streams query blocks along "row" with
    partial L1 sums reduced over "col" (docs/ARCHITECTURE.md
    §ShardedIndex).  ``rows`` defaults to the largest divisor of the
    device count <= sqrt(n), so 4 devices give the square (2, 2) mesh and
    one device degenerates to (1, 1).  Built from ``jax.devices()[:n]``
    directly so benches can stand up smaller submeshes next to the full
    one.
    """
    n = n_devices if n_devices is not None else len(jax.devices())
    if rows is None:
        rows = 1
        r = int(n ** 0.5)
        while r > 1:
            if n % r == 0:
                rows = r
                break
            r -= 1
    if n < 1 or n % rows:
        raise ValueError(f"rows={rows} does not divide device count {n}")
    devs = np.asarray(jax.devices()[:n]).reshape(rows, n // rows)
    return jax.sharding.Mesh(devs, ("row", "col"))


def make_test_mesh(shape=(1, 1), axes=("data", "model")):
    """Degenerate mesh over whatever devices exist (CPU tests)."""
    n = 1
    for s in shape:
        n *= s
    assert n <= len(jax.devices()), (shape, len(jax.devices()))
    return jax.make_mesh(shape, axes)


# Hardware constants (TPU v5e class, per chip) used by the roofline.
PEAK_FLOPS_BF16 = 197e12  # FLOP/s
HBM_BW = 819e9  # B/s
ICI_BW_PER_LINK = 50e9  # B/s
CHIPS_PER_POD = 256
