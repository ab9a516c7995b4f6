"""Compile the served path's Pallas kernels for a described TPU v5e.

No chip is attached: the TPU compiler builds for a ``v5e:2x2`` topology
described in a fixture, from shapes alone.  What this catches is what
interpret mode cannot — block shapes off the (8, 128) tiling, bool and 1-D
refs, gathers and other primitives Mosaic does not lower — at the sizes the
served path uses: the GF(2) reducer at the top bucket's per-dimension block
caps (TopoServe ``DEFAULT_BUCKETS``), and the retrieval kernels at a 100k
corpus.  Each kernel must come out as a ``tpu_custom_call``.  The smallest
bucket's whole single-phase pipeline is compiled too; the larger buckets
take minutes each and are left to ``chip_smoke.py`` on the chip.

The topology is described only inside a fixture (never at import): one
process at a time may load the TPU library, and under pytest-xdist every
worker imports this file.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.tuning import DEFAULT_TILES


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    # the TPU compiler otherwise writes its logs under the system temp dir
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compile cache off: an entry
    compiled for a described device cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile()


# top bucket (n_pad 128, edge_cap 768, tri_cap 1024): the edge block is
# (768 columns, 128 vertex rows), the triangle block (1024, 768 edge rows)
GF2_BLOCKS = [(768, 128), (1024, 768)]


@pytest.mark.parametrize("cols,rows", GF2_BLOCKS)
def test_gf2_reduce_flat_compiles(one_chip, cols, rows):
    from repro.kernels.gf2_reduce import gf2_reduce_pallas

    b = jax.ShapeDtypeStruct((cols, -(-rows // 32)), jnp.uint32,
                             sharding=one_chip)
    c = _compile(lambda x: gf2_reduce_pallas(x, interpret=False,
                                             n_rows=rows), b)
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("cols,rows", GF2_BLOCKS)
def test_gf2_reduce_grid_compiles(one_chip, cols, rows):
    from repro.kernels.gf2_reduce import gf2_reduce_batch_pallas

    b = jax.ShapeDtypeStruct((256, cols, -(-rows // 32)), jnp.uint32,
                             sharding=one_chip)
    c = _compile(lambda x: gf2_reduce_batch_pallas(x, interpret=False,
                                                   n_rows=rows), b)
    assert "tpu_custom_call" in c.as_text()


def test_hamming_scan_compiles(one_chip):
    from repro.kernels.hamming import hamming_scan_pallas

    words = jax.ShapeDtypeStruct((256, 4), jnp.uint32, sharding=one_chip)
    corpus = jax.ShapeDtypeStruct((102_400, 4), jnp.uint32,
                                  sharding=one_chip)
    c = _compile(lambda q, m, x: hamming_scan_pallas(
        q, m, x, interpret=False, **DEFAULT_TILES["hamming"]),
        words, words, corpus)
    assert "tpu_custom_call" in c.as_text()


def test_pairwise_l1_compiles(one_chip):
    from repro.kernels.pairwise_gram import pairwise_l1_pallas

    q = jax.ShapeDtypeStruct((256, 512), jnp.float32, sharding=one_chip)
    x = jax.ShapeDtypeStruct((20_480, 512), jnp.float32, sharding=one_chip)
    c = _compile(lambda a, b: pairwise_l1_pallas(
        a, b, interpret=False, **DEFAULT_TILES["pairwise_gram"]), q, x)
    assert "tpu_custom_call" in c.as_text()


def test_auction_lap_collapsed_compiles(one_chip):
    from repro.kernels.auction_lap import auction_lap_collapsed_pallas

    t = DEFAULT_TILES["auction_collapsed"]
    b, k = 2560, 16
    shapes = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
              for s, d in (((b, k, k), jnp.float32), ((b, k), jnp.bool_),
                           ((b, k), jnp.bool_), ((b, k), jnp.float32))]
    c = _compile(lambda *a: auction_lap_collapsed_pallas(
        *a, tile_b=t["tile_b"], rev_every=t["rev_every"], interpret=False),
        *shapes)
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("kernel", ["lse", "pair_sum_plan",
                                    "pair_sum_cost"])
def test_sinkhorn_compiles(one_chip, kernel):
    from repro.kernels.sinkhorn_lse import (
        sinkhorn_lse_pallas,
        sinkhorn_pair_sum_pallas,
    )

    t = DEFAULT_TILES["sinkhorn_lse"]["tile"]
    b, s = 64, 1920          # top-bucket diagram rows: 128 + 768 + 1024

    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    planes, row, eps = sds(b, 8, s), sds(b, s), sds(b, 1)
    if kernel == "lse":
        c = _compile(lambda *a: sinkhorn_lse_pallas(
            *a, tile_m=t, tile_n=t, interpret=False),
            planes, planes, row, row, eps)
    else:
        mode = kernel.rsplit("_", 1)[1]
        c = _compile(lambda *a: sinkhorn_pair_sum_pallas(
            *a, mode=mode, tile_m=t, tile_n=t, interpret=False),
            planes, planes, row, row, row, row, eps)
    assert "tpu_custom_call" in c.as_text()


def test_smallest_bucket_pipeline_compiles(one_chip):
    """The whole single-phase TopoServe program of the smallest bucket."""
    from repro.core.api import TopoPlanKey, _pipeline
    from repro.core.graph import GraphBatch
    from repro.core.reduction import passes_for_method
    from repro.serve.topo_serve import DEFAULT_BUCKETS, TopoServeConfig

    cfg = TopoServeConfig()
    bucket = DEFAULT_BUCKETS[0]
    key = TopoPlanKey(dim=cfg.dim, passes=passes_for_method(cfg.method),
                      sublevel=cfg.sublevel, edge_cap=bucket.edge_cap,
                      tri_cap=bucket.tri_cap, quad_cap=cfg.quad_cap,
                      reducer=cfg.reducer)
    b, n = cfg.max_batch, bucket.n_pad
    g = GraphBatch(
        adj=jax.ShapeDtypeStruct((b, n, n), jnp.bool_, sharding=one_chip),
        mask=jax.ShapeDtypeStruct((b, n), jnp.bool_, sharding=one_chip),
        f=jax.ShapeDtypeStruct((b, n), jnp.float32, sharding=one_chip))
    c = _compile(lambda x: _pipeline(x, key), g)
    assert c.memory_analysis() is not None
