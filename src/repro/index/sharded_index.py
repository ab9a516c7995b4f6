"""ShardedIndex: TopoIndex partitioned row-wise over a device mesh.

The single-host :class:`repro.index.topo_index.TopoIndex` caps the corpus
at one device's RAM and runs its coarse Hamming scan on the host.  This
module shards the whole retrieve path over a 2-D ``("row", "col")`` mesh
(:func:`repro.launch.mesh.make_index_mesh`) while keeping the TopoIndex
query surface — ``SimilarityServe`` and every other caller work unchanged:

* **row stores** — embeddings, packed LSH codes, and compacted clouds are
  partitioned in contiguous row blocks over the *flattened* mesh (shard
  ``p`` of ``P`` owns rows ``[p·per, (p+1)·per)``,
  ``launch.sharding.index_row_spec``);
* **coarse stage on-device** — a ``shard_map`` runs the Pallas
  XOR+popcount kernel (``repro.kernels.hamming``) over each shard's local
  codes, takes a per-shard top-``m`` (``lax.top_k``), and the host merges
  the ``P·m`` survivors.  The global top-``m`` is a subset of the union
  of per-shard top-``m``'s, and ties resolve by (distance, row) on both
  sides, so the merged candidate set is *identical* to the single-host
  scan's;
* **SUMMA distributed Gram** — for ``coarse="none"`` (and ``gram()``),
  pairwise L1 runs as a 2-D blocked SUMMA: corpus rows shard over
  ``"row"``, the embedding width over ``"col"``, and query blocks
  ring-stream along ``"row"`` via ``lax.ppermute`` — after step ``s``,
  mesh row ``r`` holds query block ``(r − s) mod R``, computes its local
  ``pairwise_l1`` block partial over the local width slice, and
  ``psum``'s over ``"col"``.  R steps cover every (query-block, row-group)
  pair with no all-gather of either operand;
* **shard-owner re-rank gather** — :meth:`clouds` groups requested rows
  by owning shard, gathers from that shard's cloud block, and scatters
  results back into request order (the serve-level exact re-rank path).

``add`` appends through the base index and marks the device state dirty;
the next query re-shards (append = re-shard, the simple policy at this
corpus scale).  ``save``/``load`` delegate to the TopoIndex ``.npz``
format — packed codes included since 1.7 — so sharded and single-host
indexes round-trip through the same files.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro import obs
from repro.core.persistence_jax import Diagrams
from repro.index.topo_index import (
    QueryResult,
    TopoIndex,
    TopoIndexConfig,
    clouds_to_diagrams,
)
from repro.kernels import tuning
from repro.kernels.hamming import hamming_scan_pallas, pack_codes_u32
from repro.kernels.ops import _interpret
from repro.kernels.pairwise_gram import pairwise_l1_pallas
from repro.launch.mesh import make_index_mesh
from repro.launch.sharding import index_gram_specs, index_row_spec

# distance sentinel for padded rows inside the sharded scan: larger than
# any real Hamming count (lsh_bits <= 2^20) but far from int32 overflow
_PAD_DIST = np.int32(1) << 28

_C_SCANS = obs.counter(
    "index.sharded_scans",
    help="ShardedIndex device-side coarse scans / SUMMA gram calls")
_C_ROWS = obs.counter(
    "index.sharded_rows", help="corpus rows scanned across all shards")


class ShardedIndex:
    """Mesh-sharded retrieve→re-rank index with the TopoIndex surface.

    >>> index = ShardedIndex(TopoIndexConfig(coarse="lsh"))
    >>> index.add(diagrams, ids=["a", "b", "c"])
    >>> ids, dists = index.query(query_diagrams, k=2)

    Wrap an existing single-host index with :meth:`from_index`; the base
    index stays the host-side store of record (embeddings / ids / clouds),
    and this class owns the device-sharded replicas plus the distributed
    query plan.
    """

    def __init__(self, config: TopoIndexConfig | None = None, mesh=None,
                 base: TopoIndex | None = None):
        if base is not None and config is not None:
            raise ValueError("pass config or base, not both")
        self.base = base if base is not None else TopoIndex(config)
        self.mesh = mesh if mesh is not None else make_index_mesh()
        self._dirty = True
        self._codes_dev = None       # (P·per, W) u32, flattened-row sharded
        self._emb_dev = None         # (R·per_r, Dp) f32, ("row","col") sharded
        self._shard_clouds: list[np.ndarray] = []
        self._per = 0                # rows per shard (flattened partition)
        self._per_r = 0              # rows per mesh-row group (SUMMA)
        self._scan_fn = None
        self._summa_fn = None

    # --------------------------------------------------- TopoIndex surface

    @property
    def config(self) -> TopoIndexConfig:
        return self.base.config

    @property
    def ids(self) -> tuple[str, ...]:
        return self.base.ids

    def __len__(self) -> int:
        return len(self.base)

    @property
    def n_shards(self) -> int:
        return self.mesh.devices.size

    @classmethod
    def from_index(cls, index: TopoIndex, mesh=None) -> "ShardedIndex":
        return cls(mesh=mesh, base=index)

    def embed(self, d: Diagrams) -> jax.Array:
        return self.base.embed(d)

    def query_codes(self, d: Diagrams) -> np.ndarray:
        return self.base.query_codes(d)

    def add(self, d: Diagrams, ids: Optional[Sequence[str]] = None) -> list[str]:
        """Append through the base index; re-sharded lazily at next query."""
        out = self.base.add(d, ids=ids)
        self._dirty = True
        return out

    def save(self, path: str) -> None:
        self.base.save(path)

    @classmethod
    def load(cls, path: str, mesh=None) -> "ShardedIndex":
        """Load a TopoIndex save and shard it over ``mesh`` (lazily)."""
        return cls.from_index(TopoIndex.load(path), mesh=mesh)

    def clouds(self, rows: np.ndarray) -> Diagrams:
        """Shard-owner gather of stored clouds for ``rows`` (re-rank stage).

        Rows are grouped by owning shard (``row // per``), gathered from
        that shard's cloud block, and scattered back into request order —
        the distributed form of ``TopoIndex.clouds`` (same Diagrams
        layout, via the shared ``clouds_to_diagrams``).
        """
        if not self.base._has_clouds:
            # same contract as the base index: a pre-1.4 load keeps the
            # exact re-rank stage disabled rather than matching garbage
            return self.base.clouds(rows)
        self._ensure_device_state()
        rows = np.asarray(rows)
        flat = rows.reshape(-1).astype(np.int64)
        owner = flat // max(self._per, 1)
        local = flat - owner * self._per
        out = np.empty((flat.size, 3, self.config.n_points), np.float32)
        for p in np.unique(owner):
            sel = owner == p
            out[sel] = self._shard_clouds[int(p)][local[sel]]
        return clouds_to_diagrams(
            out.reshape(rows.shape + (3, self.config.n_points)),
            self.config.k)

    # ------------------------------------------------------- device state

    def _ensure_device_state(self) -> None:
        """(Re)build sharded device arrays + jitted plans after adds."""
        if not self._dirty:
            return
        base, mesh = self.base, self.mesh
        n = len(base)
        if n == 0:
            self._dirty = False
            return
        n_shards = mesh.devices.size
        rows_ax = mesh.shape["row"]
        cols_ax = mesh.shape["col"]
        per = -(-n // n_shards)
        per_r = -(-n // rows_ax)
        d = base._emb.shape[1]
        dp = -(-d // cols_ax) * cols_ax
        corpus_spec, query_spec, out_spec = index_gram_specs()

        # flattened row partition: packed codes (coarse scan) + cloud blocks
        if base.config.coarse == "lsh" and base._codes.size:
            codes = pack_codes_u32(base._codes)
            pad = np.zeros((n_shards * per - n, codes.shape[1]), np.uint32)
            self._codes_dev = jax.device_put(
                np.concatenate([codes, pad], axis=0),
                NamedSharding(mesh, index_row_spec()))
        else:
            self._codes_dev = None
        self._shard_clouds = [
            base._clouds[p * per:(p + 1) * per] for p in range(n_shards)]

        # SUMMA layout: rows over "row" groups, embedding width over "col"
        emb = np.zeros((rows_ax * per_r, dp), np.float32)
        emb[:n, :d] = base._emb
        self._emb_dev = jax.device_put(
            emb, NamedSharding(mesh, corpus_spec))

        self._per, self._per_r = per, per_r
        interp = _interpret()
        ht = tuning.resolve_tiles("hamming")
        gt = tuning.resolve_tiles("pairwise_gram")

        def scan(codes_all, q_codes, q_mask, *, m_loc: int):
            """Per-shard masked Hamming scan + local top-``m_loc``.

            Returns ``(dists, rows)`` shaped (P, Q, m_loc): per shard, the
            ``m_loc`` (distance, global-row) smallest local rows —
            ``lax.top_k`` on the negated distance prefers the lower local
            index on ties, matching the host merge's (dist, row) rule.
            """
            def body(codes_loc, qc, qm):
                dist = hamming_scan_pallas(
                    qc, qm, codes_loc, tile_q=ht["tile_q"],
                    tile_n=ht["tile_n"], interpret=interp)  # (Q, per) i32
                shard = (jax.lax.axis_index("row") * cols_ax
                         + jax.lax.axis_index("col"))
                gid = shard * per + jnp.arange(per, dtype=jnp.int32)
                dist = jnp.where(gid[None, :] < n, dist, _PAD_DIST)
                neg, loc = jax.lax.top_k(-dist, m_loc)
                return (-neg)[None], (shard * per + loc)[None]

            return jax.shard_map(
                body, mesh=mesh,
                in_specs=(index_row_spec(), P(None, None), P(None, None)),
                out_specs=(P(("row", "col"), None, None),) * 2,
                check_vma=False,
            )(codes_all, q_codes, q_mask)

        def summa(q_blocks, corpus):
            """2-D blocked SUMMA pairwise-L1: (Qp, Dp) × (N', Dp) → (Qp, N').

            Query blocks ring-stream along "row" (``ppermute``); each step
            computes the local Gram block partial over this column's width
            slice and ``psum``'s over "col".  After step ``s`` mesh row
            ``r`` holds query block ``(r − s) mod R`` and writes its
            result into that output slot — R steps place every block.
            """
            def body(qb, db):
                r = jax.lax.axis_index("row")
                qb_rows = qb.shape[0]
                out0 = jnp.zeros((rows_ax, qb_rows, db.shape[0]),
                                 jnp.float32)

                def step(s, carry):
                    qb, out = carry
                    part = pairwise_l1_pallas(
                        qb, db, tile_m=gt["tile_m"], tile_n=gt["tile_n"],
                        tile_d=gt["tile_d"], interpret=interp)
                    part = jax.lax.psum(part, "col")
                    blk = jax.lax.rem(r - s + rows_ax, rows_ax)
                    out = jax.lax.dynamic_update_slice(
                        out, part[None], (blk, 0, 0))
                    qb = jax.lax.ppermute(
                        qb, "row",
                        [(i, (i + 1) % rows_ax) for i in range(rows_ax)])
                    return qb, out

                _, out = jax.lax.fori_loop(0, rows_ax, step, (qb, out0))
                return out.reshape(rows_ax * qb_rows, db.shape[0])

            return jax.shard_map(
                body, mesh=mesh,
                in_specs=(query_spec, corpus_spec),
                out_specs=out_spec,
                check_vma=False,
            )(q_blocks, corpus)

        self._scan_fn = jax.jit(scan, static_argnames=("m_loc",))
        self._summa_fn = jax.jit(summa)
        self._dirty = False

    # -------------------------------------------------------------- query

    def _coarse_candidates(self, emb_q: np.ndarray, m: int,
                           probes: int | None = None) -> np.ndarray:
        """(Q, m) Hamming-nearest rows via the sharded on-device scan."""
        base = self.base
        margins = base._lsh_margins(emb_q)
        codes_q = pack_codes_u32(np.packbits(margins > 0, axis=-1))
        mask_u8 = base._query_bit_masks(margins, probes)
        mask_q = (np.full(codes_q.shape, 0xFFFFFFFF, np.uint32)
                  if mask_u8 is None else pack_codes_u32(mask_u8))
        n = len(base)
        m_loc = min(m, self._per)
        with obs.span("index.sharded_scan",
                      shape=f"Q{codes_q.shape[0]}_N{n}_P{self.n_shards}"):
            dd, rr = self._scan_fn(
                self._codes_dev, jnp.asarray(codes_q),
                jnp.asarray(mask_q), m_loc=m_loc)
        _C_SCANS.inc(kind="hamming")
        _C_ROWS.inc(n * codes_q.shape[0])
        # host-side merge of the per-shard top-m_loc survivors: same
        # composite dist·N + row key as TopoIndex._coarse_candidates, so
        # the merged set (ties included) is identical to the host scan's
        dd = np.asarray(dd).transpose(1, 0, 2).reshape(codes_q.shape[0], -1)
        rr = np.asarray(rr).transpose(1, 0, 2).reshape(codes_q.shape[0], -1)
        valid = dd < _PAD_DIST
        key = np.where(valid, dd.astype(np.int64) * n + rr, np.int64(2**62))
        key = np.take_along_axis(
            key, np.argpartition(key, m - 1, axis=-1)[:, :m], -1)
        key.sort(axis=-1)
        return key % n

    def query(self, d: Diagrams, k: int = 5,
              probes: int | None = None) -> QueryResult:
        """Batched kNN over the sharded corpus (TopoIndex semantics).

        ``coarse="lsh"``: sharded Hamming scan → host merge → one Gram
        call over the candidate union (``TopoIndex._rank_candidates``, so
        distances are bit-identical to the single-host index).
        ``coarse="none"`` / small corpus: full SUMMA distributed Gram.
        """
        base = self.base
        if not len(base):
            raise ValueError("query on an empty ShardedIndex")
        self._ensure_device_state()
        emb_q = base.embed(d)
        c = self.config
        n = len(base)
        kk = min(int(k), n)
        p = max(int(c.probes if probes is None else probes), 1)
        n_coarse = min(max(kk, 1) * c.lsh_overfetch * p, n)
        if c.coarse == "lsh" and n_coarse < n:
            cand = self._coarse_candidates(np.asarray(emb_q), n_coarse,
                                           probes=probes)
            dists, idx = base._rank_candidates(emb_q, cand, kk)
            stats = {"stage": "sharded_lsh+gram",
                     "coarse_candidates": int(n_coarse),
                     "probes": int(c.probes if probes is None else probes)}
        else:
            g = self._summa_gram(np.asarray(emb_q))
            rows = np.broadcast_to(np.arange(n, dtype=np.int64), g.shape)
            order = np.lexsort((rows, g), axis=-1)[:, :kk]
            dists = np.take_along_axis(g, order, axis=-1)
            idx = order
            stats = {"stage": "sharded_gram", "coarse_candidates": n}
        stats.update(shards=self.n_shards,
                     mesh={"row": int(self.mesh.shape["row"]),
                           "col": int(self.mesh.shape["col"])})
        ids = [[base._ids[j] for j in row] for row in idx]
        backends = [["gram"] * len(row) for row in idx]
        return QueryResult(ids, np.asarray(dists, np.float32), backends,
                           idx, stats)

    def _summa_gram(self, emb_q: np.ndarray) -> np.ndarray:
        """(Q, N) f32 L1 distances via the distributed SUMMA Gram."""
        self._ensure_device_state()
        mesh = self.mesh
        rows_ax = mesh.shape["row"]
        nq, d = emb_q.shape
        qp = -(-max(nq, 1) // rows_ax) * rows_ax
        dp = self._emb_dev.shape[1]
        q_pad = np.zeros((qp, dp), np.float32)
        q_pad[:nq, :d] = emb_q
        _, query_spec, _ = index_gram_specs()
        q_dev = jax.device_put(q_pad, NamedSharding(mesh, query_spec))
        with obs.span("index.sharded_gram",
                      shape=f"Q{nq}_N{len(self.base)}_P{self.n_shards}"):
            out = self._summa_fn(q_dev, self._emb_dev)
        _C_SCANS.inc(kind="summa")
        _C_ROWS.inc(len(self.base) * nq)
        # the flattened row partition pads only the last row group, so
        # device order == corpus order and the pad is one global tail slice
        return np.asarray(out)[:nq, :len(self.base)]

    def gram(self) -> np.ndarray:
        """(N, N) self-distance matrix via the distributed Gram."""
        self._ensure_device_state()
        return self._summa_gram(self.base._emb)
