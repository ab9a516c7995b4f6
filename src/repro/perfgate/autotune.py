"""Tile-size autotuner: sweep Pallas grid/block shapes, pin the winners.

``python -m repro.perfgate tune [--only KERNEL,...] [--quick]`` times each
registered kernel's candidate configs on a representative workload, picks
the argmin, and persists ``results/TUNED_tiles.json`` through
:mod:`repro.kernels.tuning` — from then on the ops-layer wrappers load
the pinned shapes for this device automatically (hardcoded tiles stay
the fallback for every other machine).

The registry is an extension point: :func:`register_tunable` a new
:class:`KernelTunable` (name, candidate space, workload factory, timing
closure) and it rides the same CLI, JSON schema, and fallback rules.
Candidate spaces are full cross-products of small per-parameter option
lists — tens of configs, not thousands; this is a measured sweep, not a
search heuristic.
"""
from __future__ import annotations

import dataclasses
import itertools
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.kernels import ops, tuning
from repro.kernels.ops import _interpret
from repro.kernels.auction_lap import (
    auction_lap_collapsed_pallas,
    auction_lap_pallas,
)
from repro.kernels.gf2_reduce import gf2_reduce_batch_pallas
from repro.kernels.hamming import hamming_scan_pallas
from repro.kernels.pairwise_gram import pairwise_l1_pallas
from repro.kernels.sinkhorn_lse import sinkhorn_lse_pallas


def _timed(fn, *args, repeats: int = 2, **kwargs) -> float:
    """Best-of-``repeats`` seconds with a warmup call (excludes compile)."""
    out = fn(*args, **kwargs)
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn(*args, **kwargs)
        jax.block_until_ready(out)
        best = min(best, time.perf_counter() - t0)
    return best


@dataclasses.dataclass(frozen=True)
class KernelTunable:
    """One sweepable kernel.

    ``space`` maps parameter name → candidate values (the sweep is the
    cross product).  ``make_workload(quick)`` builds the representative
    inputs once per sweep; ``time_config(workload, config, repeats)``
    returns seconds for one candidate.  ``workload_desc`` labels the
    JSON entry so a reader knows what shape the winner was measured at.
    """

    name: str
    space: dict[str, tuple]
    make_workload: Callable[[bool], Any]
    time_config: Callable[[Any, dict, int], float]
    workload_desc: Callable[[bool], str]


TUNABLES: dict[str, KernelTunable] = {}


def register_tunable(t: KernelTunable, overwrite: bool = False) -> KernelTunable:
    if not overwrite and t.name in TUNABLES:
        raise ValueError(f"tunable {t.name!r} already registered")
    bad = set(t.space) - set(tuning.DEFAULT_TILES.get(t.name, t.space))
    if bad:
        raise ValueError(
            f"tunable {t.name!r} sweeps params {sorted(bad)} that "
            f"kernels.tuning.DEFAULT_TILES does not declare")
    TUNABLES[t.name] = t
    return t


def sweep(t: KernelTunable, quick: bool = True,
          repeats: int = 2) -> dict:
    """Time every candidate config; return the winner + full trace."""
    workload = t.make_workload(quick)
    names = list(t.space)
    candidates = []
    for values in itertools.product(*(t.space[n] for n in names)):
        config = dict(zip(names, values))
        seconds = t.time_config(workload, config, repeats)
        candidates.append({"config": config, "seconds": seconds})
    best = min(candidates, key=lambda c: c["seconds"])
    return {
        "tiles": best["config"],
        "seconds": round(best["seconds"], 6),
        "workload": t.workload_desc(quick),
        "candidates": len(candidates),
        "sweep": [{"config": c["config"],
                   "seconds": round(c["seconds"], 6)}
                  for c in candidates],
    }


def tune(only: list[str] | None = None, quick: bool = True,
         repeats: int = 2, path: str | None = None,
         save: bool = True) -> dict:
    """Sweep the registered kernels; persist winners to TUNED_tiles.json."""
    keys = list(only) if only else list(TUNABLES)
    unknown = [k for k in keys if k not in TUNABLES]
    if unknown:
        raise SystemExit(
            f"unknown tunables {unknown}; known: {sorted(TUNABLES)}")
    winners = {}
    for k in keys:
        print(f"[perfgate] tuning {k} "
              f"({len(list(itertools.product(*TUNABLES[k].space.values())))} "
              f"configs)", flush=True)
        winners[k] = sweep(TUNABLES[k], quick=quick, repeats=repeats)
        print(f"[perfgate] {k}: winner {winners[k]['tiles']} "
              f"at {winners[k]['seconds']:.4g}s "
              f"({winners[k]['workload']})", flush=True)
    report = {"kernels": winners, "device": tuning.device_string(),
              "quick": quick}
    if save:
        from benchmarks.common import git_rev

        out = tuning.save_tuned(
            winners, path=path,
            meta={"generated_by": "python -m repro.perfgate tune",
                  "git_rev": git_rev(), "quick": quick})
        report["path"] = out
        print(f"[perfgate] wrote {out}")
    return report


# ------------------------------------------------------------- the kernels

def _gram_workload(quick: bool):
    m, d = (64, 256) if quick else (256, 512)
    x = jax.random.normal(jax.random.PRNGKey(7), (m, d), jnp.float32)
    return x


register_tunable(KernelTunable(
    name="pairwise_gram",
    space={"tile_m": (8, 16, 32), "tile_n": (128, 256),
           "tile_d": (128, 256)},
    make_workload=_gram_workload,
    time_config=lambda x, c, r: _timed(
        pairwise_l1_pallas, x, x, interpret=_interpret(), repeats=r, **c),
    workload_desc=lambda q: "G64_D256" if q else "G256_D512",
))


def _hamming_workload(quick: bool):
    # packed 128-bit codes (W=4 words): the TopoIndex default; corpus size
    # is the axis that matters — the scan is O(N·W) per query row
    q, n = (16, 4096) if quick else (16, 32768)
    ks = jax.random.split(jax.random.PRNGKey(17), 2)
    cq = jax.random.randint(ks[0], (q, 4), 0, 1 << 30).astype(jnp.uint32)
    cd = jax.random.randint(ks[1], (n, 4), 0, 1 << 30).astype(jnp.uint32)
    mq = jnp.full((q, 4), 0xFFFFFFFF, jnp.uint32)
    return cq, mq, cd


register_tunable(KernelTunable(
    name="hamming",
    space={"tile_q": (8, 16, 32), "tile_n": (128, 256, 512)},
    make_workload=_hamming_workload,
    time_config=lambda w, c, r: _timed(
        hamming_scan_pallas, *w, interpret=_interpret(), repeats=r, **c),
    workload_desc=lambda q: "Q16_N4096_W4" if q else "Q16_N32768_W4",
))


def _sinkhorn_workload(quick: bool):
    from repro.metrics.distances import _cloud_planes

    b, m = (2, 256) if quick else (4, 512)
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    x = jax.random.normal(ks[0], (b, m, 2), jnp.float32)
    y = jax.random.normal(ks[1], (b, m, 2), jnp.float32)
    flags = jnp.arange(m) >= m // 2
    dual = jax.random.normal(ks[2], (b, m), jnp.float32)
    logw = jnp.zeros((b, m), jnp.float32)
    e_t = jnp.full((b, 1), 0.5, jnp.float32)
    return (_cloud_planes(x, flags), _cloud_planes(y, flags), dual, logw,
            e_t)


register_tunable(KernelTunable(
    name="sinkhorn_lse",
    space={"tile": (64, 128, 256)},
    make_workload=_sinkhorn_workload,
    time_config=lambda w, c, r: _timed(
        sinkhorn_lse_pallas, *w, tile_m=c["tile"], tile_n=c["tile"],
        interpret=_interpret(), repeats=r),
    workload_desc=lambda q: "B2_M256" if q else "B4_M512",
))


def _auction_workload(quick: bool):
    b, m = (8, 16) if quick else (32, 16)
    return jax.random.uniform(jax.random.PRNGKey(9), (b, m, m),
                              jnp.float32, 0.0, 5.0)


register_tunable(KernelTunable(
    name="auction_lap",
    space={"tile_b": (1, 2, 4, 8)},
    make_workload=_auction_workload,
    time_config=lambda c3, c, r: _timed(
        auction_lap_pallas, c3, tile_b=c["tile_b"], interpret=_interpret(),
        repeats=r),
    workload_desc=lambda q: "B8_M16" if q else "B32_M16",
))


def _collapsed_workload(quick: bool):
    # random reduced-cost problems (cbar = pp − diag1 − diag2 over valid
    # slots) plus the equivalent expanded (2K)² matrices, so the sweep can
    # time the collapse="on"/"off" formulations on the same instances.
    # Half the point costs are quantized to a handful of levels: graph
    # persistence diagrams are tie-heavy (integer filtration values), and
    # ties are what make an over-eager fwd/rev interleave ping-pong — a
    # config must survive them to win the sweep
    b, k = (8, 16) if quick else (32, 16)
    ks = jax.random.split(jax.random.PRNGKey(21), 4)
    pp = jax.random.uniform(ks[0], (b, k, k), jnp.float32, 0.0, 4.0)
    pp = pp.at[b // 2:].set(jnp.round(pp[b // 2:] * 2.0) / 2.0)
    d1 = jax.random.uniform(ks[1], (b, k), jnp.float32, 0.0, 2.0)
    d2 = jax.random.uniform(ks[2], (b, k), jnp.float32, 0.0, 2.0)
    nreal = jax.random.randint(ks[3], (b, 2), k // 2, k + 1)
    idx = jnp.arange(k)
    keep1 = idx[None, :] < nreal[:, :1]
    keep2 = idx[None, :] < nreal[:, 1:]
    valid = keep1[:, :, None] & keep2[:, None, :]
    cbar = jnp.where(valid, pp - d1[:, :, None] - d2[:, None, :], 0.0)
    big = 1e6
    eye = jnp.eye(k, dtype=bool)[None]
    tl = jnp.where(valid, pp, big)
    tr = jnp.where(eye, jnp.where(keep1, d1, 0.0)[:, :, None], big)
    bl = jnp.where(eye, jnp.where(keep2, d2, 0.0)[:, None, :], big)
    br = jnp.zeros((b, k, k), jnp.float32)
    expanded = jnp.concatenate(
        [jnp.concatenate([tl, tr], axis=-1),
         jnp.concatenate([bl, br], axis=-1)], axis=-2)
    return cbar, keep1, keep2, expanded


def _time_collapsed(w, config, repeats):
    cbar, keep1, keep2, expanded = w
    if config["collapse"] == "off":
        # the legacy expanded path ignores rev_every (forward-only solver)
        return _timed(auction_lap_pallas, expanded, tile_b=config["tile_b"],
                      interpret=_interpret(), repeats=repeats)
    t = _timed(
        auction_lap_collapsed_pallas, cbar, keep1, keep2,
        jnp.zeros_like(cbar[..., 0]), tile_b=config["tile_b"],
        rev_every=config["rev_every"], interpret=_interpret(), repeats=repeats)
    # a config that trades convergence for wall time is disqualified — an
    # unconverged lane means uncertified (possibly wrong) distances and a
    # price the serve-level warm-start cache must refuse to store
    _, _, conv, _, _ = auction_lap_collapsed_pallas(
        cbar, keep1, keep2, jnp.zeros_like(cbar[..., 0]),
        tile_b=config["tile_b"], rev_every=config["rev_every"],
        interpret=_interpret())
    if not bool(jnp.all(conv)):
        return float("inf")
    return t


register_tunable(KernelTunable(
    name="auction_collapsed",
    space={"tile_b": (1, 2, 4), "rev_every": (0, 2, 8),
           "collapse": ("on", "off")},
    make_workload=_collapsed_workload,
    time_config=_time_collapsed,
    workload_desc=lambda q: "B8_K16" if q else "B32_K16",
))


def _gf2_workload(quick: bool):
    # random strictly-lower-triangular packed matrices: GF(2) elimination
    # terminates on any matrix (each XOR strictly lowers the pivot row),
    # and random fill is the worst case for XOR chain length
    b, s = (4, 64) if quick else (16, 128)
    w = -(-s // 32)
    bits = jax.random.randint(
        jax.random.PRNGKey(13), (b, s, w), 0, 1 << 16)
    row = jnp.arange(s)[None, :, None]
    word = jnp.arange(w)[None, None, :]
    below = jnp.where(row // 32 > word, -1,
                      jnp.where(row // 32 == word, (1 << (row % 32)) - 1,
                                0))
    return (bits & below).astype(jnp.uint32)


def _time_gf2(b3, config, repeats):
    mode = config["batch_mode"]
    if mode == "grid":
        return _timed(lambda x: gf2_reduce_batch_pallas(
            x, interpret=_interpret()), b3, repeats=repeats)
    return _timed(
        jax.jit(jax.vmap(lambda bb: ops.gf2_reduce(bb))), b3,
        repeats=repeats)


register_tunable(KernelTunable(
    name="gf2_reduce",
    space={"batch_mode": ("vmap", "grid")},
    make_workload=_gf2_workload,
    time_config=_time_gf2,
    workload_desc=lambda q: "B4_S64" if q else "B16_S128",
))
