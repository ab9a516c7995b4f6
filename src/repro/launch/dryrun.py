"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production meshes, prove memory fit, and extract roofline inputs.

Usage:
  python -m repro.launch.dryrun --arch qwen3-1.7b --shape train_4k [--multipod]
  python -m repro.launch.dryrun --all [--multipod] [--out-dir results/dryrun]

The XLA_FLAGS lines below MUST precede any jax import (device count locks at
first init); only this module sets it — tests/benches see 1 device.

CPU only, and not run on a TPU host: it belongs to the language-model
scaffold, forces 512 host devices and ``--all`` spawns one child process
per cell, while a TPU chip can be held by one process at a time.  The
served path's chip check is ``chip_smoke.py`` (one process).
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse
import dataclasses
import json
import subprocess
import sys
import time
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.registry import ARCHS, get_config
from repro.launch import roofline as rl
from repro.launch import sharding as sh
from repro.launch.mesh import CHIPS_PER_POD, make_production_mesh
from repro.models import transformer as tf
from repro.models.config import SHAPES
from repro.train.optimizer import adamw_init
from repro.train.train_step import TrainState, make_train_step

def _abstract(tree, shardings=None):
    if shardings is None:
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree
        )
    return jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        tree, shardings,
    )


def input_specs(arch: str, shape: str, mesh, micro: bool = False, cfg=None):
    """ShapeDtypeStruct stand-ins (weak-type-correct, sharded, no allocation)
    for every model input of this cell, plus the step callable.

    micro=True lowers ONE microbatch (grad_accum=1, batch/ga) — used with
    --unroll for cost-exact roofline terms; a full step is exactly
    grad_accum x the microbatch plus the one grad all-reduce + optimizer
    epilogue (which this lowering still contains once).

    cfg overrides the registry config (depth-probe lowerings for --xcost).
    """
    cfg = cfg if cfg is not None else get_config(arch)
    sc = SHAPES[shape]
    if micro and sc.kind == "train" and sc.grad_accum > 1:
        sc = dataclasses.replace(
            sc, global_batch=sc.global_batch // sc.grad_accum, grad_accum=1)
    if shape == "long_500k" and not cfg.supports_long_context:
        return None, f"{arch} is full-attention; long_500k requires sub-quadratic"

    params_shapes = jax.eval_shape(lambda: tf.init_params(cfg, jax.random.PRNGKey(0)))
    pspecs = sh.param_specs(params_shapes)
    pshard = sh.to_shardings(pspecs, mesh)
    params_abs = _abstract(params_shapes, pshard)
    bspec = sh.batch_spec(sc.global_batch, mesh)
    bshard = NamedSharding(mesh, bspec)
    rep = NamedSharding(mesh, P())

    def tok(b, s):
        return jax.ShapeDtypeStruct((b, s), jnp.int32, sharding=bshard)

    extras = {}
    if cfg.family == "encdec":
        extras["frames"] = jax.ShapeDtypeStruct(
            (sc.global_batch, cfg.enc_seq, cfg.d_model), jnp.bfloat16,
            sharding=bshard)
    if cfg.family == "vlm":
        extras["vision"] = jax.ShapeDtypeStruct(
            (sc.global_batch, cfg.vision_tokens, cfg.d_model), jnp.bfloat16,
            sharding=bshard)
        s_pos = 1 if sc.kind == "decode" else sc.seq_len
        extras["mrope_positions"] = jax.ShapeDtypeStruct(
            (sc.global_batch, s_pos, 3), jnp.int32, sharding=bshard)

    if sc.kind == "train":
        opt_shapes = jax.eval_shape(adamw_init, params_shapes)
        ospecs = sh.opt_specs(pspecs)
        oshard = sh.to_shardings(ospecs, mesh)
        state_abs = TrainState(params=params_abs, opt=_abstract(opt_shapes, oshard))
        batch = {"tokens": tok(sc.global_batch, sc.seq_len), **extras}
        step = make_train_step(cfg, grad_accum=sc.grad_accum,
                               extra_keys=tuple(extras))
        return (step, (state_abs, batch)), None

    caches_shapes = jax.eval_shape(
        lambda: tf.init_caches(cfg, sc.global_batch, sc.seq_len)
    )
    cspecs = sh.cache_specs(caches_shapes, cfg, mesh, sc.global_batch)
    cshard = sh.to_shardings(cspecs, mesh)
    caches_abs = _abstract(caches_shapes, cshard)

    if sc.kind == "prefill":
        def prefill_step(params, tokens, caches, extra):
            logits, new_caches = tf.forward(
                params, cfg, tokens, mode="prefill", caches=caches, **extra
            )
            return logits[:, -1, :], new_caches

        return (prefill_step, (params_abs, tok(sc.global_batch, sc.seq_len),
                               caches_abs, extras)), None

    def decode_step(params, tokens, caches, pos, extra):
        logits, new_caches = tf.forward(
            params, cfg, tokens, mode="decode", caches=caches, pos=pos, **extra
        )
        return jnp.argmax(logits[:, -1, :], axis=-1), new_caches

    pos_abs = jax.ShapeDtypeStruct((), jnp.int32, sharding=rep)
    return (decode_step, (params_abs, tok(sc.global_batch, 1), caches_abs,
                          pos_abs, extras)), None


def tda_input_specs(mesh, sharded: bool = True):
    """The paper's own workload: batched ego-net PDs sharded over the mesh.

    sharded=True routes through shard_map (§Perf iteration 5 — zero
    collectives); False keeps the plain-pjit baseline for comparison.
    """
    from repro.configs.tda_ego import config as tda_config
    from repro.core.api import topological_signature, topological_signature_sharded
    from repro.core.graph import GraphBatch

    tcfg = tda_config()
    n_dev = mesh.devices.size
    b = tcfg.graphs_per_device * n_dev
    all_axes = tuple(mesh.axis_names)
    gshard = NamedSharding(mesh, P(all_axes))
    g_abs = GraphBatch(
        adj=jax.ShapeDtypeStruct((b, tcfg.n_pad, tcfg.n_pad), jnp.bool_, sharding=gshard),
        mask=jax.ShapeDtypeStruct((b, tcfg.n_pad), jnp.bool_, sharding=gshard),
        f=jax.ShapeDtypeStruct((b, tcfg.n_pad), jnp.float32, sharding=gshard),
    )

    def tda_step(g):
        if sharded:
            d = topological_signature_sharded(
                g, mesh, dim=tcfg.max_dim, method="both",
                sublevel=tcfg.sublevel, edge_cap=tcfg.edge_cap,
                tri_cap=tcfg.tri_cap,
            )
        else:
            d = topological_signature(
                g, dim=tcfg.max_dim, method="both", sublevel=tcfg.sublevel,
                edge_cap=tcfg.edge_cap, tri_cap=tcfg.tri_cap,
            )
        return d.birth, d.death, d.dim, d.valid

    return tda_step, (g_abs,)


def tda_two_phase_specs(mesh, phase: str):
    """Cost specs for the two-phase ReductionEngine path (core/api.py,
    ``repack="on"``), one cell per phase so the roofline separates them:

    * ``phase="reduce"`` — fixpoint pass iteration + vertex compaction +
      simplex-count measurement at the *input* caps (masked matmul sweeps;
      the cheap phase), shard_mapped with zero collectives;
    * ``phase="persist"`` — the ``passes=()`` persistence pipeline at the
      default repack ladder's middle rung (the shape class the reduced
      ego-regime graphs re-bucket into) — the phase the refactor shrinks.
    """
    from repro.configs.tda_ego import config as tda_config
    from repro.core.api import topological_signature_sharded
    from repro.core.graph import GraphBatch
    from repro.core.reduction import reduce_fixpoint
    from repro.core.repack import compact_batch, default_ladder, measure_counts
    from jax.experimental.shard_map import shard_map

    tcfg = tda_config()
    n_dev = mesh.devices.size
    b = tcfg.graphs_per_device * n_dev
    all_axes = tuple(mesh.axis_names)
    gshard = NamedSharding(mesh, P(all_axes))
    ladder = default_ladder(tcfg.n_pad, tcfg.edge_cap, tcfg.tri_cap)
    mid = ladder[len(ladder) // 2]

    def g_abs(n_pad):
        return GraphBatch(
            adj=jax.ShapeDtypeStruct((b, n_pad, n_pad), jnp.bool_, sharding=gshard),
            mask=jax.ShapeDtypeStruct((b, n_pad), jnp.bool_, sharding=gshard),
            f=jax.ShapeDtypeStruct((b, n_pad), jnp.float32, sharding=gshard),
        )

    if phase == "reduce":
        spec = P(all_axes)

        def per_device(adj, mask, f):
            g = GraphBatch(adj=adj, mask=mask, f=f)
            gr = reduce_fixpoint(g, ("prunit", "kcore"), tcfg.max_dim,
                                 tcfg.sublevel)
            gc, _ = compact_batch(gr)
            nv, ne, nt = measure_counts(gc)
            return gc.adj, gc.mask, gc.f, nv, ne, nt

        sharded = shard_map(
            per_device, mesh=mesh,
            in_specs=(spec, spec, spec),
            out_specs=(spec,) * 6,
            check_rep=False,
        )

        def reduce_step(g):
            return sharded(g.adj, g.mask, g.f)

        return reduce_step, (g_abs(tcfg.n_pad),)

    if phase == "persist":
        def persist_step(g):
            d = topological_signature_sharded(
                g, mesh, dim=tcfg.max_dim, method="none",
                sublevel=tcfg.sublevel, edge_cap=mid.edge_cap,
                tri_cap=mid.tri_cap,
            )
            return d.birth, d.death, d.dim, d.valid

        return persist_step, (g_abs(mid.n_pad),)

    raise ValueError(f"phase must be 'reduce' or 'persist', got {phase!r}")


def _depth_period(cfg) -> int:
    """Layer-count granularity at which the block pattern repeats exactly."""
    if cfg.family == "hybrid":
        return cfg.attn_period
    if cfg.local_global_pattern != (0, 0):
        return sum(cfg.local_global_pattern)
    return 1


def _probe_config(cfg, n_layers: int):
    reps = {"n_layers": n_layers}
    if cfg.family == "encdec":
        # encoder depth scales with decoder depth (whisper: 6 == 6)
        reps["n_enc_layers"] = max(1, round(cfg.n_enc_layers * n_layers
                                            / cfg.n_layers))
    return dataclasses.replace(cfg, **reps)


def _lower_cost(arch, shape, mesh, cfg):
    """(flops, bytes, collectives) of one unrolled micro lowering."""
    spec, skip = input_specs(arch, shape, mesh, micro=True, cfg=cfg)
    if skip:
        return None
    step, args = spec
    with mesh:
        lowered = jax.jit(step).lower(*args)
        compiled = lowered.compile()
    cost = compiled.cost_analysis()
    text = compiled.as_text()
    colls = rl.parse_collectives(text)
    return (float(cost.get("flops", 0.0)),
            float(cost.get("bytes accessed", 0.0)), colls,
            rl.fusion_adjusted_bytes(text))


def _extrapolate(c1, c2, l1, l2, L):
    """Linear depth extrapolation of (flops, bytes, collectives)."""
    slope = (L - l1) / (l2 - l1)

    def lin(a, b):
        return a + slope * (b - a)

    flops = lin(c1[0], c2[0])
    bts = lin(c1[1], c2[1])
    kinds = set(c1[2]) | set(c2[2])
    zero = {"count": 0, "bytes": 0.0, "traffic": 0.0}
    colls = {
        k: {f: lin(c1[2].get(k, zero)[f], c2[2].get(k, zero)[f])
            for f in ("count", "bytes", "traffic")}
        for k in kinds
    }
    return flops, bts, colls


def run_cell_xcost(arch: str, shape: str, multi_pod: bool) -> dict:
    """Cost-exact roofline terms via unrolled depth-probe extrapolation.

    XLA counts while/scan bodies once, so the full-depth scanned lowering
    under-reports FLOPs/bytes/collectives by ~n_layers.  Fully unrolling the
    real depth is compile-prohibitive, but cost is linear in depth for a
    homogeneous (periodic) stack: lower unrolled probes at 1 and 2 pattern
    periods and extrapolate to the real depth.  Train cells are lowered as
    one grad-accum microbatch (terms per microbatch; a full step is exactly
    grad_accum x this plus one grad-reduce + optimizer epilogue, already
    present once in the probe).
    """
    from repro.models.pjit_utils import set_axis_env
    from repro.models.unroll import set_unroll

    if arch == "tda_ego":
        # no layer stack; data-dependent while loops handled analytically
        # in EXPERIMENTS.md — the compiled numbers are the once-through
        # lower bound.
        return run_cell(arch, shape, multi_pod, unroll=False)

    set_unroll(True)
    mesh = make_production_mesh(multi_pod=multi_pod)
    set_axis_env(dp=tuple(a for a in ("pod", "data") if a in mesh.axis_names))
    chips = mesh.devices.size
    cfg = get_config(arch)
    sc = SHAPES[shape]
    if sc.kind == "train" and sc.grad_accum > 1:
        sc = dataclasses.replace(
            sc, global_batch=sc.global_batch // sc.grad_accum, grad_accum=1)
    per = _depth_period(cfg)
    l1, l2 = per, 2 * per

    t0 = time.time()
    c1 = _lower_cost(arch, shape, mesh, _probe_config(cfg, l1))
    if c1 is None:
        return {"arch": arch, "shape": shape, "multi_pod": multi_pod,
                "skipped": f"{arch}/{shape} skipped (see baseline cell)"}
    c2 = _lower_cost(arch, shape, mesh, _probe_config(cfg, l2))
    t_compile = time.time() - t0

    flops, bts, colls = _extrapolate(c1[:3], c2[:3], l1, l2, cfg.n_layers)
    mf = rl.model_flops_for(cfg, sc)
    terms = rl.roofline_terms(flops, bts, colls, mf, chips)
    # fusion-adjusted memory term (elementwise chains assumed fused, as on
    # a real TPU pipeline) — extrapolated with the same depth slope
    slope = (cfg.n_layers - l1) / (l2 - l1)
    adj = c1[3] + slope * (c2[3] - c1[3])
    terms["memory_adjusted_s"] = adj / rl.HBM_BW
    terms["hlo_bytes_adjusted_per_device"] = adj
    return {
        "arch": arch, "shape": shape, "multi_pod": multi_pod, "chips": chips,
        "method": "xcost-depth-extrapolation",
        "probe_layers": [l1, l2], "true_layers": cfg.n_layers,
        "grad_accum_lowered": sc.grad_accum,
        "global_batch_lowered": sc.global_batch,
        "compile_s": round(t_compile, 1),
        "probe1": {"flops": c1[0], "bytes": c1[1]},
        "probe2": {"flops": c2[0], "bytes": c2[1]},
        "roofline": terms,
    }


def run_cell(arch: str, shape: str, multi_pod: bool, unroll: bool = False,
             micro: bool = False) -> dict:
    from repro.models.pjit_utils import set_axis_env
    from repro.models.unroll import set_unroll

    set_unroll(unroll)  # cost-exact roofline: count scan bodies x trips
    mesh = make_production_mesh(multi_pod=multi_pod)
    set_axis_env(dp=tuple(a for a in ("pod", "data") if a in mesh.axis_names))
    chips = mesh.devices.size
    t0 = time.time()
    if arch == "tda_ego":
        if shape in ("ego_pd_reduce", "ego_pd_persist"):
            step, args = tda_two_phase_specs(
                mesh, phase=shape.removeprefix("ego_pd_"))
        else:
            step, args = tda_input_specs(mesh)
        cfg = None
        sc = None
    else:
        spec, skip = input_specs(arch, shape, mesh, micro=micro)
        if skip:
            return {"arch": arch, "shape": shape, "multi_pod": multi_pod,
                    "skipped": skip}
        step, args = spec
        cfg = get_config(arch)
        sc = SHAPES[shape]
        if micro and sc.kind == "train" and sc.grad_accum > 1:
            sc = dataclasses.replace(
                sc, global_batch=sc.global_batch // sc.grad_accum, grad_accum=1)

    with mesh:
        lowered = jax.jit(step).lower(*args)
        compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    text = compiled.as_text()
    colls = rl.parse_collectives(text)
    flops = float(cost.get("flops", 0.0))
    bytes_acc = float(cost.get("bytes accessed", 0.0))
    mf = rl.model_flops_for(cfg, sc) if cfg is not None else 0.0
    terms = rl.roofline_terms(flops, bytes_acc, colls, mf, chips)

    out = {
        "arch": arch, "shape": shape, "multi_pod": multi_pod, "chips": chips,
        "unrolled_costs": unroll, "microbatch_costs": micro,
        "grad_accum_lowered": getattr(sc, "grad_accum", None),
        "global_batch_lowered": getattr(sc, "global_batch", None),
        "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
        },
        "roofline": terms,
    }
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--unroll", action="store_true",
                    help="fully unroll structural scans for cost-exact "
                         "roofline terms (slower compiles)")
    ap.add_argument("--micro", action="store_true",
                    help="lower one grad-accum microbatch (use with --unroll)")
    ap.add_argument("--xcost", action="store_true",
                    help="cost-exact roofline via unrolled depth-probe "
                         "extrapolation (cheap; preferred for §Roofline)")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out-dir", default="results/dryrun")
    args = ap.parse_args()

    if args.all:
        os.makedirs(args.out_dir, exist_ok=True)
        cells = [(a, s) for a in ARCHS if a != "tda_ego" for s in SHAPES]
        cells.append(("tda_ego", "ego_pd"))
        # two-phase ReductionEngine cells: reduce vs persist roofline terms
        cells.append(("tda_ego", "ego_pd_reduce"))
        cells.append(("tda_ego", "ego_pd_persist"))
        failures = []
        for arch, shape in cells:
            tag = f"{arch}__{shape}__{'2pod' if args.multipod else '1pod'}"
            out_path = os.path.join(args.out_dir, tag + ".json")
            if os.path.exists(out_path):
                print(f"[skip-cached] {tag}")
                continue
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch, "--shape", shape, "--out-dir", args.out_dir]
            if args.multipod:
                cmd.append("--multipod")
            if args.unroll:
                cmd.append("--unroll")
            if args.micro:
                cmd.append("--micro")
            if args.xcost:
                cmd.append("--xcost")
            r = subprocess.run(cmd, capture_output=True, text=True)
            if r.returncode != 0:
                failures.append(tag)
                print(f"[FAIL] {tag}\n{r.stdout[-2000:]}\n{r.stderr[-2000:]}")
            else:
                print(f"[ok] {tag}")
        print(f"done; {len(failures)} failures: {failures}")
        sys.exit(1 if failures else 0)

    if args.xcost:
        out = run_cell_xcost(args.arch, args.shape, args.multipod)
    else:
        out = run_cell(args.arch, args.shape, args.multipod,
                       unroll=args.unroll, micro=args.micro)
    os.makedirs(args.out_dir, exist_ok=True)
    tag = f"{args.arch}__{args.shape}__{'2pod' if args.multipod else '1pod'}"
    with open(os.path.join(args.out_dir, tag + ".json"), "w") as f:
        json.dump(out, f, indent=2, default=str)
    print(json.dumps(
        {k: out[k] for k in out if k != "roofline"} |
        {"dominant": out.get("roofline", {}).get("dominant"),
         "terms_s": {t: out.get("roofline", {}).get(f"{t}_s")
                     for t in ("compute", "memory", "collective")}},
        indent=2, default=str))


if __name__ == "__main__":
    main()
