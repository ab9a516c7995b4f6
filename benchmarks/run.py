"""Benchmark suite entry point: one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--only fig4,table1] [--quick]

Writes results/bench.csv plus a machine-readable ``BENCH_<suite>.json`` per
executed suite (rows + wall time + environment metadata — the cross-PR perf
trajectory), and prints per-row CSV as it goes.  ``--quick`` shrinks each
suite to a CI/CPU smoke size: suites whose ``run`` accepts a ``quick=``
kwarg get it directly; the rest can read ``report.quick``.

Each :class:`Suite` also carries the perf-reference policy PerfGate
(``python -m repro.perfgate check``) applies when diffing a fresh run
against the committed baseline: a tuple of
:class:`repro.perfgate.references.RefSpec` declarations (first ``fnmatch``
over ``"<benchmark>.<metric>"`` wins), with the metric-name classifier in
``repro/perfgate/references.py`` supplying defaults for everything not
declared.  ``quick_invariant=True`` marks suites whose workload sizes do
not change under ``--quick`` — their relative bands gate even when the
fresh run's quick flag differs from the baseline's.
"""
from __future__ import annotations

import argparse
import dataclasses
import inspect
import os
import time
import traceback

from benchmarks.common import (
    Report,
    telemetry_delta,
    telemetry_snapshot,
    write_suite_json,
)
from repro.launch.compile_cache import use_compile_cache
from repro.perfgate.references import RefSpec


@dataclasses.dataclass(frozen=True)
class Suite:
    """One registered benchmark suite + its perf-reference policy."""

    module: str
    description: str
    references: tuple[RefSpec, ...] = ()
    quick_invariant: bool = False


SUITES = {
    "fig4": Suite("benchmarks.fig4_coral_reduction",
                  "CoralTDA vertex reduction (Fig 4)"),
    "fig5a": Suite("benchmarks.fig5_prunit",
                   "PrunIT vertex reduction (Fig 5a)"),
    "fig5b": Suite("benchmarks.fig5b_ego_time",
                   "PrunIT ego-net PD0 time (Fig 5b)"),
    "table1": Suite("benchmarks.table1_large_networks",
                    "PrunIT on large networks (Table 1)"),
    "fig6": Suite("benchmarks.fig6_combined",
                  "PrunIT+CoralTDA combined (Fig 6)"),
    "fig7_9": Suite("benchmarks.fig7_9_secondary",
                    "clique/time/edge reduction (Figs 7-9)"),
    "table3": Suite("benchmarks.table3_strong_collapse",
                    "PrunIT vs Strong Collapse (Table 3)"),
    "fig2": Suite(
        "benchmarks.fig2_clustering",
        "clustering coeff vs higher PDs (Fig 2/10)",
        references=(
            RefSpec("*.kmeans_purity", "higher", rel_band=0.08,
                    note="Fig 10 clustering separation must hold"),
            RefSpec("*.ncc_holdout_accuracy", "higher", rel_band=0.08,
                    note="nearest-class-centroid holdout accuracy"),
        ),
    ),
    "kernels": Suite(
        "benchmarks.kernel_bench",
        "Pallas kernel microbenchmarks",
        quick_invariant=True,  # fixed sizes: quick runs gate too
        references=(
            RefSpec("*_converged_frac", "higher", rel_band=0.02,
                    note="auction must converge on (near) every pair"),
            RefSpec("*_pallas_speedup", "higher", rel_band=0.60,
                    note="speedup ratios compound two timings' jitter"),
        ),
    ),
    "serve": Suite(
        "benchmarks.serve_bench",
        "TopoServe throughput/latency + parity",
        references=(
            RefSpec("*.plan_cache_misses", "info",
                    note="depends on request mix, not perf"),
        ),
    ),
    "stream": Suite(
        "benchmarks.stream_bench",
        "TopoStream updates/s + skip-rate + parity",
        references=(
            RefSpec("*.skip_rate", "higher", rel_band=0.10,
                    note="reduction-certificate hit rate is the win"),
        ),
    ),
    "metrics": Suite(
        "benchmarks.metrics_bench",
        "diagram distances + Gram kernel + parity + drift",
        references=(
            RefSpec("*.recall_at_10", "higher", rel_band=0.03,
                    note="two-stage retrieval quality (CI asserts >= 0.95)"),
            RefSpec("*.rounds_mean", "lower", rel_band=0.30,
                    note="collapsed auction bidding rounds per pair — the "
                         "perf_opt target; 'rounds' is an info token so "
                         "this gate must be explicit"),
            RefSpec("*.rounds_reduction", "higher", rel_band=0.30,
                    note="expanded/collapsed rounds ratio (>= 5x asserted "
                         "in-bench)"),
            RefSpec("*.warm_hit_rate", "higher", rel_band=0.10,
                    note="price-cache warm-start hit rate on repeated "
                         "stage1 exact drains"),
            RefSpec("*_bytes*", "lower", rel_band=0.0,
                    note="analytic working-set sizes; any growth is an "
                         "algorithmic change, not jitter"),
            RefSpec("*.speedup_vs_exhaustive", "higher", rel_band=0.60,
                    note="two-stage vs exhaustive ratio"),
        ),
    ),
    "index": Suite(
        "benchmarks.index_bench",
        "ShardedIndex scaling + Hamming kernel + retrieval recall",
        references=(
            RefSpec("*.scan_throughput", "higher", rel_band=0.60,
                    note="critical-path coarse-scan rate; host timing "
                         "jitter compounds with interpret-mode overhead"),
            RefSpec("*.recall_at_10", "higher", rel_band=0.02,
                    note="sharded two-stage retrieval quality "
                         "(in-bench assert >= 0.98)"),
            RefSpec("*.merge_seconds", "lower", rel_band=0.60,
                    note="host merge of per-shard top-m survivors — the "
                         "only serial stage of the sharded scan"),
            RefSpec("*.kernel_speedup", "higher", rel_band=0.60,
                    note="Pallas-vs-host ratio compounds two timings"),
            RefSpec("*_scan_speedup", "higher", rel_band=0.30,
                    note="4-shard critical-path scaling (>= 3x asserted "
                         "in-bench on a >= 4-device mesh)"),
        ),
    ),
    "reduction": Suite(
        "benchmarks.reduction_bench",
        "ReductionEngine two-phase repack win + reduction ratio + parity",
        references=(
            RefSpec("*_reduction_pct", "higher", rel_band=0.05,
                    note="paper's reduction ratios are structural, "
                         "not timing-jittery"),
            RefSpec("*.persist_speedup", "higher", rel_band=0.60),
            RefSpec("*.total_speedup", "higher", rel_band=0.60),
        ),
    ),
}


def _call_suite(mod, report: Report, quick: bool) -> None:
    """Invoke ``mod.run`` threading --quick through to suites that take it."""
    if "quick" in inspect.signature(mod.run).parameters:
        mod.run(report, quick=quick)
    else:
        mod.run(report)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated suite keys (default: all)")
    ap.add_argument("--quick", action="store_true",
                    help="small suite sizes (CI / CPU smoke)")
    ap.add_argument("--out", default="results/bench.csv")
    args = ap.parse_args()
    use_compile_cache()

    keys = args.only.split(",") if args.only else list(SUITES)
    unknown = [k for k in keys if k not in SUITES]
    if unknown:
        raise SystemExit(f"unknown suites {unknown}; known: {list(SUITES)}")
    out_dir = os.path.dirname(args.out) or "."
    report = Report(quick=args.quick)
    failures = []
    for k in keys:
        suite = SUITES[k]
        print(f"[bench] {k}: {suite.description}", flush=True)
        row_start = len(report.rows)
        t0 = time.time()
        tele0 = telemetry_snapshot()
        ok = True
        try:
            mod = __import__(suite.module, fromlist=["run"])
            _call_suite(mod, report, args.quick)
            print(f"[bench] {k} done in {time.time()-t0:.1f}s", flush=True)
        except Exception:
            failures.append(k)
            ok = False
            traceback.print_exc()
        # TopoScope telemetry block: registry movement attributable to this
        # suite (plan-cache traffic, kernel/metric call counts) — stamped as
        # rows too, so PerfGate baselines track call-count regressions
        telemetry = telemetry_delta(tele0)
        for metric, value in sorted(telemetry.items()):
            report.add("telemetry", metric, value)
        write_suite_json(out_dir, k, suite.description,
                         report.rows[row_start:],
                         wall_s=time.time() - t0, quick=args.quick, ok=ok,
                         telemetry=telemetry)
    os.makedirs(out_dir, exist_ok=True)
    with open(args.out, "w") as f:
        f.write(report.csv() + "\n")
    print(f"\nwrote {args.out} ({len(report.rows)} rows) "
          f"+ BENCH_<suite>.json per suite")
    if failures:
        raise SystemExit(f"failed suites: {failures}")


if __name__ == "__main__":
    main()
