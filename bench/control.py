"""The control of a cell's check: the reference in the program's place, in
bfloat16.

    python bench/control.py --workload <cell> --seeds 1 2 3 [--seconds S]

Each seed is a whole run of the cell (``bench/run.py::run_cell``: its pool,
traffic, window, sample and comparison), with the configuration's frontend
replaced by :class:`ReferenceFrontend`: every request is answered at once,
by the plain reference with ``f`` rounded to bfloat16, the precision below
the configured float32.  The harness's own check then has to read the run
as not correct.  One JSON line per seed; the exit code is 0 only when every
seed reads ``correct`` false.  ``--seconds`` defaults to the benchmark's
``run_seconds``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from ml_dtypes import bfloat16  # noqa: E402

from bench import reference, run  # noqa: E402
from bench.registry import Registry  # noqa: E402


class _Answered:
    """A request answered when it was submitted; its answer is the graph."""

    def __init__(self, g):
        self.graph = g
        self.resolved_at = time.perf_counter()

    def done(self) -> bool:
        return True

    def result(self, timeout=None):
        return self.graph


class ReferenceFrontend:
    """The reference in bfloat16 where the system under test would be; it
    routes as the configuration's frontend does."""

    def __init__(self, routing):
        self.bucket_of = routing.bucket_of

    def warm(self, graphs_by_bucket: dict) -> None:
        pass

    def start(self) -> None:
        pass

    def stop(self) -> None:
        pass

    def submit(self, g):
        return _Answered(g)

    def pending(self) -> int:
        return 0

    def counters(self) -> dict:
        return {"served": 0, "batches": 0, "padded_rows": 0, "failed": 0}

    def diagram(self, g, dims) -> list:
        return reference.diagram(g.n, g.edges, g.f, dims, dtype=bfloat16)


def readings(reg: Registry, cell_name: str, seeds, seconds: float) -> list:
    cfg = reg.cell(cell_name).config
    out = []
    for seed in seeds:
        fe = ReferenceFrontend(
            reg.frontend(cfg["frontend"]).Frontend(cfg["serving"]))
        res = run.run_cell(reg, cell_name, seed, seconds, traced=False,
                           t_start=time.perf_counter(), frontend=fe)
        out.append({"seed": seed, "correct": res["correct"],
                    "checks": res["checks"]})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float)
    args = ap.parse_args(argv)
    reg = Registry(ROOT)
    seconds = args.seconds or float(reg.spec["run_seconds"])
    rows = readings(reg, args.workload, args.seeds, seconds)
    for r in rows:
        print(json.dumps(r), flush=True)
    return 0 if not any(r["correct"] for r in rows) else 1


if __name__ == "__main__":
    sys.exit(main())
