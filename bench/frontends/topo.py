"""TopoServe as the system under test.

The server runs its own loop (``serve_forever``) on a thread; the client
calls ``submit`` and reads each future.  Everything here goes through the
program's public surface.
"""
from __future__ import annotations

import threading

import numpy as np

from bench.pool import Graph

# settings a deployment makes; everything else stays at the program default
SERVING_KEYS = ("dim", "method", "sublevel", "max_batch", "pad_batch_to")
WARM_TIMEOUT_S = 900.0   # a cold compile of the largest bucket fits


class Frontend:
    def __init__(self, serving: dict):
        from repro.serve import TopoServe, TopoServeConfig

        unknown = set(serving) - set(SERVING_KEYS) - {"buckets"}
        if unknown:
            raise ValueError(f"unknown serving settings {sorted(unknown)}")
        kw = {k: serving[k] for k in SERVING_KEYS if k in serving}
        if "buckets" in serving:      # small ladders for the harness tests
            from repro.serve.topo_serve import Bucket
            kw["buckets"] = tuple(Bucket(*b) for b in serving["buckets"])
        self.server = TopoServe(TopoServeConfig(**kw))
        self._thread = None

    # -------------------------------------------------------------- routing
    def bucket_of(self, g: Graph) -> str:
        b = self.server.bucket_for(g.n, len(g.edges), g.triangles)
        return f"n{b.n_pad}"

    # --------------------------------------------------------------- set-up
    def warm(self, graphs_by_bucket: dict) -> None:
        """Every batch the traffic can form, through the server's own
        ``submit`` and ``drain``: in each bucket it uses, one batch of each
        fill from 1 to ``max_batch`` (a partly filled batch is padded by
        programs of its own shape)."""
        cap = self.server.config.max_batch
        for label, graphs in sorted(graphs_by_bucket.items()):
            for fill in range(1, cap + 1):
                futs = [self.submit(graphs[j % len(graphs)])
                        for j in range(fill)]
                self.server.drain()
                for f in futs:
                    f.result(timeout=WARM_TIMEOUT_S)

    # ------------------------------------------------------------- serving
    def start(self) -> None:
        self._thread = threading.Thread(target=self.server.serve_forever,
                                        name="serve_forever", daemon=True)
        self._thread.start()

    def submit(self, g: Graph):
        return self.server.submit(g.edges.tolist(), g.n, g.f.tolist())

    def stop(self) -> None:
        self.server.stop()
        if self._thread is not None:
            self._thread.join(timeout=120)
            if self._thread.is_alive():
                raise RuntimeError("serve_forever did not stop")

    # ------------------------------------------------------------- reading
    def pending(self) -> int:
        """Requests queued and not yet picked up by a drain."""
        return self.server.pending()

    def counters(self) -> dict:
        s = self.server.stats
        return {"served": s["served"], "batches": s["batches"],
                "padded_rows": s["padded_rows"], "failed": s["failed"]}

    def diagram(self, result, dims) -> list:
        """Sorted (dimension, birth, death) of the pairs in ``dims``."""
        b, e, k, v = (np.asarray(x) for x in (result.birth, result.death,
                                              result.dim, result.valid))
        sel = v & np.isin(k, list(dims))
        return sorted(zip(k[sel].tolist(), b[sel].tolist(), e[sel].tolist()))
