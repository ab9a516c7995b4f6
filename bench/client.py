"""The client side of a run: submits, timestamps and collects answers.

One record per request: when it was due, how long ``submit`` took on the
harness clock, when its answer came (``resolved_at`` of the future, on the
same ``perf_counter`` clock) and whether it failed.  Answers are dropped as
soon as they are read, except those of the pool graphs drawn for the
correctness check, so the device holds no more than a run needs.
"""
from __future__ import annotations

import time

import numpy as np


class Client:
    def __init__(self, frontend, stream, bucket_of: list, quota: int,
                 always: set, trace_submit=None):
        self.frontend = frontend
        self.stream = stream
        self.bucket_of = bucket_of         # bucket label per pool index
        self.quota = quota                 # answers kept per bucket
        self.always = always               # pool indices always kept
        self.taken: dict[str, int] = {}
        self.keep: set[int] = set()        # request numbers to keep
        self.t0 = None
        self.trace_submit = trace_submit   # context factory, traced runs
        self.due: list[float] = []
        self.sent: list[float] = []
        self.admit: list[float] = []
        self.pool_index: list[int] = []
        self.done: list[float] = []
        self.ok: list[bool] = []
        self.kept: list[tuple] = []        # (pool index, answer)
        self.errors: list[str] = []
        self.open: list[tuple] = []        # (request number, future)

    def open_window(self, t0: float) -> None:
        """From now on, requests are due in the window and the first
        ``quota`` of each bucket, in the seeded order, are kept for the
        check, with every submission of the ``always`` graphs."""
        self.counters_at_open = self.frontend.counters()
        self.t0 = t0

    def submit(self, due: float) -> None:
        i, g = self.stream.next()
        t = time.perf_counter()
        if self.trace_submit is not None:
            with self.trace_submit():
                fut = self.frontend.submit(g)
        else:
            fut = self.frontend.submit(g)
        k = len(self.due)
        self.admit.append(time.perf_counter() - t)
        self.sent.append(t)
        self.due.append(due)
        self.pool_index.append(i)
        self.done.append(float("nan"))
        self.ok.append(False)
        self.open.append((k, fut))
        if self.t0 is not None:
            b = self.bucket_of[i]
            if i in self.always or self.taken.get(b, 0) < self.quota:
                self.taken[b] = self.taken.get(b, 0) + 1
                self.keep.add(k)

    def _finish(self, k: int, fut) -> None:
        try:
            answer = fut.result(timeout=0)
        except Exception as e:          # a failed request: counted, kept
            self.errors.append(repr(e)[:200])
            self.done[k] = fut.resolved_at or float("nan")
            return
        self.done[k] = fut.resolved_at
        self.ok[k] = True
        if k in self.keep:
            self.kept.append((self.pool_index[k], answer))

    def sweep(self) -> None:
        """Read every answer that has come."""
        still = []
        for k, fut in self.open:
            if fut.done():
                self._finish(k, fut)
            else:
                still.append((k, fut))
        self.open = still

    def wait_all(self, timeout_s: float) -> None:
        """Wait for every open request, at most ``timeout_s`` in all."""
        end = time.perf_counter() + timeout_s
        for k, fut in self.open:
            left = end - time.perf_counter()
            try:
                fut.result(timeout=max(left, 0.0))
            except TimeoutError:
                continue
            except Exception:
                pass
        self.sweep()

    def arrays(self) -> dict:
        return {"due": np.asarray(self.due), "sent": np.asarray(self.sent),
                "admit": np.asarray(self.admit),
                "done": np.asarray(self.done), "ok": np.asarray(self.ok)}
