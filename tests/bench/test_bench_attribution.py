"""Attribution by the program's own names (bench/attribution.py) on
synthetic traces and records, and the queue wait on a served run."""
from __future__ import annotations

import types

import numpy as np
import pytest

from bench import attribution as at

HLO = """HloModule jit_topo_plan_prunit_e64_t96_d1, is_scheduled=true

%body.1 (p: s32[]) -> s32[] {
  %fusion.4 = s32[] fusion(s32[] %p), kind=kLoop, calls=%f
  ROOT %fusion.5 = s32[] fusion(s32[] %fusion.4), kind=kLoop, calls=%g, metadata={op_type="add" op_name="jit(topo_plan_prunit_e64_t96_d1)/plan.persist/while/body/add" source_file="x.py" source_line=3}
}

ENTRY %main.9 (a: pred[4,16,16]) -> s32[] {
  %copy.1 = pred[4,16,16]{1,2,0} copy(pred[4,16,16]{0,2,1} %a)
  %fusion.2 = s32[] fusion(pred[4,16,16]{1,2,0} %copy.1), kind=kLoop, metadata={op_type="and" op_name="jit(topo_plan_prunit_e64_t96_d1)/plan.reduce/jit(prunit_mask)/and"}
  ROOT %while.3 = s32[] while(s32[] %fusion.2), body=%body.1, metadata={op_type="while" op_name="jit(topo_plan_prunit_e64_t96_d1)/plan.persist/while"}
}
"""
MODULE = "jit_topo_plan_prunit_e64_t96_d1"


def _trace():
    """One plan execution [0, 50) and one eager program [60, 70).  In the
    plan: a layout copy (no scope), a reduce op, a persist ``while`` whose
    body ops nest inside it (one of them without a scope), and another copy
    after it."""
    modules = [(0, 50, MODULE + "(1778)"), (60, 70, "jit_squeeze(42)")]
    ops = [(0, 2, "%copy.1 = pred[4,16,16] copy(%a)"),
           (2, 10, "%fusion.2 = s32[] fusion(%copy.1)"),
           (10, 40, "%while.3 = s32[] while(%fusion.2)"),
           (12, 20, "%fusion.4 = s32[] fusion(%p)"),
           (22, 30, "%fusion.5 = s32[] fusion(%fusion.4)"),
           (40, 45, "%copy.1 = pred[4,16,16] copy(%a)"),
           (60, 64, "%squeeze.1 = f32[8] reshape(%x)")]
    return modules, ops


def test_op_scopes_reads_op_name_metadata():
    assert at.op_scopes(HLO) == {"%fusion.2": "plan.reduce",
                                 "%while.3": "plan.persist",
                                 "%fusion.5": "plan.persist"}


def test_module_name_drops_the_fingerprint():
    assert at.module_name(MODULE + "(17786820674329444357)") == MODULE


@pytest.mark.parametrize("t0, t1, want", [
    # nested ops count once; the unscoped copies are the plan's rest
    (0, 100, {"plan.reduce": 8, "plan.persist": 30, "unscoped": 7,
              "other": 4}),
    # a window that cuts the reduce op and the while
    (5, 30, {"plan.reduce": 5, "plan.persist": 20, "unscoped": 0,
             "other": 0}),
])
def test_phase_split_adds_up_to_busy_time(t0, t1, want):
    modules, ops = _trace()
    split = at.phase_split(modules, ops, {MODULE: at.op_scopes(HLO)}, t0, t1)
    assert split == want
    from bench.trace import clip, union_length
    assert sum(split.values()) == union_length(clip(ops, t0, t1))


def test_phase_split_of_an_unnamed_program_is_other():
    modules, ops = _trace()
    split = at.phase_split(modules, ops, {}, 0, 100)
    assert split == {"plan.reduce": 0, "plan.persist": 0, "unscoped": 0,
                     "other": 49}


def test_executions_count_by_module():
    modules, _ = _trace()
    modules.append((80, 90, MODULE + "(1778)"))
    assert at.executions(modules, 0, 100) == {MODULE: 2, "jit_squeeze": 1}
    assert at.executions(modules, 55, 100) == {MODULE: 1, "jit_squeeze": 1}


def test_idle_in_batches_is_exact_on_a_built_case():
    busy = [(0, 10, "a"), (20, 30, "b"), (25, 28, "nested")]
    batches = [(5, 25, "serve.batch"), (35, 50, "serve.batch")]
    # idle [10, 20) lies in the first batch, [35, 40) in the second
    assert at.idle_in(busy, batches, 0, 40) == 15


def test_idle_in_batches_never_exceeds_idle_time():
    rng = np.random.default_rng(7)
    for _ in range(50):
        def intervals(k):
            s = rng.integers(0, 100, k)
            return [(int(a), int(a + d), "x")
                    for a, d in zip(s, rng.integers(1, 20, k))]
        busy, spans = intervals(8), intervals(5)
        t0, t1 = 10, 90
        grid = np.arange(t0, t1) + 0.5
        cover = lambda iv: np.any(  # noqa: E731
            [(grid >= s) & (grid < e) for s, e, _ in iv], axis=0)
        want = int(np.sum(~cover(busy) & cover(spans)))
        got = at.idle_in(busy, spans, t0, t1)
        assert got == want
        assert got <= int(np.sum(~cover(busy)))


def _records(due, sent, admit, done, ok):
    return {k: np.asarray(v, dtype=bool if k == "ok" else float)
            for k, v in dict(due=due, sent=sent, admit=admit, done=done,
                             ok=ok).items()}


def test_queue_waits_on_synthetic_records():
    # batches [1.0, 1.5) and [2.0, 2.2); window [0.5, 3.0)
    batches = [(2.0, 2.2), (1.0, 1.5)]
    r = _records(due=[0.6, 0.7, 0.4, 0.8, 0.9, 3.5],
                 sent=[0.6, 0.7, 0.4, 0.8, 0.9, 3.5],
                 admit=[0.1, 0.1, 0.1, 0.1, 0.1, 0.1],
                 done=[1.4, 2.1, 1.4, np.nan, 1.7, 3.9],
                 ok=[True, True, True, False, True, True])
    # 0.4 is due before the window, 0.8 failed, 0.9 was answered outside
    # every batch and 3.5 is due after the window
    np.testing.assert_allclose(at.queue_waits(r, batches, 0.5, 3.0),
                               [1.0 - 0.7, 2.0 - 0.8])
    assert len(at.queue_waits(r, [], 0.5, 3.0)) == 0


def test_queue_wait_metric_reads_the_program_stamp():
    """On a served run the reader's wait (submit's end to the start of the
    answering ``serve.batch``) agrees with each future's own pickup stamp,
    ``picked_at - submitted_at``."""
    import time

    import networkx as nx

    from repro import obs
    from repro.serve import TopoServe, TopoServeConfig

    srv = TopoServe(TopoServeConfig(method="prunit", max_batch=3))
    obs.configure(enabled=True)
    obs.clear_trace()
    try:
        t0 = time.perf_counter()
        sent, admit, futs = [], [], []
        for k in range(7):
            g = nx.cycle_graph(4 + k)
            t = time.perf_counter()
            futs.append(srv.submit(list(g.edges()), g.number_of_nodes()))
            sent.append(t)
            admit.append(time.perf_counter() - t)
        srv.drain()
        for f in futs:
            f.result()
        t1 = time.perf_counter()
        run = types.SimpleNamespace(
            t0=t0, t1=t1, spans=[],
            records=_records(due=sent, sent=sent, admit=admit,
                             done=[f.resolved_at for f in futs],
                             ok=[True] * len(futs)))
        got = at.queue_wait_p95_ms(run)
        exact = np.array([f.picked_at - f.submitted_at for f in futs])
    finally:
        obs.configure(enabled=False)
        obs.clear_trace()
    assert got == pytest.approx(np.percentile(exact, 95) * 1e3, abs=5.0)
    assert at.queue_wait_p95_ms(types.SimpleNamespace(spans=None)) is None


def test_read_modules_of_a_trace_without_the_plane(tmp_path):
    import jax
    import jax.numpy as jnp

    with pytest.raises(FileNotFoundError):
        at.read_modules(str(tmp_path))
    jax.profiler.start_trace(str(tmp_path))
    try:
        jnp.ones(8).sum().block_until_ready()
    finally:
        jax.profiler.stop_trace()
    assert at.read_modules(str(tmp_path)) == {"modules": [], "ops": []}
