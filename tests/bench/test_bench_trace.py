"""The benchmark's trace reduction (bench/trace.py) on synthetic traces."""
from __future__ import annotations

import glob
import os

import pytest

from bench import trace as tr


def _synthetic():
    # device ops (ns): [0,10) [5,20) overlap, [30,40), [70,74); window [0,100)
    dev = [(0, 10, "fusion.1"), (5, 20, "fusion.2"), (30, 40, "while.3"),
           (70, 74, "fusion.1")]
    host = [(20, 28, "bench.submit"), (22, 30, "PjitFunction(f)"),
            (45, 69, "bench.submit"), (80, 99, "TransferToDevice")]
    return {"device": {"/device:TPU:0": dev}, "host": host}


def test_union_counts_overlap_once():
    t = _synthetic()
    assert tr.union_length(t["device"]["/device:TPU:0"]) == 20 + 10 + 4


def test_gaps_longest_first_and_clipped():
    dev = _synthetic()["device"]["/device:TPU:0"]
    assert tr.gaps(dev, 0, 100) == [(40, 70), (74, 100), (20, 30)]
    # a window that starts inside an op leaves no gap before it
    assert tr.gaps(dev, 8, 35) == [(20, 30)]


def test_overlap_by_name_sums_each_host_event():
    host = _synthetic()["host"]
    assert dict(tr.overlap_by_name(host, 20, 30)) == {
        "bench.submit": 8, "PjitFunction(f)": 8}
    assert tr.overlap_by_name(host, 40, 70) == [("bench.submit", 24)]


def test_summarize_busy_idle_ops_and_named_gaps():
    s = tr.summarize(_synthetic(), 0, 100)
    assert s["busy_s"] == pytest.approx(34e-9)
    assert s["window_s"] == pytest.approx(100e-9)
    assert s["idle_share"] == pytest.approx(0.66)
    assert s["device_ops"][0] == ["fusion.2", pytest.approx(15e-9)]
    assert [n for n, _ in s["device_ops"]] == ["fusion.2", "fusion.1",
                                               "while.3"]
    labels = [g[0] for g in s["idle_gaps"]]
    assert labels[0] == "bench.submit"                  # [40, 70)
    assert labels[1] == "TransferToDevice"              # [74, 100)
    assert s["idle_gaps"][0][1] == pytest.approx(30e-9)


def test_summarize_averages_busy_over_devices():
    t = _synthetic()
    t["device"]["/device:TPU:1"] = [(0, 100, "fusion.9")]
    s = tr.summarize(t, 0, 100)
    assert s["busy_s"] == pytest.approx((34e-9 + 100e-9) / 2)


def test_summarize_leaves_out_planes_without_an_op_in_the_window():
    t = _synthetic()
    t["device"]["/device:TPU:0 idle"] = [(200, 300, "fusion.9")]
    t["device"]["/device:A:0"] = []
    s = tr.summarize(t, 0, 100)
    assert s["busy_s"] == pytest.approx(34e-9)
    assert s["device_planes"] == ["/device:TPU:0"]


def test_ops_are_named_without_their_hlo_text():
    t = _synthetic()
    t["device"]["/device:TPU:0"] = [
        (0, 10, "%while.7 = (s32[]) while(s32[] %t), body=%b"),
        (20, 25, "%while.7 = (s32[]) while(s32[] %t), body=%b"),
        (30, 31, "fusion.1")]
    s = tr.summarize(t, 0, 100)
    assert s["device_ops"][0] == ["%while.7", pytest.approx(15e-9)]
    assert s["device_ops"][1][0] == "fusion.1"


def test_summarize_refuses_a_trace_without_device():
    with pytest.raises(ValueError):
        tr.summarize({"device": {}, "host": []}, 0, 10)


def test_read_xplane_finds_host_annotations(tmp_path):
    """A recorded trace: the reader returns the harness's annotations on
    the host plane (the CPU backend writes no device plane)."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x * 2).sum())
    x = jnp.ones((64,))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            with jax.profiler.TraceAnnotation("bench.submit"):
                f(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    assert glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                     recursive=True)
    t = tr.read_xplane(str(tmp_path))
    w0, w1 = tr.window_of(t["host"], "bench.window")
    s0, s1 = tr.window_of(t["host"], "bench.submit")
    assert w0 <= s0 < s1 <= w1
    with pytest.raises(KeyError):
        tr.window_of(t["host"], "no.such.event")
