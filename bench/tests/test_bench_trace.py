"""The trace reduction, on a hand-built timeline and on a small trace
recorded on a TPU v5e (``data/tpu_gemm512.xplane.pb``: a 512^3
``ozimmu_h-auto:df32:fused`` GEMM called back to back for 0.05 s)."""
from __future__ import annotations

from pathlib import Path

import pytest

from bench import trace as T

DATA = Path(__file__).resolve().parent / "data" / "tpu_gemm512.xplane.pb"
KERNEL = 'custom-call(...), custom_call_target="tpu_custom_call"'


def _op(name, s, e, dev=0, kernel=False):
    text = f"%{name} = s32[8] {KERNEL if kernel else 'fusion(...)'}"
    return T.Event(name, s, e, dev, text)


def test_reduction_on_a_hand_built_timeline():
    red = T.Reduced(
        window=(0.0, 100.0), devices=[0],
        ops=[_op("group_gemm.1", 0, 30, kernel=True),
             _op("fusion.2", 20, 40), _op("scale_accum.3", 60, 70,
                                         kernel=True),
             _op("group_gemm.4", 70, 80, kernel=True)],
        modules=[T.Event("jit_impl(1)", 0, 45), T.Event("jit_impl(2)", 50,
                                                        55)],
        spans=[T.Event("bench/traced", 0, 100), T.Event("bench/step", 0, 50),
               T.Event("bench/sample", 40, 50), T.Event("bench/step", 55,
                                                       100)])
    assert red.busy_s() == pytest.approx(60e-9)
    assert red.idle_share() == pytest.approx(0.4)
    assert red.op_seconds(red.kernel_events("group_gemm")) == \
        pytest.approx(40e-9)
    assert red.op_seconds(red.kernel_events("scale_accum")) == \
        pytest.approx(10e-9)
    assert red.kernel_events("fusion") == []        # not a Mosaic kernel
    # the gap 40-60 has its middle at 50, inside the innermost "sample";
    # 80-100 lies in the second step
    assert red.idle_gaps() == [("bench/sample", pytest.approx(20e-9)),
                               ("bench/step", pytest.approx(20e-9))]
    assert [m.name for m in red.modules_running_kernels(red.modules)] == \
        ["jit_impl(1)"]
    bd = red.breakdown()
    assert bd["device_ops"][0] == ["group_gemm", pytest.approx(40e-9)]


@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(DATA))
    return pd, T.reduce_profile(pd)


def test_recorded_trace_busy_idle_and_kernels(recorded):
    pd, red = recorded
    lo, hi = red.window
    spans = [e for p in pd.planes if p.name == "/host:CPU"
             for line in p.lines for e in line.events
             if e.name == "bench/traced"]
    assert (lo, hi) == (spans[0].start_ns,
                        spans[0].start_ns + spans[0].duration_ns)
    ops = [(max(e.start_ns, lo), min(e.start_ns + e.duration_ns, hi),
            e.name) for p in pd.planes if p.name == "/device:TPU:0"
           for line in p.lines if line.name == "XLA Ops"
           for e in line.events
           if e.start_ns + e.duration_ns > lo and e.start_ns < hi]
    # busy: a sweep over the sorted intervals, written apart from
    # trace.union_length
    busy, reach = 0.0, lo
    for s, e, _ in sorted(ops):
        if e > reach:
            busy += e - max(s, reach)
            reach = e
    assert red.busy_s() == pytest.approx(busy * 1e-9, rel=1e-12)
    assert 0 < red.idle_share() < 1
    for kernel in ("group_gemm", "scale_accum", "split_fused"):
        want = sum(e - s for s, e, name in ops
                   if name.startswith(f"%{kernel}.")
                   and "tpu_custom_call" in name)
        assert want > 0
        assert red.op_seconds(red.kernel_events(kernel)) == \
            pytest.approx(want * 1e-9, rel=1e-12)
    # every idle stretch is attributed to a span the harness recorded,
    # and idle plus busy make the window
    gaps = red.idle_gaps()
    assert sum(g for _, g in gaps) == pytest.approx(
        red.window_s() - red.busy_s(), rel=1e-9)
    assert {label for label, _ in gaps} <= {"bench/window", "bench/wait",
                                            "bench/dispatch"}
    # one module execution per GEMM call, each running the kernels
    assert len(red.modules_running_kernels(red.modules)) == \
        len(red.modules) > 0
