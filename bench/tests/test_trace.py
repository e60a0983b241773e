"""The trace reduction and the per-layer readers on a trace recorded on one
v5e chip (``bench/tests/record_trace.py``): inside ``bench.window``, one
``jit_stacked`` program under ``bench.datagen``, then three ``jit_step``
programs (two fusions each) under ``bench.dispatch`` with 10 ms host sleeps
between them."""
from __future__ import annotations

import os
from types import SimpleNamespace

import pytest

from bench import trace as tr
from bench.harness import load_module

PATH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                    "testdata", "small.xplane.pb")


@pytest.fixture(scope="module")
def trace():
    return tr.load(PATH)


def test_planes_and_spans(trace):
    assert list(trace.devices) == ["/device:TPU:0"]
    names = [n for _, _, n in trace.spans]
    assert names.count("bench.window") == 1
    assert names.count("bench.dispatch") == 3 and names.count("bench.datagen") == 1
    lo, hi = trace.window()
    assert (hi - lo) / 1e9 == pytest.approx(0.036090707)


def test_busy_is_the_union_of_ops(trace):
    lo, hi = trace.window()
    dev = trace.devices["/device:TPU:0"]
    # the ops of one device never overlap in this trace: their clipped
    # durations add up to the busy time, which is all three jit_step runs
    total = sum(e - s for s, e, _ in tr.clip(dev.ops, lo, hi))
    assert tr.busy_ns(dev, lo, hi) == pytest.approx(total)
    assert tr.busy_ns(dev, lo, hi) / 1e9 == pytest.approx(0.000502464)
    assert tr.module_ns(dev, lo, hi, "jit_step") / 1e9 == pytest.approx(0.00050249)


def test_module_time_depends_on_the_window(trace):
    lo, hi = trace.window()
    dev = trace.devices["/device:TPU:0"]
    # the device clock runs 1 ms ahead of the host span that launched the
    # datagen program, so the window misses it and a wider one holds it
    assert tr.module_ns(dev, lo, hi, "jit_stacked") == 0.0
    assert tr.module_ns(dev, lo - 2e6, hi, "jit_stacked") / 1e9 == pytest.approx(9.8293e-05)


def test_self_time_subtracts_nested_ops():
    ops = [(0, 100, "%while.1 = loop"), (10, 30, "%fusion.2 = a"), (40, 90, "%fusion.3 = b"),
           (50, 60, "%copy.4 = c")]
    got = dict(tr.self_times(ops))
    assert got == {"%while.1 = loop": 30, "%fusion.2 = a": 20, "%fusion.3 = b": 40,
                   "%copy.4 = c": 10}
    assert tr.op_name("%fusion.2 = a") == "%fusion.2"
    text = ("%fusion.7 = (f32[4,8]{1,0:T(4,128)}, bf16[2]{0}) fusion(f32[4,8]{1,0} %p), "
            "kind=kOutput, calls=%fused_computation.7")
    assert tr.op_name(text) == "%fusion.7 = (f32[4,8], bf16[2]) fusion"


def test_top_ops_and_idle_gaps(trace):
    lo, hi = trace.window()
    top = dict(tr.top_ops(trace, lo, hi))
    assert set(top) == {"%fusion = bf16[2048,2048] fusion",
                        "%convolution_tanh_fusion = bf16[2048,2048] fusion",
                        "%copy-start = (bf16[2048,2048], bf16[2048,2048], u32[]) copy-start",
                        "%copy-done = bf16[2048,2048] copy-done"}
    assert sum(top.values()) == pytest.approx(0.000502464)
    gaps = tr.idle_gaps(trace, lo, hi)
    # the three longest gaps are the host sleeping between dispatches
    assert [g[0] for g in gaps[:3]] == ["host.other"] * 3
    assert all(g[1] > 0.01 for g in gaps[:3])
    assert sum(g[1] for g in tr.idle_gaps(trace, lo, hi, n=100)) == pytest.approx(
        (hi - lo) / 1e9 - 0.000502464)


def ctx(trace, **kw):
    lo, hi = trace.window()
    dev = trace.devices["/device:TPU:0"]
    base = dict(trace=trace, trace_window_ns=(lo, hi), trace_window_s=(hi - lo) / 1e9,
                busy_s=tr.busy_ns(dev, lo, hi) / 1e9, window_s=(hi - lo) / 1e9,
                tokens=1000, chips=1, flops_per_token=1e9,
                peak={"bf16_flops_per_s": 197e12})
    base.update(kw)
    return SimpleNamespace(**base)


def test_readers(trace):
    idle = load_module("metrics", "device_idle_frac").read(ctx(trace))
    assert idle == pytest.approx(1 - 0.000502464 / 0.036090707)
    mfu = load_module("metrics", "step_mfu").read(ctx(trace))
    assert mfu == pytest.approx(100 * 1e12 / (0.036090707 * 197e12))
    # no input-pipeline program inside the window: nothing to read
    assert load_module("metrics", "datagen_frac").read(ctx(trace)) is None
    lo, hi = trace.window()
    wide = ctx(trace, trace_window_ns=(lo - 2e6, hi))
    assert load_module("metrics", "datagen_frac").read(wide) == pytest.approx(
        9.8293e-05 / ((hi - lo) / 1e9))


def test_readers_without_a_trace():
    empty = SimpleNamespace(trace=None, window_s=1.0, tokens=0)
    for name in ("device_idle_frac", "datagen_frac", "step_mfu"):
        assert load_module("metrics", name).read(empty) is None
