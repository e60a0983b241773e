"""The reading of the program's scopes and host spans (``bench/scopes.py``)
on traces recorded on one v5e chip, and pins that show the benchmark's own
readings of the same traces unchanged beside it.

* ``scopes.xplane.pb`` (``bench/tests/record_scopes_trace.py``): rounds 1
  and 2 of a reduced smollm-135m MuLoCo engine (K=2, H=2, eval in the
  program), one per dispatch of ``run_rounds``, inside ``bench.window``;
* ``small.xplane.pb`` (``test_trace.py``) and ``kernels.xplane.pb``
  (``test_kernels.py``), recorded before the program had scopes or spans.
"""
from __future__ import annotations

import os
from types import SimpleNamespace

import pytest

from bench import scopes
from bench import trace as tr
from bench.harness import load_module

DATA = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "testdata")
SCOPE_METRICS = {"fwd_bwd_frac": "repro.fwd_bwd", "inner_opt_frac": "repro.inner_opt",
                 "outer_sync_frac": "repro.outer_sync", "eval_frac": "repro.eval"}
LAYERS = ("repro.fwd_bwd", "repro.inner_opt", "repro.outer_sync", "repro.eval",
          "repro.datagen")


def ctx_of(trace) -> SimpleNamespace:
    lo, hi = trace.window()
    busy = [tr.busy_ns(d, lo, hi) / 1e9 for d in trace.devices.values()]
    return SimpleNamespace(trace=trace, trace_window_ns=(lo, hi),
                           trace_window_s=(hi - lo) / 1e9, busy_s=sum(busy) / len(busy))


# the readings of bench/trace.py and of the benchmark's metrics on the two
# older traces, as they were before bench/scopes.py existed
PINNED = {
    "small": {
        "device_idle_frac": 0.9860777457199716, "datagen_frac": None,
        "top_ops": [["%fusion = bf16[2048,2048] fusion", 0.000272616],
                    ["%convolution_tanh_fusion = bf16[2048,2048] fusion", 0.000229816],
                    ["%copy-start = (bf16[2048,2048], bf16[2048,2048], u32[]) copy-start",
                     2.6e-08],
                    ["%copy-done = bf16[2048,2048] copy-done", 6e-09]],
        "idle_gaps": [["host.other", 0.012783948], ["host.other", 0.011511938],
                      ["host.other", 0.011292347], ["bench.datagen", 2e-09],
                      ["host.other", 2e-09], ["host.other", 2e-09], ["host.other", 2e-09],
                      ["host.other", 1e-09], ["host.other", 1e-09]],
    },
    "kernels": {
        "device_idle_frac": 0.9971731433798617, "datagen_frac": None,
        "top_ops": [["%_quantize_rowwise_jit.1 = (f32[256,576], u8[256,576]) custom-call",
                     2.063e-06],
                    ["%copy.7 = f32[256,576] copy", 1.413e-06],
                    ["%fusion = (f32[256], f32[256]) fusion", 1.253e-06],
                    ["%_dequantize_rowwise_jit.1 = f32[256,576] custom-call", 8.51e-07],
                    ["%copy.4 = f32[256,576] copy", 4.48e-07],
                    ["%copy.5 = f32[256,1] copy", 2.96e-07],
                    ["%copy.2 = f32[256,1] copy", 2.91e-07],
                    ["%compare_select_fusion = f32[256,1] fusion", 2.8e-08],
                    ["%copy-done = f32[256,576] copy-done", 2.6e-08],
                    ["%copy-start = (f32[256,576], f32[256,576], u32[]) copy-start", 6e-09]],
        "idle_gaps": [["host.other", 0.001912881], ["host.other", 0.000441713],
                      ["host.other", 2e-09], ["host.other", 2e-09], ["host.other", 2e-09],
                      ["host.other", 2e-09], ["host.other", 1e-09], ["host.other", 1e-09],
                      ["host.other", 1e-09]],
    },
}


@pytest.mark.parametrize("name", sorted(PINNED))
def test_older_readings_unchanged(name):
    path = os.path.join(DATA, f"{name}.xplane.pb")
    trace, scoped = tr.load(path), scopes.load(path)
    pins = PINNED[name]
    for t in (trace, scoped):
        ctx = ctx_of(t)
        lo, hi = ctx.trace_window_ns
        for metric in ("device_idle_frac", "datagen_frac"):
            assert load_module("metrics", metric).read(ctx) == pins[metric]
        assert tr.top_ops(t, lo, hi) == pins["top_ops"]
        assert tr.idle_gaps(t, lo, hi) == pins["idle_gaps"]
        # neither trace holds a scope or a repro. span: the new readers find
        # nothing, and name the gaps as bench/trace.py does
        for metric in SCOPE_METRICS:
            assert load_module("metrics", metric).read(ctx) is None
    assert scoped.program_spans == []
    lo, hi = scoped.window()
    assert scopes.idle_gaps(scoped, lo, hi) == pins["idle_gaps"]


def test_metadata_reader_finds_tf_op():
    ops = scopes.tf_ops(os.path.join(DATA, "small.xplane.pb"))
    fusions = {tr.op_name(k): v for k, v in ops.items()}
    assert fusions["%convolution_tanh_fusion = bf16[2048,2048] fusion"] == \
        "jit(step)/dot_general:"
    assert fusions["%fusion = bf16[2048,2048] fusion"] == "jit(step)/dot_general:"
    kernels = scopes.tf_ops(os.path.join(DATA, "kernels.xplane.pb"))
    assert any(v.startswith("jit(wire)/jit(_quantize_rowwise_jit)/pallas_call")
               for v in kernels.values())


def test_gap_named_by_bench_span_then_innermost_program_span():
    ops = [(0, 10, "%a"), (20, 30, "%b"), (50, 60, "%c"), (90, 100, "%d")]
    trace = scopes.ScopedTrace(
        devices={"/device:TPU:0": tr.Device(ops=ops, modules=[])},
        spans=[(0, 100, "bench.window"), (10, 20, "bench.dispatch")],
        tf_ops={}, program_spans=[(0, 100, "repro.run_rounds"), (5, 25, "repro.dispatch"),
                                  (30, 50, "repro.drain")])
    got = scopes.idle_gaps(trace, 0, 100)
    # 60-90: only the whole call covers it; 30-50: the drain; 10-20: the
    # bench. span wins over the tighter-fitting program span
    assert got == [["repro.run_rounds", 30e-9], ["repro.drain", 20e-9],
                   ["bench.dispatch", 10e-9]]
    bare = tr.Trace(devices=trace.devices, spans=trace.spans)
    assert [g[1] for g in tr.idle_gaps(bare, 0, 100)] == [g[1] for g in got]


def test_scope_split_counts_self_time_once():
    ops = [(0, 100, "%while"), (10, 30, "%f1"), (40, 90, "%f2"), (95, 100, "%f3")]
    trace = scopes.ScopedTrace(
        devices={"/device:TPU:0": tr.Device(ops=ops, modules=[])}, spans=[],
        tf_ops={"%f1": "jit(s)/while/body/repro.fwd_bwd/dot_general:",
                "%f2": "jit(s)/while/body/repro.inner_opt/repro.newton_schulz/dot:",
                "%while": "jit(s)/while:"}, program_spans=[])
    split = scopes.scope_split(trace, 0, 100, ["repro.fwd_bwd", "repro.inner_opt"])
    assert split == {"repro.fwd_bwd": 20.0, "repro.inner_opt": 50.0, scopes.UNSCOPED: 30.0}
    assert scopes.scope_ns(trace, 0, 100, "repro.newton_schulz") == 50.0
    assert scopes.top_unscoped(trace, 0, 100, ["repro."]) == [
        ["%while", 25e-9, "jit(s)/while:"], ["%f3", 5e-9, ""]]


def test_operation_without_tf_op_takes_the_scope_around_it():
    fwd, opt = "jit(s)/repro.fwd_bwd/dot:", "jit(s)/repro.inner_opt/mul:"
    ops = [(0, 10, "%f1"), (10, 12, "%copy1"), (12, 20, "%f2"), (20, 23, "%copy2"),
           (23, 30, "%o1"), (30, 34, "%copy3")]
    trace = scopes.ScopedTrace(
        devices={"/device:TPU:0": tr.Device(ops=ops, modules=[])}, spans=[],
        tf_ops={"%f1": fwd, "%f2": fwd, "%o1": opt}, program_spans=[])
    layers = ["repro.fwd_bwd", "repro.inner_opt"]
    # the copy between two forward/backward operations is theirs; the one
    # between two scopes, and the last one, belong to none
    assert scopes.scope_split(trace, 0, 40, layers) == {
        "repro.fwd_bwd": 20.0, "repro.inner_opt": 7.0, scopes.UNSCOPED: 7.0}
    assert scopes.scope_split(trace, 0, 40, layers, infer=False) == {
        "repro.fwd_bwd": 18.0, "repro.inner_opt": 7.0, scopes.UNSCOPED: 9.0}
    assert scopes.scope_ns(trace, 0, 40, "repro.fwd_bwd") == 20.0


def test_copy_without_hlo_reads_the_same(tmp_path):
    src = os.path.join(DATA, "small.xplane.pb")
    scopes.save_without_hlo(src, tmp_path / "less.pb")
    assert 0 < os.path.getsize(tmp_path / "less.pb") < os.path.getsize(src)
    a, b = scopes.load(src), scopes.load(str(tmp_path / "less.pb"))
    assert (a.devices, a.spans, a.tf_ops) == (b.devices, b.spans, b.tf_ops)


@pytest.fixture(scope="module")
def scoped():
    return scopes.load(os.path.join(DATA, "scopes.xplane.pb"))


def test_scoped_trace_maps_device_ops_to_scopes(scoped):
    from repro import tracing

    lo, hi = scoped.window()
    ops = [o for d in scoped.devices.values() for o in tr.clip(d.ops, lo, hi)]
    assert sum(name in scoped.tf_ops for _, _, name in ops) > 0.9 * len(ops)
    paths = set(scoped.tf_ops.values())
    # the deltas fuse into the reduce, so no device operation is named after them
    for scope in set(tracing.DEVICE_SCOPES) - {tracing.PSEUDOGRAD}:
        assert any(scope in p for p in paths), scope
    assert set(LAYERS) < set(tracing.DEVICE_SCOPES)
    assert set(SCOPE_METRICS.values()) < set(LAYERS)


def test_scope_readers_give_shares(scoped):
    ctx = ctx_of(scoped)
    got = {m: load_module("metrics", m).read(ctx) for m in SCOPE_METRICS}
    assert got == pytest.approx({"fwd_bwd_frac": 0.012950486539217952,
                                 "inner_opt_frac": 0.016050812435357983,
                                 "outer_sync_frac": 0.0039685271918430045,
                                 "eval_frac": 0.0008923293580894441})
    assert all(0 < v <= 1 for v in got.values())
    # the benchmark's own readers see the same trace as before
    assert load_module("metrics", "device_idle_frac").read(ctx) == pytest.approx(
        1 - 2757767 / 26898140)
    assert load_module("metrics", "datagen_frac").read(ctx) > 0


@pytest.mark.parametrize("infer", [True, False])
def test_scoped_self_times_add_up_to_busy(scoped, infer):
    lo, hi = scoped.window()
    split = scopes.scope_split(scoped, lo, hi, LAYERS, infer=infer)
    busy = tr.busy_ns(scoped.devices["/device:TPU:0"], lo, hi)
    assert sum(split.values()) == pytest.approx(busy, rel=1e-12)
    assert 0 < split[scopes.UNSCOPED] < 0.25 * busy
    nested = {s: scopes.scope_ns(scoped, lo, hi, s) for s in
              ("repro.newton_schulz", "repro.reduce", "repro.outer_update")}
    assert 0 < nested["repro.newton_schulz"] <= split["repro.inner_opt"]
    assert 0 < nested["repro.reduce"] + nested["repro.outer_update"] <= \
        scopes.scope_ns(scoped, lo, hi, "repro.outer_sync")


def test_program_spans_name_the_idle_gaps(scoped):
    names = sorted(scoped.program_spans)
    assert [n for _, _, n in names].count("repro.run_rounds") == 1
    assert [n for _, _, n in names].count("repro.dispatch") == 2
    (lo_r, hi_r), = [(s, e) for s, e, n in names if n == "repro.run_rounds"]
    assert all(lo_r <= s <= e <= hi_r for s, e, _ in names)
    lo, hi = scoped.window()
    bare, named = tr.idle_gaps(scoped, lo, hi), scopes.idle_gaps(scoped, lo, hi)
    assert [g[1] for g in bare] == [g[1] for g in named]
    # no bench. span lies inside the window here: the driver's spans name every gap
    assert {g[0] for g in bare} == {"host.other"}
    assert named[0][0] == "repro.drain" and all(g[0].startswith("repro.") for g in named)
