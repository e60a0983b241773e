"""Operations and bytes of the Pallas kernels (``bench/kernels``), the parse of
a custom call's types from its trace text, and the roofline reader."""
from __future__ import annotations

import os
from types import SimpleNamespace

import pytest

from bench import trace as tr
from bench.harness import load_module

PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
FWD = ("%jvp__.1 = (bf16[2,3,512,64]{3,2,1,0:T(8,128)(2,1)S(1)}, "
       "f32[2,3,512,1]{3,2,1,0:T(8,128)S(1)}) custom-call(s32[3,4]{1,0} %copy-done.1, "
       "bf16[2,3,512,64]{3,2,1,0:T(8,128)(2,1)} %copy.30, bf16[2,512,64]{2,1,0} %copy.31, "
       "bf16[2,512,64]{2,1,0} %copy.32), custom_call_target=\"tpu_custom_call\"")
DKV = ("%transpose_jvp___.3 = (bf16[2,512,64]{2,1,0:T(8,128)(2,1)S(1)}, "
       "bf16[2,512,64]{2,1,0:T(8,128)(2,1)S(1)}) custom-call(s32[3,4]{1,0} %a, "
       "bf16[2,3,512,64]{3,2,1,0} %b, bf16[2,512,64]{2,1,0} %c, bf16[2,512,64]{2,1,0} %d, "
       "bf16[2,3,512,64]{3,2,1,0} %e, f32[2,3,512,1]{3,2,1,0} %f, f32[2,3,512,1]{3,2,1,0} %g)"
       ", custom_call_target=\"tpu_custom_call\"")
QUANT = ("%_lambda_.1 = (f32[256,576]{1,0:T(8,128)S(1)}, u8[256,576]{1,0:T(8,128)(4,1)S(1)}) "
         "custom-call(f32[256,576]{1,0} %copy.7, f32[256,1]{1,0} %copy.8, f32[256,1]{1,0} %c)"
         ", custom_call_target=\"tpu_custom_call\"")


def test_custom_call_types():
    results, operands = tr.custom_call(FWD)
    assert results == [("bf16", (2, 3, 512, 64)), ("f32", (2, 3, 512, 1))]
    assert operands == [("s32", (3, 4)), ("bf16", (2, 3, 512, 64)), ("bf16", (2, 512, 64)),
                        ("bf16", (2, 512, 64))]
    assert tr.custom_call("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %x), kind=kLoop") is None
    assert tr.nbytes(("bf16", (2, 3))) == 12 and tr.nbytes(("u8", (5,))) == 5


def test_flash_attention_by_hand():
    fa = load_module("kernels", "flash_attn")
    pairs = 512 * 513 / 2  # causal (query, key) pairs of one query head
    flops, moved = fa.cost(*tr.custom_call(FWD))
    # 2 batch x kv-heads, 3 query heads each, two products of 2 * 64 per pair
    assert flops == pytest.approx(2 * 3 * pairs * 2 * 2 * 64)
    q, kv, lse = 2 * 3 * 512 * 64 * 2, 2 * 512 * 64 * 2, 2 * 3 * 512 * 4
    assert moved == 3 * 4 * 4 + q + 2 * kv + q + lse
    flops, moved = fa.cost(*tr.custom_call(DKV))
    assert flops == pytest.approx(2 * 3 * pairs * 4 * 2 * 64)
    assert moved == 3 * 4 * 4 + 2 * q + 2 * kv + 2 * lse + 2 * kv
    assert fa.cost(*tr.custom_call(QUANT)) is None


def test_quantize_by_hand():
    qz = load_module("kernels", "quantize")
    flops, moved = qz.cost(*tr.custom_call(QUANT))
    assert flops == 0.0
    # f32 in, lo and scale in, f32 reconstruction and u8 codes out
    assert moved == 256 * 576 * 4 + 2 * 256 * 4 + 256 * 576 * (4 + 1)
    deq = ("%d.2 = f32[256,576]{1,0} custom-call(u8[256,576]{1,0} %a, f32[256,1]{1,0} %b, "
           "f32[256,1]{1,0} %c), custom_call_target=\"tpu_custom_call\"")
    assert qz.cost(*tr.custom_call(deq)) == (0.0, 256 * 576 * (1 + 4) + 2 * 256 * 4)
    assert qz.cost(*tr.custom_call(FWD)) is None


def test_roofline_share_of_synthetic_calls():
    qz = load_module("kernels", "quantize")
    _, moved = qz.cost(*tr.custom_call(QUANT))
    least = moved / PEAK["hbm_bytes_per_s"]
    ops = [(0, 1000, QUANT), (2000, 3000, QUANT), (3000, 3500, "%fusion.1 = f32[8]{0} fusion()")]
    t = tr.Trace(devices={"/device:TPU:0": tr.Device(ops=ops, modules=[])}, spans=[])
    share = tr.roofline_share(t, 0, 4000, qz.cost, PEAK)
    assert share == pytest.approx(100 * 2 * least / 2e-6)
    # a call cut by the window's edge is left out, and none left reads nothing
    assert tr.roofline_share(t, 500, 4000, qz.cost, PEAK) == pytest.approx(100 * least / 1e-6)
    assert tr.roofline_share(t, 2500, 4000, qz.cost, PEAK) is None
    ctx = SimpleNamespace(trace=t, trace_window_ns=(0, 4000), peak=PEAK)
    assert load_module("metrics", "quantize_roofline").read(ctx) == pytest.approx(share)
    assert load_module("metrics", "flash_attn_roofline").read(ctx) is None


KERNELS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                       "testdata", "kernels.xplane.pb")


def test_recorded_kernel_trace():
    """``bench/tests/record_kernel_trace.py`` on one v5e chip: flash forward,
    dq and dk/dv on q [6, 2, 512, 64], then quantize and dequantize of a
    [256, 576] matrix, one call each."""
    t = tr.load(KERNELS)
    lo, hi = t.window()
    dev = t.devices["/device:TPU:0"]
    fa, qz = load_module("kernels", "flash_attn"), load_module("kernels", "quantize")
    found = {}
    for s, e, name in dev.ops:
        parsed = tr.custom_call(name)
        if parsed:
            found[fa.kind(*parsed) or qz.kind(*parsed)] = (e - s) / 1e9
    assert sorted(found) == ["dequantize", "dkv", "dq", "fwd", "quantize"]
    pairs = 6 * 2 * 512 * 513 / 2
    q, kv, row = 6 * 2 * 512 * 64 * 2, 6 * 512 * 64 * 2, 6 * 2 * 512 * 4
    least = {
        "fwd": max(2 * 2 * 64 * pairs / 197e12, (48 + 2 * q + 2 * kv + row) / 819e9),
        "dq": max(3 * 2 * 64 * pairs / 197e12, (48 + 3 * q + 2 * kv + 2 * row) / 819e9),
        "dkv": max(4 * 2 * 64 * pairs / 197e12, (48 + 2 * q + 4 * kv + 2 * row) / 819e9),
    }
    want = 100 * sum(least.values()) / sum(found[k] for k in least)
    # the device clock runs about 0.7 ms ahead of the host span here, so the
    # window is widened to hold the first program's calls
    ctx = SimpleNamespace(trace=t, trace_window_ns=(lo - 2e6, hi), peak=PEAK)
    assert load_module("metrics", "flash_attn_roofline").read(ctx) == pytest.approx(want)
    m, n = 256, 576
    quant = (m * n * 4 + 2 * m * 4 + m * n * 5) / 819e9
    dequant = (m * n + 2 * m * 4 + m * n * 4) / 819e9
    want = 100 * (quant + dequant) / (found["quantize"] + found["dequantize"])
    assert load_module("metrics", "quantize_roofline").read(ctx) == pytest.approx(want)
