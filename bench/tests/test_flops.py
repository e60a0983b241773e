"""The benchmark's FLOP counts against parameter counts and hand counts."""
from __future__ import annotations

import json
import os

import pytest

from bench.harness import load_module

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def model(name: str) -> dict:
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)["model"]


@pytest.mark.parametrize("name, family, params", [
    ("smollm-135m", "dense", 134_515_008),
    ("mamba2-370m", "ssm", 419_825_152),
])
def test_param_count(name, family, params):
    assert load_module("flops", family).param_count(model(name)) == params


def test_dense_one_layer_by_hand():
    m = dict(model("smollm-135m"), n_layers=1)
    flops = load_module("flops", "dense")
    # q 576x576, k and v 576x192, o 576x576, three 576x1536 SwiGLU matrices
    layer_matrices = 576 * 576 + 2 * 576 * 192 + 576 * 576 + 3 * 576 * 1536
    head = 576 * 49152
    # causal attention at S=2048: 9 heads of 64, scores and values, 1024.5
    # positions on average
    attention = 2 * 2 * 9 * 64 * 2049 / 2
    want = 2 * (layer_matrices + head) + attention
    assert flops.forward_flops_per_token(m, 2048) == pytest.approx(want, rel=1e-12)
    assert flops.train_flops_per_token(m, 2048) == pytest.approx(3 * want, rel=1e-12)


def test_ssm_one_layer_by_hand():
    m = dict(model("mamba2-370m"), n_layers=1)
    flops = load_module("flops", "ssm")
    # in_proj 1024 -> 2*2048 + 2*128 + 32 = 4384, out_proj 2048 -> 1024
    matrices = 1024 * 4384 + 2048 * 1024 + 1024 * 50280
    conv = 2 * 4 * (2048 + 2 * 128)
    # chunk 256: C.B (2*128) and the mix over 32 heads of 64 (2*2048) for
    # 128.5 earlier positions on average; chunk state in and out
    intra = (2 * 128 + 2 * 2048) * 257 / 2
    states = 2 * 2 * 32 * 64 * 128
    want = 2 * matrices + conv + intra + states
    assert flops.forward_flops_per_token(m, 2048) == pytest.approx(want, rel=1e-12)


def test_causal_attention_is_half_the_square():
    m = dict(model("smollm-135m"), n_layers=1)
    flops = load_module("flops", "dense")
    short, long = (flops.forward_flops_per_token(m, s) for s in (1023, 2047))
    # doubling the context adds 2 * 2 * 9 * 64 * 512 per token
    assert long - short == pytest.approx(2 * 2 * 9 * 64 * 512)
