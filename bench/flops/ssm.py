"""Model FLOPs per trained token of a Mamba2 (SSD) language model.

Forward per token: 2 x every matrix parameter (input and output
projections, output head), the depthwise convolution (2 * width per
channel), and the SSD products in the chunked dual form with chunk Q:
within a chunk, position i needs C_i . B_j (2N each) and the mix of dt x_j
(2 * H * P each) for the i + 1 positions j <= i, (Q + 1) / 2 on average;
across chunks, writing the token into its chunk state and reading the
entering state each cost 2 * H * P * N. Backward is twice the forward;
recomputation is not counted.
"""
from __future__ import annotations


def _sizes(m: dict):
    di = m["ssm_expand"] * m["d_model"]
    N, P = m["ssm_state"], m["ssm_head_dim"]
    return di, N, di // P, P


def matrix_params(m: dict) -> int:
    d, L, V = m["d_model"], m["n_layers"], m["vocab"]
    di, N, H, _ = _sizes(m)
    return L * (d * (2 * di + 2 * N + H) + di * d) + d * V


def param_count(m: dict) -> int:
    d, L, V, W = m["d_model"], m["n_layers"], m["vocab"], m["conv_width"]
    di, N, H, _ = _sizes(m)
    conv_ch = di + 2 * N
    per_layer_rest = W * conv_ch + conv_ch + 3 * H + di + d
    head = 0 if m.get("tie_embeddings") else d * V
    return matrix_params(m) - d * V + V * d + head + L * per_layer_rest + d


def forward_flops_per_token(m: dict, seq_len: int) -> float:
    di, N, H, P = _sizes(m)
    Q = m["ssm_chunk"]
    conv = 2.0 * m["conv_width"] * (di + 2 * N)
    intra = (2.0 * N + 2.0 * H * P) * (Q + 1) / 2.0
    states = 2.0 * 2.0 * H * P * N
    return 2.0 * matrix_params(m) + m["n_layers"] * (conv + intra + states)


def train_flops_per_token(m: dict, seq_len: int) -> float:
    return 3.0 * forward_flops_per_token(m, seq_len)
