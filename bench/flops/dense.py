"""Model FLOPs per trained token of a dense decoder (forward + backward).

A matrix product [m, k] x [k, n] costs 2mkn. Forward per token: 2 x every
matrix parameter the token passes through (q, k, v, o, the three SwiGLU
matrices, and the output head, tied or not; the embedding lookup is a
gather and costs nothing), plus causal attention: the token at position i
scores against i + 1 keys and mixes i + 1 values, 2 * n_heads * head_dim
FLOPs each, so a sequence of S averages (S + 1) / 2 positions. Backward is
twice the forward. Recomputation (remat) and the optimizer are not model
work and are not counted.
"""
from __future__ import annotations


def matrix_params(m: dict) -> int:
    """Parameters that enter a matrix product, per token, head included."""
    d, H, KV, hd, ff, L, V = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                              m["head_dim"], m["d_ff"], m["n_layers"], m["vocab"])
    per_layer = d * H * hd + 2 * d * KV * hd + H * hd * d + 3 * d * ff
    return L * per_layer + d * V


def param_count(m: dict) -> int:
    """Every parameter: matrices, embedding, norm scales."""
    d, L, V = m["d_model"], m["n_layers"], m["vocab"]
    head = 0 if m["tie_embeddings"] else d * V
    return matrix_params(m) - d * V + V * d + head + 2 * L * d + d


def forward_flops_per_token(m: dict, seq_len: int) -> float:
    attn = 2.0 * 2.0 * m["n_heads"] * m["head_dim"] * (seq_len + 1) / 2.0
    return 2.0 * matrix_params(m) + m["n_layers"] * attn


def train_flops_per_token(m: dict, seq_len: int) -> float:
    return 3.0 * forward_flops_per_token(m, seq_len)
