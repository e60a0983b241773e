"""Plain float32 reference of the Mamba2 language model (arXiv:2405.21060).

Per layer: RMSNorm, then the Mamba2 mixer: one input projection to
(z, x, B, C, dt); a causal depthwise convolution of width ``conv_width`` and
SiLU over (x, B, C); dt = softplus(dt + dt_bias), A = -exp(A_log); the SSD
recurrence h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T, y_t = h_t C_t + D x_t
with one B/C group, computed in the paper's chunked dual form (quadratic
within a chunk, a linear scan over chunk states); gated RMSNorm of
y * SiLU(z); output projection; residual. A final RMSNorm and the output
head close the model. Departures that the configuration file states: the
head is untied, the embedding is multiplied by sqrt(d_model), and RMSNorm
uses eps 1e-6 with a (1 + scale) weight initialised at zero.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench.reference.common import F32, Numerics, cross_entropy, rms_norm, truncated

MUON_LEAVES = ("in_proj", "out_proj")
EPS = 1e-6


def is_muon(path: str) -> bool:
    """The two projections take Muon; everything else takes AdamW."""
    return path.split("/")[-1] in MUON_LEAVES


def _sizes(m: dict):
    di = m["ssm_expand"] * m["d_model"]
    N, P = m["ssm_state"], m["ssm_head_dim"]
    return di, N, di // P, P


def init(key, m: dict) -> dict:
    """Weights from the seed: split(key, 3) -> embedding, mixers, head."""
    L, d, V, W = m["n_layers"], m["d_model"], m["vocab"], m["conv_width"]
    di, N, H, _ = _sizes(m)
    conv_ch = di + 2 * N
    ks = jax.random.split(key, 3)
    km = jax.random.split(ks[1], 4)
    return {
        "embed": truncated(ks[0], (V, d), d),
        "layers": {
            "mamba": {
                "in_proj": truncated(km[0], (L, d, 2 * di + 2 * N + H), d),
                "conv_w": jax.random.normal(km[1], (L, W, conv_ch)) * 0.1,
                "conv_bias": jnp.zeros((L, conv_ch), F32),
                "a_log": jnp.log(jnp.broadcast_to(jnp.linspace(1.0, 16.0, H), (L, H))),
                "dt_bias": jnp.zeros((L, H), F32),
                "d_skip": jnp.ones((L, H), F32),
                "gate_norm_scale": jnp.zeros((L, di), F32),
                "out_proj": truncated(km[3], (L, di, d), di),
            },
            "ln_scale": jnp.zeros((L, d), F32),
        },
        "final_norm_scale": jnp.zeros((d,), F32),
        "head": truncated(ks[2], (d, V), d),
    }


def ssd(nx: Numerics, x, dt, A, Bm, Cm, chunk: int):
    """y for x [B, S, H, P], dt [B, S, H], A [H], B/C [B, S, N]."""
    Bsz, S, H, P = x.shape
    N = Bm.shape[-1]
    c = S // chunk
    x = x.reshape(Bsz, c, chunk, H, P)
    dA = (dt * A).reshape(Bsz, c, chunk, H)
    dt = dt.reshape(Bsz, c, chunk, H)
    Bm = Bm.reshape(Bsz, c, chunk, N)
    Cm = Cm.reshape(Bsz, c, chunk, N)
    cum = jnp.cumsum(dA, axis=2)  # [B, c, Q, H]
    # within a chunk: decay from j to i is exp(cum_i - cum_j) for j <= i
    seg = cum[:, :, :, None, :] - cum[:, :, None, :, :]  # [B, c, i, j, H]
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))[None, None, :, :, None]
    decay = jnp.where(lower, jnp.exp(jnp.where(lower, seg, 0.0)), 0.0)
    scores = nx.dot("bcin,bcjn->bcij", Cm, Bm)[..., None] * decay  # [B, c, i, j, H]
    y_in = nx.dot("bcijh,bcjhp->bcihp", scores, dt[..., None] * x)
    # state at the end of each chunk from its own inputs
    to_end = jnp.exp(cum[:, :, -1:, :] - cum)  # [B, c, Q, H]
    states = nx.dot("bcjn,bcjhp->bchpn", Bm, (to_end * dt)[..., None] * x)
    chunk_decay = jnp.exp(cum[:, :, -1, :])  # [B, c, H]

    def carry(h, inp):
        s, g = inp
        return g[..., None, None] * h + s, h

    _, entering = jax.lax.scan(carry, jnp.zeros((Bsz, H, P, N), F32),
                               (jnp.moveaxis(states, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)))
    entering = jnp.moveaxis(entering, 0, 1)  # [B, c, H, P, N]
    y_off = nx.dot("bcin,bchpn->bcihp", Cm, entering) * jnp.exp(cum)[..., None]
    return (y_in + y_off).reshape(Bsz, S, H, P)


def mixer(nx: Numerics, m: dict, x: jax.Array, p: dict) -> jax.Array:
    Bsz, S, _ = x.shape
    di, N, H, P = _sizes(m)
    W = m["conv_width"]
    proj = nx.dot("bsd,de->bse", x, p["in_proj"])
    z, xbc, dt = proj[..., :di], proj[..., di:2 * di + 2 * N], proj[..., 2 * di + 2 * N:]
    padded = jnp.pad(xbc, ((0, 0), (W - 1, 0), (0, 0)))
    conv = sum(padded[:, i:i + S] * p["conv_w"][i] for i in range(W)) + p["conv_bias"]
    xbc = jax.nn.silu(conv)
    xs, Bm, Cm = xbc[..., :di], xbc[..., di:di + N], xbc[..., di + N:]
    dt = jax.nn.softplus(dt + p["dt_bias"])
    A = -jnp.exp(p["a_log"])
    xh = xs.reshape(Bsz, S, H, P)
    y = ssd(nx, xh, dt, A, Bm, Cm, m["ssm_chunk"]) + p["d_skip"][:, None] * xh
    y = rms_norm(y.reshape(Bsz, S, di) * jax.nn.silu(z), p["gate_norm_scale"], EPS)
    return nx.dot("bse,ed->bsd", y, p["out_proj"])


def nll(nx: Numerics, params: dict, tokens: jax.Array, labels: jax.Array, m: dict) -> jax.Array:
    """Summed next-token cross-entropy over tokens [B, S]."""
    x = params["embed"][tokens] * math.sqrt(m["d_model"])

    def step(x, lp):
        return x + mixer(nx, m, rms_norm(x, lp["ln_scale"], EPS), lp["mamba"]), None

    x, _ = jax.lax.scan(jax.checkpoint(step), x, params["layers"])
    x = rms_norm(x, params["final_norm_scale"], EPS)
    return sum(cross_entropy(nx, x[b], params["head"], labels[b]) for b in range(x.shape[0]))
