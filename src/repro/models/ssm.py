"""Mamba2 (state-space duality / SSD) block — arXiv:2405.21060.

Implements the chunked SSD algorithm: quadratic attention-like computation
inside chunks of length Q plus a linear recurrence over chunk states, which is
the TPU-friendly dual form (batched matmuls for the MXU + one short
``lax.scan``). Decode is the O(1)-per-token recurrent update on a
[B, H, P, N] state — this is why SSM archs run the 524k-token decode shape
natively.

Layout notes
  d_inner = expand * d_model, P = ssm_head_dim, H = d_inner / P heads,
  N = ssm_state, single B/C group (G=1) as in mamba2-370m.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro import tracing
from repro.kernels import ssd
from repro.kernels.backend import pallas_interpret
from repro.models.common import ModelConfig, dense_init, rms_norm

PyTree = Any


def init_mamba(key, cfg: ModelConfig, n_layers: int | None = None) -> PyTree:
    d, di, N, H = cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
    conv_ch = di + 2 * N  # conv over (x, B, C)
    d_in_proj = 2 * di + 2 * N + H  # z, x, B, C, dt
    L = (n_layers,) if n_layers else ()
    ks = jax.random.split(key, 4)
    pd = cfg.pdtype
    return {
        "in_proj": dense_init(ks[0], (*L, d, d_in_proj), fan_in=d, dtype=pd),
        "conv_w": (jax.random.normal(ks[1], (*L, cfg.conv_width, conv_ch)) * 0.1).astype(pd),
        "conv_bias": jnp.zeros((*L, conv_ch), pd),
        "a_log": jnp.log(jnp.broadcast_to(jnp.linspace(1.0, 16.0, H), (*L, H))).astype(pd),
        "dt_bias": jnp.zeros((*L, H), pd),
        "d_skip": jnp.ones((*L, H), pd),
        "gate_norm_scale": jnp.zeros((*L, di), pd),
        "out_proj": dense_init(ks[3], (*L, di, d), fan_in=di, dtype=pd),
    }


def _segsum(x: jax.Array) -> jax.Array:
    """x [..., Q] -> [..., Q, Q]; out[i, j] = sum_{j < k <= i} x[k], -inf for j > i."""
    Q = x.shape[-1]
    cs = jnp.cumsum(x, axis=-1)
    diff = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((Q, Q), bool))
    return jnp.where(mask, diff, -jnp.inf)


def _causal_conv(xBC: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """Depthwise causal conv. xBC [B,S,C]; w [W,C]; b [C]."""
    W = w.shape[0]
    pad = jnp.pad(xBC, ((0, 0), (W - 1, 0), (0, 0)))
    out = jnp.zeros_like(xBC, dtype=jnp.float32)
    S = xBC.shape[1]
    for i in range(W):  # W is tiny (4): unrolled taps
        out = out + pad[:, i : i + S, :].astype(jnp.float32) * w[i].astype(jnp.float32)
    return jax.nn.silu(out + b.astype(jnp.float32)).astype(xBC.dtype)


def _split_proj(p: PyTree, cfg: ModelConfig, x: jax.Array):
    di, N, H = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
    zxbcdt = x @ p["in_proj"].astype(x.dtype)
    z, xBC, dt = jnp.split(zxbcdt, [di, 2 * di + 2 * N], axis=-1)
    return z, xBC, dt  # dt: [B, S, H]


def ssd_intra_chunk_xla(xc: jax.Array, dt_c: jax.Array, Bc: jax.Array, Cm_c: jax.Array,
                        dA_c: jax.Array) -> jax.Array:
    """Y_diag [B,Cc,Q,H,P] of the chunked SSD's diagonal blocks in jnp, with
    [B,Cc,H,Q,Q] tensors: the XLA path of ``mamba_forward`` and the oracle
    of the fused kernel (:func:`repro.kernels.ssd.ssd_intra_chunk`). xc
    [B,Cc,Q,H,P], dt_c and dA_c [B,Cc,Q,H], Bc and Cm_c [B,Cc,Q,N]."""
    Lmat = jnp.exp(_segsum(jnp.swapaxes(dA_c, 2, 3)))  # [B,Cc,H,Q,Q]
    CB = jnp.einsum("bcin,bcjn->bcij", Cm_c, Bc)  # [B,Cc,Q,Q]
    M = CB[:, :, None] * Lmat  # [B,Cc,H,i,j]
    return jnp.einsum("bchij,bcjh,bcjhp->bcihp", M, dt_c, xc)


def ssd_path(cfg: ModelConfig, S: int) -> str:
    """The path ``mamba_forward`` runs the intra-chunk SSD on for a sequence
    of length S: ``'pallas'`` (the fused kernel,
    :func:`repro.kernels.ssd.ssd_intra_chunk`) where it runs compiled (a
    TPU, where ``pallas_interpret()`` is False) and the chunk and head
    shapes suit it (:func:`repro.kernels.ssd.supported`: chunks of whole
    128-lane tiles, head dim and state of whole 8-row tiles); ``'xla'``
    (the jnp einsums with ``[B, Cc, H, Q, Q]`` tensors) otherwise."""
    fits = S % cfg.ssm_chunk == 0 and ssd.supported(
        cfg.ssm_chunk, cfg.ssm_head_dim, cfg.ssm_state)
    return "pallas" if fits and not pallas_interpret() else "xla"


def mamba_forward(p: PyTree, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    """Full-sequence chunked SSD. x: [B, S, d] with S % chunk == 0.

    The intra-chunk block runs on :func:`ssd_path` under the device scope
    ``repro.ssd``."""
    B, S, _ = x.shape
    di, N, H, P, Q = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_chunk
    assert S % Q == 0, f"seq {S} must be divisible by ssm_chunk {Q}"
    Cc = S // Q
    dt_compute = cfg.compute_dtype

    z, xBC, dt = _split_proj(p, cfg, x)
    xBC = _causal_conv(xBC, p["conv_w"], p["conv_bias"])
    xs, Bm, Cm = jnp.split(xBC, [di, di + N], axis=-1)  # [B,S,di],[B,S,N],[B,S,N]

    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))  # [B,S,H]
    A = -jnp.exp(p["a_log"].astype(jnp.float32))  # [H]
    dA = dt * A  # [B,S,H]

    # chunk views
    xc = xs.reshape(B, Cc, Q, H, P).astype(jnp.float32)
    Bc = Bm.reshape(B, Cc, Q, N).astype(jnp.float32)
    Cm_c = Cm.reshape(B, Cc, Q, N).astype(jnp.float32)
    dA_c = dA.reshape(B, Cc, Q, H)
    dt_c = dt.reshape(B, Cc, Q, H)
    dAcum = jnp.cumsum(dA_c, axis=2)  # [B,Cc,Q,H]

    # --- intra-chunk (diagonal blocks) ---
    with jax.named_scope(tracing.SSD):
        if ssd_path(cfg, S) == "pallas":
            Y_diag = ssd.ssd_intra_chunk(xc, dt_c, Bc, Cm_c, dAcum)
        else:
            Y_diag = ssd_intra_chunk_xla(xc, dt_c, Bc, Cm_c, dA_c)

    # --- chunk states ---
    decay_states = jnp.exp(dAcum[:, :, -1:, :] - dAcum)  # [B,Cc,Q,H]
    S_chunk = jnp.einsum("bcjh,bcjn,bcjhp->bchpn", decay_states * dt_c, Bc, xc)  # [B,Cc,H,P,N]

    # --- inter-chunk recurrence (linear scan over chunk states) ---
    chunk_decay = jnp.exp(dAcum[:, :, -1, :])  # [B,Cc,H]

    def scan_fn(h, inp):
        s_c, g_c = inp  # state contribution + decay of this chunk
        h_out = h  # state *entering* the chunk
        h_next = g_c[..., None, None] * h + s_c
        return h_next, h_out

    h0 = jnp.zeros((B, H, P, N), jnp.float32)
    _, h_in = jax.lax.scan(
        scan_fn,
        h0,
        (jnp.moveaxis(S_chunk, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)),
    )
    h_in = jnp.moveaxis(h_in, 0, 1)  # [B,Cc,H,P,N], state entering each chunk

    state_decay = jnp.exp(dAcum)  # [B,Cc,Q,H]
    Y_off = jnp.einsum("bcin,bchpn,bcih->bcihp", Cm_c, h_in, state_decay)

    Y = (Y_diag + Y_off).reshape(B, S, H, P)
    x_heads = xs.reshape(B, S, H, P).astype(jnp.float32)
    Y = (Y + p["d_skip"].astype(jnp.float32)[None, None, :, None] * x_heads).reshape(B, S, di)

    # gated RMSNorm + out projection
    Y = rms_norm((Y * jax.nn.silu(z.astype(jnp.float32))).astype(dt_compute), p["gate_norm_scale"])
    return Y @ p["out_proj"].astype(dt_compute)


def init_ssm_state(cfg: ModelConfig, batch: int, n_layers: int) -> PyTree:
    H, P, N = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    conv_ch = cfg.d_inner + 2 * N
    return {
        "h": jnp.zeros((n_layers, batch, H, P, N), jnp.float32),
        "conv": jnp.zeros((n_layers, batch, cfg.conv_width - 1, conv_ch), cfg.compute_dtype),
    }


def mamba_decode(p: PyTree, cfg: ModelConfig, x: jax.Array, state: PyTree) -> tuple[jax.Array, PyTree]:
    """One-token recurrent update. x: [B, 1, d]; state: {"h", "conv"} (per layer)."""
    B = x.shape[0]
    di, N, H, P = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head_dim
    dtc = cfg.compute_dtype

    z, xBC_new, dt = _split_proj(p, cfg, x)  # xBC_new [B,1,C]
    # rolling conv buffer: [B, W-1, C] previous inputs
    buf = jnp.concatenate([state["conv"], xBC_new.astype(state["conv"].dtype)], axis=1)  # [B,W,C]
    w = p["conv_w"].astype(jnp.float32)  # [W, C]
    conv_out = jnp.sum(buf.astype(jnp.float32) * w[None], axis=1, keepdims=True)  # [B,1,C]
    xBC = jax.nn.silu(conv_out + p["conv_bias"].astype(jnp.float32)).astype(dtc)
    new_conv = buf[:, 1:, :]

    xs, Bm, Cm = jnp.split(xBC, [di, di + N], axis=-1)
    dt = jax.nn.softplus(dt[:, 0].astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))  # [B,H]
    A = -jnp.exp(p["a_log"].astype(jnp.float32))
    g = jnp.exp(dt * A)  # [B,H]

    xh = xs[:, 0].reshape(B, H, P).astype(jnp.float32)
    Bv = Bm[:, 0].astype(jnp.float32)  # [B,N]
    Cv = Cm[:, 0].astype(jnp.float32)
    h = state["h"] * g[..., None, None] + jnp.einsum("bh,bhp,bn->bhpn", dt, xh, Bv)
    y = jnp.einsum("bhpn,bn->bhp", h, Cv) + p["d_skip"].astype(jnp.float32)[:, None] * xh
    y = y.reshape(B, 1, di)

    y = rms_norm((y * jax.nn.silu(z.astype(jnp.float32))).astype(dtc), p["gate_norm_scale"])
    out = y @ p["out_proj"].astype(dtc)
    return out, {"h": h, "conv": new_conv}
