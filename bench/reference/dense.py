"""Plain float32 reference of the dense decoder (Llama layout, as smollm-135m
runs here).

Per layer: RMSNorm, grouped-query causal attention with rotary positions
(half-split rotation, base ``rope_theta``), residual, RMSNorm, SwiGLU MLP,
residual; then a final RMSNorm and the tied (or separate) output head.
Departures from the published model that the configuration file states and
this reference follows: the token embedding is multiplied by sqrt(d_model),
RMSNorm uses eps 1e-6 with a (1 + scale) weight initialised at zero, and the
weights are drawn as the configuration's ``init`` says.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench.reference.common import F32, Numerics, cross_entropy, rms_norm, truncated

MUON_LEAVES = ("wq", "wk", "wv", "wo", "w_in", "w_gate", "w_out")
EPS = 1e-6


def is_muon(path: str) -> bool:
    """Hidden matrices take Muon; embeddings, norms and the head take AdamW."""
    return path.split("/")[-1] in MUON_LEAVES


def init(key, m: dict) -> dict:
    """Weights from the seed: split(key, 6) -> attention, MLP, embedding,
    head; each stacked over layers; truncated normal / sqrt(fan_in)."""
    L, d, H, KV, hd, ff, V = (m["n_layers"], m["d_model"], m["n_heads"],
                              m["n_kv_heads"], m["head_dim"], m["d_ff"], m["vocab"])
    ks = jax.random.split(key, 6)
    ka = jax.random.split(ks[0], 4)
    km = jax.random.split(ks[1], 3)
    params = {
        "embed": truncated(ks[2], (V, d), d),
        "layers": {
            "attn": {
                "wq": truncated(ka[0], (L, d, H * hd), d),
                "wk": truncated(ka[1], (L, d, KV * hd), d),
                "wv": truncated(ka[2], (L, d, KV * hd), d),
                "wo": truncated(ka[3], (L, H * hd, d), H * hd),
            },
            "ln1_scale": jnp.zeros((L, d), F32),
            "ln2_scale": jnp.zeros((L, d), F32),
            "mlp": {
                "w_in": truncated(km[0], (L, d, ff), d),
                "w_out": truncated(km[1], (L, ff, d), ff),
                "w_gate": truncated(km[2], (L, d, ff), d),
            },
        },
        "final_norm_scale": jnp.zeros((d,), F32),
    }
    if not m["tie_embeddings"]:
        params["head"] = truncated(ks[3], (d, V), d)
    return params


def rope(x: jax.Array, theta: float) -> jax.Array:
    """Rotary positions on [B, S, heads, hd]: rotate (first half, second half)."""
    S, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = jnp.arange(S, dtype=F32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def layer(nx: Numerics, m: dict, x: jax.Array, lp: dict) -> jax.Array:
    B, S, d = x.shape
    H, KV, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    a = lp["attn"]
    h = rms_norm(x, lp["ln1_scale"], EPS)
    q = rope(nx.dot("bsd,de->bse", h, a["wq"]).reshape(B, S, H, hd), m["rope_theta"])
    k = rope(nx.dot("bsd,de->bse", h, a["wk"]).reshape(B, S, KV, hd), m["rope_theta"])
    v = nx.dot("bsd,de->bse", h, a["wv"]).reshape(B, S, KV, hd)
    # query head j reads key/value head j // (H / KV)
    k = jnp.repeat(k, H // KV, axis=2)
    v = jnp.repeat(v, H // KV, axis=2)
    s = nx.dot("bqhe,bkhe->bhqk", q, k) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = nx.dot("bhqk,bkhe->bqhe", p, v).reshape(B, S, H * hd)
    x = x + nx.dot("bse,ed->bsd", o, a["wo"])
    h = rms_norm(x, lp["ln2_scale"], EPS)
    w = lp["mlp"]
    g = jax.nn.silu(nx.dot("bsd,df->bsf", h, w["w_gate"])) * nx.dot("bsd,df->bsf", h, w["w_in"])
    return x + nx.dot("bsf,fd->bsd", g, w["w_out"])


def nll(nx: Numerics, params: dict, tokens: jax.Array, labels: jax.Array, m: dict) -> jax.Array:
    """Summed next-token cross-entropy over tokens [B, S]."""
    x = params["embed"][tokens] * math.sqrt(m["d_model"])
    step = jax.checkpoint(lambda x, lp: (layer(nx, m, x, lp), None))
    x, _ = jax.lax.scan(step, x, params["layers"])
    x = rms_norm(x, params["final_norm_scale"], EPS)
    head = params["embed"].T if m["tie_embeddings"] else params["head"]
    return sum(cross_entropy(nx, x[b], head, labels[b]) for b in range(x.shape[0]))
