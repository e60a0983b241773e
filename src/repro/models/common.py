"""Shared model-definition building blocks.

All models are pure functional pytrees: ``init(rng, cfg) -> params`` and
forward functions taking ``(cfg, params, ...)``. Layers are stored *stacked*
(leading ``[L, ...]`` axis) and iterated with ``jax.lax.scan`` so the HLO is
depth-independent — essential for compiling 88-100 layer production configs
on the dry-run host, and it is what makes per-layer streaming-DiLoCo
partitions a simple boolean mask over the L axis.
"""
from __future__ import annotations

import dataclasses
import math
from contextvars import ContextVar
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

PyTree = Any

# ---------------------------------------------------------------------------
# Config
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str = "model"
    arch_type: str = "dense"  # dense | moe | ssm | hybrid | audio | vlm
    n_layers: int = 2
    d_model: int = 256
    n_heads: int = 4
    n_kv_heads: int = 4
    d_ff: int = 512
    vocab: int = 1024
    head_dim: int = 0  # 0 -> d_model // n_heads
    activation: str = "swiglu"  # swiglu | relu2 | gelu
    qk_norm: bool = True
    post_norm: bool = False  # gemma3-style extra RMSNorm after sublayer outputs
    rope_theta: float = 1_000_000.0
    tie_embeddings: bool = False
    logit_softcap: float = 0.0
    # MoE
    n_experts: int = 0
    n_shared_experts: int = 0
    experts_per_token: int = 0
    capacity_factor: float = 1.25
    router_aux_coef: float = 0.01
    moe_groups: int = 16  # token groups (sharded over 'data') for dispatch locality
    # SSM (mamba2)
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_head_dim: int = 64
    ssm_chunk: int = 64
    conv_width: int = 4
    # hybrid (zamba2): one *shared* attention block applied every hybrid_period layers
    hybrid_period: int = 6
    # vlm (llama-3.2-vision): cross-attn layer every vlm_period-th layer
    vlm_period: int = 5
    n_image_tokens: int = 1600
    # audio (whisper)
    n_audio_frames: int = 1500
    n_encoder_layers: int = 0
    # attention variant
    sliding_window: int = 0  # 0 = full causal attention
    # attention execution backend: 'xla' (dense below blockwise_threshold,
    # online-softmax blockwise above), 'pallas' (fused flash-attention
    # kernel, interpret mode off-TPU; shard_mapped over the mesh by the
    # kernel-partitioning routing, so it lowers on multi-device worlds too)
    # or 'auto' (models.attention.attention_path: 'pallas' on a TPU from
    # FLASH_MIN_SEQ, else 'xla'; the train CLI's default)
    attn_impl: str = "xla"
    blockwise_threshold: int = 4096  # seqs >= this switch xla to blockwise
    attn_block_q: int = 512  # q-block rows per attention tile
    attn_block_kv: int = 1024  # kv-block rows per attention tile
    # training sequence length (0 = unspecified). The launchers plumb
    # --seq-len here so the model config is the single source of truth for
    # the data pipeline, and the sliding window is clamped to it.
    max_seq_len: int = 0
    # numerics
    dtype: str = "bfloat16"
    param_dtype: str = "float32"
    remat: bool = True
    # provenance / applicability
    citation: str = ""
    skip_shapes: tuple = ()  # input shapes this arch skips (documented in DESIGN.md)

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def pdtype(self):
        return jnp.dtype(self.param_dtype)

    @property
    def d_inner(self) -> int:  # mamba2 inner width
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_head_dim

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


# ---------------------------------------------------------------------------
# Activation sharding hints (hillclimbing lever; no-op unless rules installed)
# ---------------------------------------------------------------------------

_ACT_RULES: ContextVar[dict[str, P] | None] = ContextVar("act_rules", default=None)


class activation_sharding:
    """Context manager installing named activation sharding constraints.

    Example::

        with activation_sharding({"residual": P("data", None, "model")}):
            logits = forward(...)
    """

    def __init__(self, rules: dict[str, P]):
        self.rules = rules

    def __enter__(self):
        self._tok = _ACT_RULES.set(self.rules)
        return self

    def __exit__(self, *exc):
        _ACT_RULES.reset(self._tok)
        return False


def shard_hint(x: jax.Array, name: str) -> jax.Array:
    rules = _ACT_RULES.get()
    if rules is None or name not in rules:
        return x
    spec = rules[name]
    # right-align the spec with the value's rank (rules are written for the
    # canonical [B, S, ...] layout; lower-rank views drop leading axes)
    entries = list(spec)
    if len(entries) > x.ndim:
        entries = entries[len(entries) - x.ndim:]
    elif len(entries) < x.ndim:
        entries = [None] * (x.ndim - len(entries)) + entries
    return jax.lax.with_sharding_constraint(x, P(*entries))


# ---------------------------------------------------------------------------
# Primitives
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    normed = x32 * jax.lax.rsqrt(var + eps)
    return (normed * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


def activation_fn(name: str, x: jax.Array, gate: jax.Array | None = None) -> jax.Array:
    if name == "swiglu":
        assert gate is not None
        return jax.nn.silu(gate) * x
    if name == "relu2":  # nemotron-4 squared ReLU
        return jnp.square(jax.nn.relu(x))
    if name == "gelu":
        return jax.nn.gelu(x)
    raise ValueError(f"unknown activation {name!r}")


def rope_frequencies(hd: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding. x: [B, S, H, hd]; positions: [B, S] or [S]."""
    hd = x.shape[-1]
    freqs = rope_frequencies(hd, theta)  # [hd/2]
    if positions.ndim == 1:
        positions = positions[None, :]
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B, S, hd/2]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(n: int, d: int) -> jax.Array:
    """Whisper-style fixed sinusoidal embeddings [n, d]."""
    pos = jnp.arange(n, dtype=jnp.float32)[:, None]
    dim = jnp.arange(d // 2, dtype=jnp.float32)[None, :]
    inv = jnp.exp(-math.log(10000.0) * dim / max(d // 2 - 1, 1))
    ang = pos * inv
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------


def dense_init(key, shape, fan_in: int | None = None, dtype=jnp.float32) -> jax.Array:
    fan = fan_in if fan_in is not None else shape[-2]
    std = 1.0 / math.sqrt(fan)
    return (jax.random.truncated_normal(key, -3.0, 3.0, shape, jnp.float32) * std).astype(dtype)


def embed_init(key, shape, dtype=jnp.float32) -> jax.Array:
    # std 1/sqrt(d): with the sqrt(d) input scaling this keeps the residual
    # stream O(1) AND keeps tied-embedding logits O(1).
    std = 1.0 / math.sqrt(shape[-1])
    return (jax.random.truncated_normal(key, -3.0, 3.0, shape, jnp.float32) * std).astype(dtype)


def key_tree(key: jax.Array, n: int) -> list[jax.Array]:
    return list(jax.random.split(key, n))


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------


def softmax_cross_entropy(logits: jax.Array, labels: jax.Array, mask: jax.Array | None = None,
                          z_loss: float = 0.0) -> tuple[jax.Array, dict]:
    """Mean next-token cross-entropy in fp32. logits [B,S,V], labels [B,S].

    Sharded-vocab-safe: the gold logit is gathered with a one-hot einsum
    (reduces locally over the 'model'-sharded vocab axis, then a scalar-sized
    all-reduce) instead of take_along_axis, which GSPMD can only lower by
    all-gathering the full fp32 logits. Max subtraction happens in-fusion so
    the fp32 logit tensor is never a standalone temp (§Perf iteration 1).
    """
    logits = logits.astype(jnp.float32)
    lmax = jax.lax.stop_gradient(jnp.max(logits, axis=-1))
    logz = lmax + jnp.log(jnp.sum(jnp.exp(logits - lmax[..., None]), axis=-1))
    onehot = jax.nn.one_hot(labels, logits.shape[-1], dtype=logits.dtype)
    gold = jnp.einsum("...v,...v->...", logits, onehot)
    nll = logz - gold
    if z_loss:
        nll = nll + z_loss * jnp.square(logz)
    if mask is None:
        mask = jnp.ones_like(nll)
    mask = mask.astype(jnp.float32)
    loss = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return loss, {"loss": loss, "tokens": jnp.sum(mask)}


def fused_cross_entropy(hidden: jax.Array, head_w: jax.Array, labels: jax.Array,
                        chunk: int = 512) -> tuple[jax.Array, dict]:
    """Head-matmul + cross-entropy fused per sequence chunk.

    The full [B, S, V] logit tensor is never materialized: each S-chunk's
    logits live only inside a rematerialized map step (fp32, [B, chunk, V]).
    This is the production big-vocab loss (§Perf iteration 1): peak memory
    drops from O(B*S*V) to O(B*chunk*V) and backward recomputes chunk logits
    instead of storing them.

    hidden: [B, S, d] post-final-norm states; head_w: [d, V]; labels: [B, S].
    """
    B, S, d = hidden.shape
    chunk = min(chunk, S)
    while S % chunk:
        chunk //= 2
    n = S // chunk
    hc = hidden.reshape(B, n, chunk, d).swapaxes(0, 1)  # [n, B, chunk, d]
    lc = labels.reshape(B, n, chunk).swapaxes(0, 1)

    @jax.checkpoint
    def one(x_c, y_c):
        logits = (x_c @ head_w.astype(x_c.dtype)).astype(jnp.float32)
        lmax = jax.lax.stop_gradient(jnp.max(logits, axis=-1))
        logz = lmax + jnp.log(jnp.sum(jnp.exp(logits - lmax[..., None]), axis=-1))
        onehot = jax.nn.one_hot(y_c, logits.shape[-1], dtype=logits.dtype)
        gold = jnp.einsum("...v,...v->...", logits, onehot)
        return jnp.sum(logz - gold)

    def scan_body(acc, xy):
        return acc + one(*xy), None

    total, _ = jax.lax.scan(scan_body, jnp.float32(0.0), (hc, lc))
    loss = total / (B * S)
    return loss, {"loss": loss, "tokens": jnp.float32(B * S)}
