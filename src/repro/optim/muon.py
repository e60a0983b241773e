"""Muon — the paper's MuLoCo inner optimizer, as a transform chain.

Momentum accumulation followed by 5 quintic Newton–Schulz iterations that
orthogonalize each hidden weight-matrix update (Jordan et al., 2024
coefficients a,b,c = 3.4445, -4.7750, 2.0315), with decoupled weight decay
(important at scale per Liu et al., 2025). Per the paper, Muon is applied to
hidden matrices only; embeddings, norms, biases and the output head fall back
to AdamW — expressed as::

    partition(muon_label, {
        "muon":  chain(trace_momentum(cfg), orthogonalize(cfg, ns_impl)),
        "adamw": scale_by_adam(cfg),
    })

wrapped by :func:`repro.optim.base.descend` with the per-shape lr scale.
Variants (MuonBP, NorMuon) swap or extend the "muon" chain — see
:mod:`repro.optim.muon_variants`.

Stacked parameters from scan-over-layers ([L, m, n]) and MoE expert banks
([L, E, m, n]) are orthogonalized per-matrix via reshape+vmap.

``ns_impl='pallas'`` routes the Newton–Schulz matmuls through the Pallas TPU
kernel in ``repro.kernels`` (interpret-mode on CPU); ``'jnp'`` is the pure
XLA path used for dry-runs and production lowering.
"""
from __future__ import annotations

import math
import re
from typing import Any

import jax
import jax.numpy as jnp

from repro.models.common import shard_hint
from repro.optim.adamw import scale_by_adam
from repro.optim.base import Optimizer, OptimizerConfig, descend
from repro.optim.transform import Transform, chain, partition
from repro.tracing import NEWTON_SCHULZ

PyTree = Any

NS_COEFFS = (3.4445, -4.7750, 2.0315)

# Parameters that never receive Muon (paper: embeddings, norms, output layer;
# we extend with SSM scalar/vector state and conv filters which are not plain
# matmul weights).
_ADAMW_PATTERN = re.compile(
    r"(embed|unembed|head|norm|bias|scale|dt_bias|a_log|d_skip|conv|rope|router_bias)",
    re.IGNORECASE,
)


def muon_label(path: str, leaf) -> str:
    """'muon' for hidden matmul matrices, 'adamw' otherwise."""
    if _ADAMW_PATTERN.search(path):
        return "adamw"
    shape = leaf.shape
    if len(shape) < 2 or shape[-1] < 2 or shape[-2] < 2:
        return "adamw"
    return "muon"


def param_labels(params: PyTree) -> PyTree:
    from repro.utils.tree import tree_map_with_path

    return tree_map_with_path(muon_label, params)


def _ns_body(X: jax.Array) -> jax.Array:
    """One quintic NS iteration on [..., m, n] (batched-safe)."""
    a, b, c = NS_COEFFS
    Xt = jnp.swapaxes(X, -1, -2)
    A = X @ Xt
    B = b * A + c * (A @ A)
    return a * X + B @ X


@jax.named_scope(NEWTON_SCHULZ)
def newton_schulz(G: jax.Array, iters: int = 5, eps: float = 1e-7) -> jax.Array:
    """Orthogonalize the trailing two dims of G via quintic Newton–Schulz.

    Works on [m, n] and any stacked [..., m, n]. Computation in bf16 per the
    Muon reference (NS is robust to low precision), normalization in fp32.
    """
    orig_dtype = G.dtype
    *batch, m, n = G.shape
    X = G.reshape((-1, m, n)).astype(jnp.float32)
    transpose = m > n
    if transpose:
        X = jnp.swapaxes(X, -1, -2)
    norm = jnp.sqrt(jnp.sum(X * X, axis=(-2, -1), keepdims=True)) + eps
    X = (X / norm).astype(jnp.bfloat16)

    def body(X, _):
        return _ns_body(X), None

    X, _ = jax.lax.scan(body, X, None, length=iters)
    if transpose:
        X = jnp.swapaxes(X, -1, -2)
    return X.reshape((*batch, m, n)).astype(orig_dtype)


@jax.named_scope(NEWTON_SCHULZ)
def newton_schulz_pallas(G: jax.Array, iters: int = 5, eps: float = 1e-7) -> jax.Array:
    """Same contract as :func:`newton_schulz` but with Pallas-kernel matmuls."""
    from repro.kernels.ops import ns_orthogonalize

    return ns_orthogonalize(G, iters=iters, eps=eps)


def ns_fn_for(ns_impl: str):
    return newton_schulz_pallas if ns_impl == "pallas" else newton_schulz


def _muon_lr_scale(shape: tuple[int, ...], mode: str) -> float:
    m, n = int(shape[-2]), int(shape[-1])
    if mode == "paper":  # paper §5: rescale lr by sqrt(n/m) for W in R^{m x n}
        return math.sqrt(n / m)
    if mode == "jordan":
        return max(1.0, m / n) ** 0.5
    if mode == "moonlight":
        return 0.2 * math.sqrt(max(m, n))
    if mode == "none":
        return 1.0
    raise ValueError(f"unknown muon lr scale mode {mode!r}")


# ---------------------------------------------------------------------------
# The Muon transform stages
# ---------------------------------------------------------------------------


def trace_momentum(cfg: OptimizerConfig) -> Transform:
    """Muon momentum: m_t = beta * m_{t-1} + g_t (paper Alg. 1; note NO
    (1-beta) dampening — the raw gradient is added). Passes the fp32
    accumulator downstream, stores it in ``state_dtype``."""
    b1 = cfg.b1
    sdt = jnp.dtype(cfg.state_dtype)

    def init(tree: PyTree) -> PyTree:
        return {"m": jax.tree.map(lambda p: jnp.zeros(p.shape, sdt), tree)}

    def update(updates: PyTree, state: PyTree, params: PyTree):
        m = jax.tree.map(
            lambda g, m: b1 * m.astype(jnp.float32) + g.astype(jnp.float32),
            updates, state["m"])
        return m, {"m": jax.tree.map(lambda x: x.astype(sdt), m)}

    return Transform(init=init, update=update)


def orthogonalize(cfg: OptimizerConfig, ns_impl: str = "jnp") -> Transform:
    """Newton–Schulz orthogonalization of each [..., m, n] update.

    Layer-parallel resharding hints: the momentum is resharded so whole
    matrices live on one chip (leading stacked axis -> mesh) and the 5 NS
    iterations run with ZERO collectives; the orthogonalized result is
    resharded back. Without this, every NS matmul psums an [m,m] partial
    product (measured: 6.1 TB/chip/step on mistral-123b train_4k —
    EXPERIMENTS.md §Perf it.2). No-op unless launch installs an "ns_matrix"
    rule.
    """
    ns_fn = ns_fn_for(ns_impl)
    iters = cfg.ns_iters

    def orth(u, _params):
        def per_leaf(m):
            m_local = shard_hint(m, "ns_matrix")
            out = ns_fn(m_local, iters=iters).astype(jnp.float32)
            return shard_hint(out, "ns_out")

        return jax.tree.map(per_leaf, u)

    from repro.optim.transform import stateless

    return stateless(orth)


def muon_partition(cfg: OptimizerConfig, muon_chain: Transform) -> Transform:
    """``partition(muon_label, {muon: <chain>, adamw: scale_by_adam})``."""
    return partition(muon_label, {"muon": muon_chain,
                                  "adamw": scale_by_adam(cfg)})


def muon_mults(cfg: OptimizerConfig, adamw_lr_ratio: float = 1.0):
    """Per-leaf (update, decay) lr multipliers for the descent stage: hidden
    matrices get the shape-dependent Muon scale (decay stays at the base lr,
    matching the paper's decoupled decay); AdamW-fallback leaves get the
    optional lr ratio on both terms."""

    def mults(path: str, leaf) -> tuple[float, float]:
        if muon_label(path, leaf) == "muon":
            return _muon_lr_scale(leaf.shape, cfg.muon_lr_scale_mode), 1.0
        return adamw_lr_ratio, adamw_lr_ratio

    return mults


def muon(cfg: OptimizerConfig, ns_impl: str = "jnp", adamw_lr_ratio: float = 1.0) -> Optimizer:
    """Muon for hidden matrices + AdamW for everything else.

    ``adamw_lr_ratio`` scales the AdamW learning rate relative to the Muon lr
    (commonly tuned separately; paper tunes one inner lr, so default 1).
    """
    tx = muon_partition(cfg, chain(trace_momentum(cfg), orthogonalize(cfg, ns_impl)))
    return descend(tx, cfg, muon_mults(cfg, adamw_lr_ratio))
