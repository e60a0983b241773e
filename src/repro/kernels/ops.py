"""Public wrappers around the Pallas kernels: padding to block multiples,
batching, backend selection (:func:`repro.kernels.backend.pallas_interpret`),
and mesh routing.

Each wrapper consults the kernel-partitioning context
(:mod:`repro.kernels.partition`) *outside* any jit cache: with no mesh
routed (the CPU/test default) it dispatches to the same jitted single-device
implementation as before; with a mesh routed by the StepPlan machinery it
shard_maps the kernel body over the specs the kernel module declares
(``rowwise_specs`` / ``ns_stack_spec`` / ``outer_update_spec``). Pad-to-block
happens inside the mapped region on local shapes, so sharding never changes
any element's arithmetic — the shard_mapped results are bitwise-identical
to the single-device calls (tests/test_shard_map.py).

The context read cannot live inside ``@jax.jit``: a cached trace would pin
whichever routing was active at first call. The public functions are plain
Python that pick the jitted or shard_mapped path per call; inside an outer
jit (every production call site) both paths are inlined into the enclosing
trace anyway.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import autotune
from repro.kernels.backend import pallas_interpret
from repro.kernels.matmul import matmul_epilogue, ns_stack_spec
from repro.kernels.outer_update import fused_nesterov_update, outer_update_spec
from repro.kernels.partition import active_partitioning, shard_wrap
from repro.kernels.quantize import (
    DEFAULT_BLOCK_ROWS,
    rowwise_dequantize,
    rowwise_quantize,
    rowwise_specs,
)
from repro.kernels.topk_pack import pack_topk, unpack_topk  # noqa: F401 (re-export)
from repro.optim.muon import NS_COEFFS


def _pad_to(x: jax.Array, mults: tuple[int, ...]) -> jax.Array:
    pads = []
    for dim, mult in zip(x.shape, mults):
        rem = (-dim) % mult
        pads.append((0, rem))
    if all(p == (0, 0) for p in pads):
        return x
    return jnp.pad(x, pads)


@partial(jax.jit, static_argnames=("alpha", "beta", "block"))
def matmul(a: jax.Array, b: jax.Array, d: jax.Array | None = None, *,
           alpha: float = 1.0, beta: float = 0.0, block: int = 128) -> jax.Array:
    """C = alpha * a@b + beta * d with automatic padding.

    Whole-matrix (device-local) by construction: on a mesh this runs inside
    the shard_mapped NS stack, never partitioned on its own."""
    m, k = a.shape
    _, n = b.shape
    ap = _pad_to(a, (block, block))
    bp = _pad_to(b, (block, block))
    dp = _pad_to(d, (block, block)) if d is not None else None
    out = matmul_epilogue(ap, bp, dp, alpha=alpha, beta=beta,
                          block_m=block, block_n=block, block_k=block,
                          interpret=pallas_interpret())
    return out[:m, :n]


def _ns_iteration_pallas(x: jax.Array, block: int) -> jax.Array:
    a, b, c = NS_COEFFS
    A = matmul(x, x.T, block=block)                       # X X^T
    B = matmul(A, A, d=A, alpha=c, beta=b, block=block)   # c*A@A + b*A (fused epilogue)
    return matmul(B, x, d=x, alpha=1.0, beta=a, block=block)  # B@X + a*X (fused epilogue)


def _ns_stack(g3: jax.Array, *, iters: int, eps: float, block: int) -> jax.Array:
    """[bsz, m, n] -> orthogonalized [bsz, m, n]; matrix-local, so safe to
    shard_map over the stack axis."""
    m, n = g3.shape[-2:]
    x = g3.astype(jnp.float32)
    transpose = m > n
    if transpose:
        x = jnp.swapaxes(x, -1, -2)
    x = x / (jnp.sqrt(jnp.sum(x * x, axis=(-2, -1), keepdims=True)) + eps)

    def one(xi):
        for _ in range(iters):
            xi = _ns_iteration_pallas(xi, block)
        return xi

    x = jax.vmap(one)(x) if x.shape[0] > 1 else one(x[0])[None]
    if transpose:
        x = jnp.swapaxes(x, -1, -2)
    return x.astype(g3.dtype)


@partial(jax.jit, static_argnames=("iters", "block"))
def _ns_orthogonalize_jit(g, iters, eps, block):
    orig_dtype = g.dtype
    *batch, m, n = g.shape
    out = _ns_stack(g.reshape((-1, m, n)), iters=iters, eps=eps, block=block)
    return out.reshape((*batch, m, n)).astype(orig_dtype)


def ns_orthogonalize(g: jax.Array, iters: int = 5, eps: float = 1e-7,
                     block: int | None = None) -> jax.Array:
    """Newton–Schulz orthogonalization of the trailing 2 dims via the Pallas
    matmul-epilogue kernel. Batched leading dims are folded into the matrix
    stack — vmapped on one device, shard_mapped over the stack axis when a
    mesh is routed (whole matrices always stay device-local).

    ``block=None`` (the default) consults the autotune table for this
    (m, n, dtype, backend) and falls back to the historical 128 on a miss;
    sweep entries are bitwise-gated, so a tuned block can only retile the
    NS matmuls without splitting the contraction."""
    if block is None:
        m, n = g.shape[-2:]
        block = autotune.ns_block(m, n, str(g.dtype)) or 128
    part = active_partitioning()
    if part is None:
        return _ns_orthogonalize_jit(g, iters, eps, block)
    *batch, m, n = g.shape
    g3 = g.reshape((-1, m, n))
    spec = ns_stack_spec(part, g3.shape[0])
    fn = shard_wrap(partial(_ns_stack, iters=iters, eps=eps, block=block),
                    part, in_specs=(spec,), out_specs=spec)
    return fn(g3).reshape(g.shape)


def _quantize_body(x: jax.Array, *, bits: int, block_rows: int):
    return rowwise_quantize(x, bits, block_rows=block_rows,
                            interpret=pallas_interpret())


@partial(jax.jit, static_argnames=("bits", "block_rows"))
def _quantize_rowwise_jit(x, bits, block_rows):
    return _quantize_body(x, bits=bits, block_rows=block_rows)


def quantize_rowwise(x: jax.Array, bits: int = 4, block_rows: int | None = None):
    """Fused row-wise linear quant->dequant. Returns (dequantized, codes, lo, scale).

    On a routed mesh the row axis is shard_mapped per ``rowwise_specs``
    (rows are independent — each carries its own lo/scale).

    ``block_rows=None`` consults the autotune table for this wire shape and
    falls back to ``DEFAULT_BLOCK_ROWS`` on a miss (every TPU lookup misses:
    the committed entries are keyed ``/cpu``). block_rows is pure row tiling
    (every row quantizes against its own lo/scale), so any tuned value is
    bitwise-inert — the sweep's gate re-verifies that per shape anyway."""
    if block_rows is None:
        block_rows = autotune.quantize_block_rows(
            x.shape[0], x.shape[1], bits, str(x.dtype)) or DEFAULT_BLOCK_ROWS
    part = active_partitioning()
    if part is None:
        return _quantize_rowwise_jit(x, bits, block_rows)
    mat, meta = rowwise_specs(part, x.shape[0])
    fn = shard_wrap(partial(_quantize_body, bits=bits, block_rows=block_rows),
                    part, in_specs=(mat,), out_specs=(mat, mat, meta, meta))
    return fn(x)


def _dequantize_body(codes: jax.Array, lo: jax.Array, scale: jax.Array, *,
                     block_rows: int) -> jax.Array:
    return rowwise_dequantize(codes, lo, scale, block_rows=block_rows,
                              interpret=pallas_interpret())


@partial(jax.jit, static_argnames=("block_rows",))
def _dequantize_rowwise_jit(codes, lo, scale, block_rows):
    return _dequantize_body(codes, lo, scale, block_rows=block_rows)


def dequantize_rowwise(codes: jax.Array, lo: jax.Array, scale: jax.Array,
                       block_rows: int | None = None) -> jax.Array:
    """Fused receiver-side reconstruction: (codes u8 [m, n], lo, scale) -> f32.

    ``block_rows=None`` resolves through the autotune table under the SAME
    key the quantizer uses (the wire shape + bits=4 wire default), so both
    ends of the wire pick the same tiling."""
    if block_rows is None:
        block_rows = autotune.quantize_block_rows(
            codes.shape[0], codes.shape[1], 4, "float32") or DEFAULT_BLOCK_ROWS
    part = active_partitioning()
    if part is None:
        return _dequantize_rowwise_jit(codes, lo, scale, block_rows)
    mat, meta = rowwise_specs(part, codes.shape[0])
    fn = shard_wrap(partial(_dequantize_body, block_rows=block_rows),
                    part, in_specs=(mat, meta, meta), out_specs=mat)
    return fn(codes, lo, scale)


def _nesterov_flat(t: jax.Array, p: jax.Array, uu: jax.Array, *,
                   lr: float, momentum: float, block: int):
    n = t.shape[0]
    t2, u2 = fused_nesterov_update(
        _pad_to(t, (block,)), _pad_to(p, (block,)), _pad_to(uu, (block,)),
        lr=lr, momentum=momentum, block=block, interpret=pallas_interpret())
    return t2[:n], u2[:n]


@partial(jax.jit, static_argnames=("lr", "momentum", "block"))
def _nesterov_update_jit(theta, psi, u, lr, momentum, block):
    shape = theta.shape
    t2, u2 = _nesterov_flat(
        theta.reshape(-1), psi.reshape(-1).astype(jnp.float32),
        u.reshape(-1).astype(jnp.float32), lr=lr, momentum=momentum, block=block)
    return t2.reshape(shape), u2.reshape(shape)


def _nesterov_block(t: jax.Array, p: jax.Array, uu: jax.Array, *,
                    lr: float, momentum: float, block: int):
    """Shape-preserving mapped body: flatten the *local* block, run the
    elementwise kernel, restore the local shape."""
    shape = t.shape
    t2, u2 = _nesterov_flat(t.reshape(-1), p.reshape(-1), uu.reshape(-1),
                            lr=lr, momentum=momentum, block=block)
    return t2.reshape(shape), u2.reshape(shape)


def nesterov_update(theta: jax.Array, psi: jax.Array, u: jax.Array, *,
                    lr: float, momentum: float, block: int = 1024):
    """Fused outer Nesterov update on arbitrary-shaped tensors.

    On a routed mesh the operands are shard_mapped in the outer state's own
    ZeRO layout (``outer_update_spec`` — shape-preserving, flatten happens
    per shard), which keeps the donated TrainState aliased through the
    round/superstep programs; the update is elementwise, so every split is
    bitwise-exact."""
    part = active_partitioning()
    if part is None:
        return _nesterov_update_jit(theta, psi, u, lr, momentum, block)
    spec = outer_update_spec(part, theta.shape)
    fn = shard_wrap(partial(_nesterov_block, lr=lr, momentum=momentum, block=block),
                    part, in_specs=(spec, spec, spec), out_specs=(spec, spec))
    return fn(theta, psi.astype(jnp.float32), u.astype(jnp.float32))
