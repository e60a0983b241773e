"""Blocked matmul with fused polynomial epilogue — the Newton–Schulz hot-spot.

Muon's Newton–Schulz iteration is three chained matmuls per step:

    A = X X^T ;  B = b*A + c*(A A) ;  X' = a*X + B X

Each is an instance of ``C = alpha * (A @ B) + beta * D`` — so one Pallas
kernel with an axpy epilogue covers the whole iteration and keeps the
epilogue adds in VMEM (no extra HBM round-trips between the polynomial
terms, the TPU-native answer to the fused-CUDA Muon step).

Tiling: grid (m/bm, n/bn, k/bk); fp32 accumulator scratch in VMEM; MXU-
aligned 128x128x128 default blocks. Inputs are padded to block multiples by
the ops.py wrapper (zero padding is exact for matmul).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def ns_stack_spec(part, bsz: int):
    """shard_map spec for a [bsz, m, n] Newton-Schulz matrix stack.

    Whole matrices stay device-local (the three chained matmuls of one NS
    iteration reduce over full rows/columns — exactly the layout the refuted
    'ns_matrix' GSPMD resharding hints tried and failed to get; shard_map
    makes it explicit instead). Only the stacked-matrix batch axis shards,
    and only when it divides — the common replicated fallback also lowers,
    which is what turns ``--ns-impl pallas`` legal on the production mesh.
    """
    from jax.sharding import PartitionSpec as P

    from repro.kernels.partition import axes_for

    axes = axes_for(part, bsz, part.ns_axes)
    return P(axes or None, None, None)


def _matmul_epilogue_kernel(a_ref, b_ref, d_ref, o_ref, acc_ref, *, alpha, beta, k_steps):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        a_ref[...].astype(jnp.float32),
        b_ref[...].astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _finish():
        out = alpha * acc_ref[...]
        if beta != 0.0:
            out = out + beta * d_ref[...].astype(jnp.float32)
        o_ref[...] = out.astype(o_ref.dtype)


def matmul_epilogue(
    a: jax.Array,
    b: jax.Array,
    d: jax.Array | None = None,
    *,
    alpha: float = 1.0,
    beta: float = 0.0,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 128,
    interpret: bool,
    out_dtype=None,
) -> jax.Array:
    """C = alpha * (a @ b) + beta * d for 2-D operands (pre-padded shapes)."""
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    assert m % block_m == 0 and n % block_n == 0 and k % block_k == 0, (
        f"shapes ({m},{k})x({k},{n}) must be multiples of blocks "
        f"({block_m},{block_n},{block_k}); pad in ops.py"
    )
    if d is None:
        d = jnp.zeros((m, n), a.dtype)
        beta = 0.0
    k_steps = k // block_k
    out_dtype = out_dtype or a.dtype

    kernel = functools.partial(
        _matmul_epilogue_kernel, alpha=alpha, beta=beta, k_steps=k_steps
    )
    return pl.pallas_call(
        kernel,
        grid=(m // block_m, n // block_n, k_steps),
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_k, block_n), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
    )(a, b, d)
