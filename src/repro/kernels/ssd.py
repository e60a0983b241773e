"""Fused intra-chunk SSD: Mamba2's diagonal blocks as one Pallas kernel.

Mamba2's chunked SSD (``repro.models.ssm.mamba_forward``) splits the scan
into chunks of Q positions. Inside a chunk, head h's output is a masked,
decayed attention over the chunk:

    L_h[i, j] = exp(dAcum[i, h] - dAcum[j, h])  for j <= i, else 0
    M_h       = (C B^T) * L_h                    [Q, Q]
    Y_h       = M_h @ (dt_h * x_h)               [Q, P]

XLA builds ``L`` and ``M`` as ``[B, Cc, H, Q, Q]`` float32 tensors in HBM
(537 MB each at mamba2-370m's 8 x 2048 batch), and its backward writes
``dM`` at the same shape. This kernel keeps every ``[Q, Q]`` tile in VMEM:

* **Layout.** The sequence is the minor (lane) axis: the kernel takes
  ``x^T`` ``[B, H*P, S]``, ``dt^T`` ``[B, H, S]``, ``B^T`` and ``C^T``
  ``[B, N, S]``, and returns ``Y_diag^T`` ``[B, H*P, S]``. That is the
  layout XLA gives most of the layer's float32 activations for the
  chunk-state products around the block, so most transposes at the call
  fold into layouts (on a v5e, one ``f32[H*P, B, S]`` copy and one output
  fusion per layer-step remain, about a third of the time under
  ``repro.ssd``). A grid step takes one (batch, chunk) pair and up
  to ``MAX_HEADS_PER_STEP`` heads: head h is rows ``[h*P, (h+1)*P)`` of a
  ``[Hg*P, Q]`` slab. ``dAcum`` travels as ``[B, S, H]`` (head h's column)
  and ``[B, H, S]`` (its row); a head's column and row are masked sums
  over the heads, which are exact.
* **dt in VMEM.** ``dt * x`` is formed per tile and never reaches HBM
  (XLA materialises the broadcast of ``dt`` otherwise); ``d dt`` is summed
  over the head's P rows in the backward.
* **Precision.** ``C B^T`` is formed once per step and reused for every
  head. Every MXU product takes bf16-rounded operands and accumulates in
  float32; every elementwise value (the decay, ``L``, ``M``, ``dM``, the
  gradients and accumulators) is float32.
* **Mesh.** Under a routed mesh (:mod:`repro.kernels.partition`) the call
  is shard_mapped over the batch axis ('data', as ``batch_shardings``
  splits it; K arrives through 'pod' as for flash).
* **Custom VJP.** The residuals are the inputs only (O(S), never a
  ``[Q, Q]`` tensor), as ``jax.checkpoint`` of the layer expects. The
  backward recomputes ``L`` and ``M`` per tile and forms
  ``d(dt x) = M^T dY``, ``dM = dY (dt x)^T``, ``dCB = sum_h dM * L``
  (accumulated over the grid's head groups in VMEM), ``dC = dCB B``,
  ``dB = dCB^T C`` and ``d dAcum = rowsum(G) - colsum(G)`` with
  ``G = dM * M``.

The jnp oracle is the XLA path of the same block,
:func:`repro.models.ssm.ssd_intra_chunk_xla`. Off-TPU the kernel runs in the
Pallas interpreter (:func:`repro.kernels.backend.pallas_interpret`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec

from repro.kernels.backend import pallas_interpret
from repro.kernels.partition import active_partitioning, axes_for, shard_wrap

LANES = 128
# Heads per grid step, at most: more amortise C B^T and the step overhead,
# fewer keep the unrolled kernel body short.
MAX_HEADS_PER_STEP = 8
_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=64 * 1024 * 1024)


def heads_per_step(H: int) -> int:
    """Heads in one grid step: the most, up to MAX_HEADS_PER_STEP, that
    divide H."""
    return max(hg for hg in range(1, min(H, MAX_HEADS_PER_STEP) + 1) if H % hg == 0)


def supported(Q: int, P: int, N: int) -> bool:
    """True for shapes the kernel compiles for: chunks of whole 128-lane
    tiles, and head and state widths of whole 8-row sublane tiles."""
    return Q % LANES == 0 and P % 8 == 0 and N % 8 == 0


def _dot(a, b, contract):
    """MXU product with bf16-rounded operands, accumulated in f32;
    ``contract`` = (a's dim, b's dim)."""
    return jax.lax.dot_general(
        a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
        (((contract[0],), (contract[1],)), ((), ())),
        preferred_element_type=jnp.float32)


def _causal(Q):
    return (jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
            >= jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1))


def _row(t_hq, sub, h):
    """Row ``h`` (a traced index) of an [H, Q] block, as [1, Q]: a masked
    sum over the heads, which is exact."""
    return jnp.sum(jnp.where(sub == h, t_hq, 0.0), axis=0, keepdims=True)


def _decay(a_qh, a_row, h, causal):
    """L_h [Q, Q] in f32: exp(a[i] - a[j]) on and below the diagonal, 0
    above, from the chunk's dAcum ``a_qh`` [Q, H] and head h's row."""
    lane = jax.lax.broadcasted_iota(jnp.int32, a_qh.shape, 1)
    col = jnp.sum(jnp.where(lane == h, a_qh, 0.0), axis=1, keepdims=True)
    return jnp.exp(jnp.where(causal, col - a_row, -jnp.inf))


def _fwd_kernel(ct_ref, bt_ref, aqh_ref, ahq_ref, dt_ref, xt_ref, yt_ref, *, Hg, P):
    h0 = pl.program_id(2) * Hg
    Q = xt_ref.shape[2]
    cb = _dot(ct_ref[0], bt_ref[0], (0, 0))  # C B^T [Q, Q]
    causal = _causal(Q)
    a_qh, a_hq, dt = aqh_ref[0], ahq_ref[0], dt_ref[0]
    sub = jax.lax.broadcasted_iota(jnp.int32, a_hq.shape, 0)
    for h in range(Hg):
        rows = slice(h * P, (h + 1) * P)
        m = cb * _decay(a_qh, _row(a_hq, sub, h0 + h), h0 + h, causal)
        xdt = xt_ref[0, rows, :] * _row(dt, sub, h0 + h)  # (dt x)^T [P, Q]
        yt_ref[0, rows, :] = _dot(xdt, m, (1, 1))  # (M dt x)^T


def _bwd_kernel(ct_ref, bt_ref, aqh_ref, ahq_ref, dt_ref, xt_ref, dyt_ref,
                dxt_ref, dct_ref, dbt_ref, daqh_ref, dahq_ref, ddt_ref, dcb_scr, *, Hg, P):
    g = pl.program_id(2)
    h0 = g * Hg
    Q = xt_ref.shape[2]
    ct, bt = ct_ref[0], bt_ref[0]
    cb = _dot(ct, bt, (0, 0))
    causal = _causal(Q)
    a_qh, a_hq, dt = aqh_ref[0], ahq_ref[0], dt_ref[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, a_qh.shape, 1)
    sub = jax.lax.broadcasted_iota(jnp.int32, a_hq.shape, 0)

    @pl.when(g == 0)
    def _init():
        dcb_scr[...] = jnp.zeros_like(dcb_scr)
        daqh_ref[0] = jnp.zeros_like(a_qh)
        dahq_ref[0] = jnp.zeros_like(a_hq)
        ddt_ref[0] = jnp.zeros_like(dt)

    dcb = dcb_scr[...]
    da_qh, da_hq, ddt = daqh_ref[0], dahq_ref[0], ddt_ref[0]
    for h in range(Hg):
        rows = slice(h * P, (h + 1) * P)
        xt, dyt = xt_ref[0, rows, :], dyt_ref[0, rows, :]
        dt_h = _row(dt, sub, h0 + h)
        decay = _decay(a_qh, _row(a_hq, sub, h0 + h), h0 + h, causal)
        m = cb * decay
        dm = _dot(dyt, xt * dt_h, (0, 0))  # dY (dt x)^T [Q, Q]
        dxdt = _dot(dyt, m, (1, 0))  # (M^T dY)^T [P, Q]
        dxt_ref[0, rows, :] = dxdt * dt_h
        ddt = jnp.where(sub == h0 + h, jnp.sum(dxdt * xt, axis=0, keepdims=True), ddt)
        dcb = dcb + dm * decay
        gm = dm * m
        da_qh = jnp.where(lane == h0 + h, jnp.sum(gm, axis=1, keepdims=True), da_qh)
        da_hq = jnp.where(sub == h0 + h, jnp.sum(gm, axis=0, keepdims=True), da_hq)
    dcb_scr[...] = dcb
    daqh_ref[0] = da_qh
    dahq_ref[0] = da_hq
    ddt_ref[0] = ddt

    @pl.when(g == pl.num_programs(2) - 1)
    def _finish():
        dct_ref[0] = _dot(bt, dcb, (1, 1))  # (dCB B)^T
        dbt_ref[0] = _dot(ct, dcb, (1, 0))  # (dCB^T C)^T


def _specs(Q, H, N, Hg, P):
    """Blocks of a (b, c, head group) step, with the sequence on lanes:
    the chunk's [N, Q] of B^T and C^T, its [Q, H] and [H, Q] of dAcum and
    [H, Q] of dt, and the group's [Hg*P, Q] slab of x^T."""
    nq = pl.BlockSpec((1, N, Q), lambda b, c, g: (b, 0, c))
    qh = pl.BlockSpec((1, Q, H), lambda b, c, g: (b, c, 0))
    hq = pl.BlockSpec((1, H, Q), lambda b, c, g: (b, 0, c))
    slab = pl.BlockSpec((1, Hg * P, Q), lambda b, c, g: (b, g, c))
    return nq, qh, hq, slab


def _fwd(xt, dtt, bt, ct, a, *, Q, P, interpret):
    B, HP, S = xt.shape
    H, N = HP // P, bt.shape[1]
    Hg = heads_per_step(H)
    nq, qh, hq, slab = _specs(Q, H, N, Hg, P)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, Hg=Hg, P=P),
        grid=(B, S // Q, H // Hg),
        in_specs=[nq, nq, qh, hq, hq, slab],
        out_specs=slab,
        out_shape=jax.ShapeDtypeStruct((B, HP, S), jnp.float32),
        interpret=interpret,
        compiler_params=_COMPILER_PARAMS,
    )(ct, bt, a, jnp.swapaxes(a, 1, 2), dtt, xt)


def _bwd(xt, dtt, bt, ct, a, dyt, *, Q, P, interpret):
    B, HP, S = xt.shape
    H, N = HP // P, bt.shape[1]
    Hg = heads_per_step(H)
    nq, qh, hq, slab = _specs(Q, H, N, Hg, P)
    f32 = jnp.float32
    dxt, dct, dbt, da_qh, da_hq, ddtt = pl.pallas_call(
        functools.partial(_bwd_kernel, Hg=Hg, P=P),
        grid=(B, S // Q, H // Hg),
        in_specs=[nq, nq, qh, hq, hq, slab, slab],
        out_specs=[slab, nq, nq, qh, hq, hq],
        out_shape=[jax.ShapeDtypeStruct((B, HP, S), f32),
                   jax.ShapeDtypeStruct((B, N, S), f32),
                   jax.ShapeDtypeStruct((B, N, S), f32),
                   jax.ShapeDtypeStruct((B, S, H), f32),
                   jax.ShapeDtypeStruct((B, H, S), f32),
                   jax.ShapeDtypeStruct((B, H, S), f32)],
        scratch_shapes=[pltpu.VMEM((Q, Q), f32)],
        interpret=interpret,
        compiler_params=_COMPILER_PARAMS,
    )(ct, bt, a, jnp.swapaxes(a, 1, 2), dtt, xt, dyt)
    # d a_i collects G's row i (as the minuend) and column i (subtrahend)
    return dxt, ddtt, dbt, dct, da_qh - jnp.swapaxes(da_hq, 1, 2)


@functools.lru_cache(maxsize=None)
def _ssd_fn(Q: int, P: int, interpret: bool):
    """custom_vjp'd intra-chunk SSD on the kernel layout, sequence minor:
    x^T [B, H*P, S], dt^T [B, H, S], B^T and C^T [B, N, S], dAcum
    [B, S, H] -> Y_diag^T [B, H*P, S], all f32."""

    @jax.custom_vjp
    def fn(*args):
        return _fwd(*args, Q=Q, P=P, interpret=interpret)

    def fwd(*args):
        return _fwd(*args, Q=Q, P=P, interpret=interpret), args

    def bwd(res, dyt):
        return _bwd(*res, dyt, Q=Q, P=P, interpret=interpret)

    fn.defvjp(fwd, bwd)
    return fn


def ssd_intra_chunk(x: jax.Array, dt: jax.Array, Bc: jax.Array, Cc: jax.Array,
                    dAcum: jax.Array, *, interpret: bool | None = None) -> jax.Array:
    """Y_diag of the chunked SSD, fused.

    ``x`` ``[B, Cc, Q, H, P]``, ``dt`` ``[B, Cc, Q, H]``, ``Bc``/``Cc``
    ``[B, Cc, Q, N]``, ``dAcum`` (the in-chunk cumulative sum of dt * A)
    ``[B, Cc, Q, H]`` -> ``Y_diag`` ``[B, Cc, Q, H, P]`` float32, the value
    of :func:`repro.models.ssm.ssd_intra_chunk_xla`. The shape must pass
    :func:`supported` (``repro.models.ssm.ssd_path`` checks it).
    ``interpret`` defaults to :func:`pallas_interpret`.
    """
    B, C, Q, H, P = x.shape
    N = Bc.shape[-1]
    S = C * Q
    if interpret is None:
        interpret = pallas_interpret()
    fn = _ssd_fn(Q, P, bool(interpret))
    part = active_partitioning()
    if part is not None:
        # shard_map outside the custom_vjp, as the flash kernel does: the
        # sequences are independent, so each device runs its own
        spec = PartitionSpec(axes_for(part, B, ("data",)) or None, None, None)
        fn = shard_wrap(fn, part, in_specs=(spec,) * 5, out_specs=spec)

    def seq_minor(t, width):  # [B, Cc, Q, ...] -> [B, width, S]
        return jnp.swapaxes(t.reshape(B, S, width).astype(jnp.float32), 1, 2)

    yt = fn(seq_minor(x, H * P), seq_minor(dt, H), seq_minor(Bc, N), seq_minor(Cc, N),
            dAcum.reshape(B, S, H).astype(jnp.float32))
    return jnp.swapaxes(yt, 1, 2).reshape(B, C, Q, H, P)
