"""Blocked Pallas flash-attention — the training-hot-path kernel.

Every MuLoCo round runs K workers x H inner steps of transformer
forward/backward, so attention dominates the engine's roofline at production
sequence lengths. This kernel is the fused-SRAM answer (Dao et al., 2022,
lowered TPU-style a la the maxtext block kernels), following the same
pattern the repo already uses for Newton-Schulz (``kernels/matmul.py``) and
quantization (``kernels/quantize.py``):

* **GQA-native layout**: queries travel as ``[B*KV, G, S, hd]`` (G = H/KV
  query heads per KV head), K/V as ``[B*KV, S, hd]`` — each K/V tile is
  loaded into VMEM once per q block and shared by all G query heads, never
  materialized H/KV times. G leads the q tile, so the kernel merges
  ``[G, bq, hd] -> [G*bq, hd]`` along the sublane axis into one MXU operand
  (Mosaic refuses the ``[bq, G, hd]`` merge in bf16). Per-row softmax
  statistics (``lse``, ``dl``) travel as ``[B*KV, G, S, 1]`` for the same
  reason.
* **Online softmax**: fp32 ``m``/``l``/``acc`` accumulators live in VMEM
  scratch across the kv-block sweep; the epilogue normalizes once and also
  emits the per-row logsumexp for the backward pass. The MXU takes q, k, v,
  do and the probability and ds tiles in the operands' own dtype (bf16 in
  training) and accumulates in fp32; scores, softmax statistics and the
  dq/dk/dv accumulators stay fp32.
* **Full-block skipping**: the grid is built from an explicit *visit
  schedule* (:func:`attention_schedule`) carried in via scalar prefetch —
  kv blocks entirely above the causal diagonal or outside the sliding
  window are **never visited** (not merely masked), so the causal grid does
  ~half the work and a sliding-window grid O(window/S) of it. The schedule
  is plain Python over static shapes, so tests assert the visit count on
  the grid itself, not on timing.
* **Flash-style custom VJP**: the backward recomputes per-block
  probabilities from the saved logsumexp (O(S) residuals: q, k, v, o, lse —
  never an [S, S] tensor), matching the ``jax.checkpoint`` contract of the
  XLA blockwise fallback. Two kernels: a q-major sweep for dq and a
  kv-major sweep for dk/dv, both on the same skip schedule.

Like the other kernels, this runs in interpret mode off-TPU (the CPU test
target; :func:`repro.kernels.backend.pallas_interpret` decides). On
multi-device meshes the call sites consult the kernel partitioning context
(:mod:`repro.kernels.partition`): when the StepPlan
machinery routes a mesh, the custom-VJP call — forward and both backward
sweeps — is wrapped in ``shard_map`` over the fused [B*KV, ...] batch-head
axis (:func:`flash_specs`), so ``attn_impl='pallas'`` lowers under GSPMD
with bitwise-identical outputs. The visit schedule stays a closed-over
trace constant (replicated); the paged decode kernel co-shards the page
table with its batch-slot axis against a replicated KV pool
(:func:`paged_specs`).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from repro.kernels.backend import pallas_interpret
from repro.kernels.partition import (
    KernelPartitioning,
    active_partitioning,
    axes_for,
    shard_wrap,
)

NEG_INF = -2.0e38
DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_KV = 1024
# The backward sweeps hold several [G*bq, bkv] f32 tiles (scores, probs, dp,
# ds): 17 MiB at G=3 and the default blocks, past Mosaic's 16 MiB default
# scoped-VMEM limit. A v5e core has 128 MiB of VMEM.
_COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=64 * 1024 * 1024)


# ---------------------------------------------------------------------------
# The visit schedule: which (q-block, kv-block) pairs the grid executes.
# ---------------------------------------------------------------------------


def _block_visited(qi: int, kj: int, block_q: int, block_kv: int,
                   causal: bool, window: int) -> bool:
    """True when block (qi, kj) contains any unmasked (row, col) pair."""
    if causal and kj * block_kv > qi * block_q + block_q - 1:
        return False  # entirely above the diagonal
    if window and (qi * block_q) - (kj * block_kv + block_kv - 1) >= window:
        return False  # entirely left of the sliding window
    return True


def attention_schedule(nq: int, nkv: int, block_q: int, block_kv: int,
                       causal: bool, window: int,
                       skip: bool = True) -> list[tuple[int, int]]:
    """q-major list of visited (q-block, kv-block) pairs — this IS the grid.

    ``skip=False`` returns the full nq x nkv sweep (the no-skip oracle the
    block-skip tests compare against). For causal attention with
    ``block_q <= block_kv`` the visited count is at most
    ``nq*nkv/2 + nq`` — asserted here so every kernel launch proves its own
    grid bound.
    """
    pairs = [(qi, kj) for qi in range(nq) for kj in range(nkv)
             if not skip or _block_visited(qi, kj, block_q, block_kv, causal, window)]
    if skip and causal and not window and block_q <= block_kv:
        assert len(pairs) <= nq * nkv // 2 + nq, (len(pairs), nq, nkv)
    return pairs


def visited_kv_range(qi: int, nkv: int, block_q: int, block_kv: int,
                     causal: bool, window: int) -> tuple[int, int]:
    """Contiguous [lo, hi) kv-block range q-block ``qi`` must visit.

    Causal masking bounds ``hi`` (diagonal), the sliding window bounds
    ``lo``; both are static, so the XLA blockwise fallback scans exactly
    this range per q block.
    """
    visited = [kj for kj in range(nkv)
               if _block_visited(qi, kj, block_q, block_kv, causal, window)]
    assert visited, (qi, nkv, block_q, block_kv, causal, window)
    assert visited == list(range(visited[0], visited[-1] + 1)), "range not contiguous"
    return visited[0], visited[-1] + 1


def clamp_block(block: int, S: int) -> int:
    """A divisor of S that is <= block, found by halving — terminates at
    b=1 for any S (S % 1 == 0), so odd sequence lengths fall back to
    unit blocks rather than failing."""
    b = max(1, min(block, S))
    while S % b:
        b //= 2
    return b


def visited_fraction(S: int, block_q: int, block_kv: int,
                     causal: bool, window: int) -> float:
    """Fraction of the nq x nkv block grid the schedule visits — the
    roofline's attention-flops discount for both attention impls."""
    bq, bkv = clamp_block(block_q, S), clamp_block(block_kv, S)
    nq, nkv = S // bq, S // bkv
    return len(attention_schedule(nq, nkv, bq, bkv, causal, window)) / (nq * nkv)


@functools.lru_cache(maxsize=None)
def _sched_array(nq: int, nkv: int, block_q: int, block_kv: int,
                 causal: bool, window: int, kv_major: bool,
                 skip: bool) -> np.ndarray:
    """int32 [n, 4] rows (qi, kj, first, last) for the scalar-prefetch grid.

    q-major order for the forward/dq sweeps (first/last flag the edges of
    each q block's kv run); kv-major for the dk/dv sweep (flags per kv
    block's q run).
    """
    pairs = attention_schedule(nq, nkv, block_q, block_kv, causal, window,
                               skip=skip)
    group = 1 if kv_major else 0
    if kv_major:
        pairs = sorted(pairs, key=lambda p: (p[1], p[0]))
    sched = np.zeros((len(pairs), 4), np.int32)
    for g, (qi, kj) in enumerate(pairs):
        sched[g, 0], sched[g, 1] = qi, kj
        sched[g, 2] = 1 if (g == 0 or pairs[g][group] != pairs[g - 1][group]) else 0
        sched[g, 3] = 1 if (g == len(pairs) - 1
                            or pairs[g][group] != pairs[g + 1][group]) else 0
    return sched


# ---------------------------------------------------------------------------
# Kernels (q [BKV, G, S, hd]; k/v [BKV, S, hd]; fp32 accumulation in VMEM)
# ---------------------------------------------------------------------------


def _mask_and_positions(qi, kj, bq, bkv, G, causal, window):
    """Unmasked-entry predicate for the [G*bq, bkv] score tile (row
    ``g*bq + r`` is query row r of head g)."""
    rows = qi * bq + jax.lax.rem(
        jax.lax.broadcasted_iota(jnp.int32, (G * bq, bkv), 0), bq)
    cols = kj * bkv + jax.lax.broadcasted_iota(jnp.int32, (G * bq, bkv), 1)
    mask = jnp.ones((G * bq, bkv), bool)
    if causal:
        mask &= rows >= cols
    if window:
        mask &= rows - cols < window
    return mask


def _dot(a, b, contract):
    """MXU product of two operands in their own dtype (bf16 in training),
    accumulated in f32; ``contract`` = (a's dim, b's dim)."""
    return jax.lax.dot_general(a, b, (((contract[0],), (contract[1],)), ((), ())),
                               preferred_element_type=jnp.float32)


def _scores(q_ref, k_ref, bq, G, hd, scale):
    q = q_ref[0].reshape(G * bq, hd)
    return _dot(q, k_ref[0], (1, 1)) * scale


def _fwd_kernel(sched_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, bq, bkv, G, hd, causal, window, scale):
    g = pl.program_id(1)
    qi, kj = sched_ref[g, 0], sched_ref[g, 1]

    @pl.when(sched_ref[g, 2] == 1)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    s = _scores(q_ref, k_ref, bq, G, hd, scale)
    mask = _mask_and_positions(qi, kj, bq, bkv, G, causal, window)
    s = jnp.where(mask, s, NEG_INF)
    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    # explicit mask (not just exp of NEG_INF): keeps fully-masked blocks at
    # exactly zero contribution, which is what makes skipped == visited
    # bitwise (tests/test_attention.py::test_block_skipping_is_exact)
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_scr[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    v = v_ref[0]
    acc_new = acc_scr[...] * corr + _dot(p.astype(v.dtype), v, (1, 0))
    m_scr[...] = m_new
    l_scr[...] = l_new
    acc_scr[...] = acc_new

    @pl.when(sched_ref[g, 3] == 1)
    def _epilogue():
        l = jnp.maximum(l_new, 1e-30)
        o_ref[0] = (acc_new / l).reshape(G, bq, hd).astype(o_ref.dtype)
        lse_ref[0] = (m_new + jnp.log(l)).reshape(G, bq, 1)


def _probs(sched_ref, q_ref, k_ref, lse_ref, g, *, bq, bkv, G, hd,
           causal, window, scale):
    """Recompute the [G*bq, bkv] probability tile from the saved logsumexp."""
    qi, kj = sched_ref[g, 0], sched_ref[g, 1]
    s = _scores(q_ref, k_ref, bq, G, hd, scale)
    mask = _mask_and_positions(qi, kj, bq, bkv, G, causal, window)
    lse = lse_ref[0].reshape(G * bq, 1)
    return jnp.where(mask, jnp.exp(s - lse), 0.0)


def _dq_kernel(sched_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
               dq_ref, dq_scr, *, bq, bkv, G, hd, causal, window, scale):
    g = pl.program_id(1)

    @pl.when(sched_ref[g, 2] == 1)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    p = _probs(sched_ref, q_ref, k_ref, lse_ref, g, bq=bq, bkv=bkv, G=G,
               hd=hd, causal=causal, window=window, scale=scale)
    do = do_ref[0].reshape(G * bq, hd)
    dp = _dot(do, v_ref[0], (1, 1))
    ds = p * (dp - dl_ref[0].reshape(G * bq, 1))
    k = k_ref[0]
    dq_scr[...] += scale * _dot(ds.astype(k.dtype), k, (1, 0))

    @pl.when(sched_ref[g, 3] == 1)
    def _epilogue():
        dq_ref[0] = dq_scr[...].reshape(G, bq, hd).astype(dq_ref.dtype)


def _dkv_kernel(sched_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
                dk_ref, dv_ref, dk_scr, dv_scr, *, bq, bkv, G, hd,
                causal, window, scale):
    g = pl.program_id(1)

    @pl.when(sched_ref[g, 2] == 1)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    p = _probs(sched_ref, q_ref, k_ref, lse_ref, g, bq=bq, bkv=bkv, G=G,
               hd=hd, causal=causal, window=window, scale=scale)
    do = do_ref[0].reshape(G * bq, hd)
    dv_scr[...] += _dot(p.astype(do.dtype), do, (0, 0))
    dp = _dot(do, v_ref[0], (1, 1))
    ds = p * (dp - dl_ref[0].reshape(G * bq, 1))
    q = q_ref[0].reshape(G * bq, hd)
    dk_scr[...] += scale * _dot(ds.astype(q.dtype), q, (0, 0))

    @pl.when(sched_ref[g, 3] == 1)
    def _epilogue():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# pallas_call wrappers
# ---------------------------------------------------------------------------


def _grid_spec(sched: np.ndarray, BKV: int, bq: int, bkv: int, G: int,
               hd: int, extra_in: list, extra_out: list, scratch: list):
    """PrefetchScalarGridSpec shared by all three sweeps: the schedule rides
    as scalar prefetch and the index maps read (qi, kj) off it."""
    q_spec = pl.BlockSpec((1, G, bq, hd), lambda b, g, s: (b, 0, s[g, 0], 0))
    kv_spec = pl.BlockSpec((1, bkv, hd), lambda b, g, s: (b, s[g, 1], 0))
    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(BKV, sched.shape[0]),
        in_specs=[q_spec, kv_spec, kv_spec, *extra_in],
        out_specs=extra_out,
        scratch_shapes=scratch,
    )


def _fwd(q, k, v, *, causal, window, bq, bkv, scale, interpret, skip):
    BKV, G, S, hd = q.shape
    nq, nkv = S // bq, S // bkv
    sched = _sched_array(nq, nkv, bq, bkv, causal, window, False, skip)
    kernel = functools.partial(_fwd_kernel, bq=bq, bkv=bkv, G=G, hd=hd,
                               causal=causal, window=window, scale=scale)
    q_out = pl.BlockSpec((1, G, bq, hd), lambda b, g, s: (b, 0, s[g, 0], 0))
    lse_out = pl.BlockSpec((1, G, bq, 1), lambda b, g, s: (b, 0, s[g, 0], 0))
    grid_spec = _grid_spec(
        sched, BKV, bq, bkv, G, hd, extra_in=[],
        extra_out=[q_out, lse_out],
        scratch=[pltpu.VMEM((G * bq, 1), jnp.float32),
                 pltpu.VMEM((G * bq, 1), jnp.float32),
                 pltpu.VMEM((G * bq, hd), jnp.float32)])
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((BKV, G, S, hd), q.dtype),
                   jax.ShapeDtypeStruct((BKV, G, S, 1), jnp.float32)],
        interpret=interpret,
        compiler_params=_COMPILER_PARAMS,
    )(jnp.asarray(sched), q, k, v)


def _bwd(q, k, v, o, lse, do, *, causal, window, bq, bkv, scale, interpret,
         skip):
    BKV, G, S, hd = q.shape
    nq, nkv = S // bq, S // bkv
    # dl = rowsum(do * o): the only extra residual the flash backward needs
    dl = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1,
                 keepdims=True)
    do_spec = pl.BlockSpec((1, G, bq, hd), lambda b, g, s: (b, 0, s[g, 0], 0))
    row_spec = pl.BlockSpec((1, G, bq, 1), lambda b, g, s: (b, 0, s[g, 0], 0))
    kv_out = pl.BlockSpec((1, bkv, hd), lambda b, g, s: (b, s[g, 1], 0))
    kw = dict(bq=bq, bkv=bkv, G=G, hd=hd, causal=causal, window=window,
              scale=scale)

    sched_q = _sched_array(nq, nkv, bq, bkv, causal, window, False, skip)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **kw),
        grid_spec=_grid_spec(
            sched_q, BKV, bq, bkv, G, hd,
            extra_in=[do_spec, row_spec, row_spec],
            extra_out=[do_spec],
            scratch=[pltpu.VMEM((G * bq, hd), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((BKV, G, S, hd), q.dtype)],
        interpret=interpret,
        compiler_params=_COMPILER_PARAMS,
    )(jnp.asarray(sched_q), q, k, v, do, lse, dl)[0]

    sched_kv = _sched_array(nq, nkv, bq, bkv, causal, window, True, skip)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, **kw),
        grid_spec=_grid_spec(
            sched_kv, BKV, bq, bkv, G, hd,
            extra_in=[do_spec, row_spec, row_spec],
            extra_out=[kv_out, kv_out],
            scratch=[pltpu.VMEM((bkv, hd), jnp.float32),
                     pltpu.VMEM((bkv, hd), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((BKV, S, hd), k.dtype),
                   jax.ShapeDtypeStruct((BKV, S, hd), v.dtype)],
        interpret=interpret,
        compiler_params=_COMPILER_PARAMS,
    )(jnp.asarray(sched_kv), q, k, v, do, lse, dl)
    return dq, dk, dv


@functools.lru_cache(maxsize=None)
def _flash_fn(causal: bool, window: int, bq: int, bkv: int, scale: float,
              interpret: bool, skip: bool):
    """custom_vjp'd [BKV, G, S, hd] attention for one static config."""

    @jax.custom_vjp
    def fn(q, k, v):
        return _fwd(q, k, v, causal=causal, window=window, bq=bq, bkv=bkv,
                    scale=scale, interpret=interpret, skip=skip)[0]

    def fwd(q, k, v):
        o, lse = _fwd(q, k, v, causal=causal, window=window, bq=bq, bkv=bkv,
                      scale=scale, interpret=interpret, skip=skip)
        return o, (q, k, v, o, lse)

    def bwd(res, do):
        q, k, v, o, lse = res
        dq, dk, dv = _bwd(q, k, v, o, lse, do, causal=causal, window=window,
                          bq=bq, bkv=bkv, scale=scale, interpret=interpret,
                          skip=skip)
        return dq, dk, dv

    fn.defvjp(fwd, bwd)
    return fn


# ---------------------------------------------------------------------------
# shard_map specs (consulted when the StepPlan machinery routes a mesh)
# ---------------------------------------------------------------------------


def flash_specs(part: KernelPartitioning, lead: int) -> tuple[P, P]:
    """(q_spec [lead, G, S, hd], kv_spec [lead, S, hd]) for the fused
    batch-head axis. ``lead = B*KV`` is B-major, so the ('data', 'model')
    preference aligns batch with 'data' and kv-heads with 'model'; S stays
    whole per device (the visit schedule is global over S). The specs serve
    forward and both backward sweeps — dq shards like q, dk/dv like k/v."""
    axes = axes_for(part, lead, part.flash_axes)
    a = axes or None
    return P(a, None, None, None), P(a, None, None)


def paged_specs(part: KernelPartitioning, batch: int) -> tuple[P, P, P, P]:
    """(q, page_table, lengths, pool) specs for paged decode.

    The batch-slot axis shards q [B, KV, G, hd], the page table
    [B, max_pages], and lengths [B] *together* — each device looks up its
    own slots' rows — while the KV pool stays replicated so any page id
    resolves locally. (Replicating the table against a sharded B would
    index the wrong rows; replicating the pool is what keeps the scalar-
    prefetched indices valid everywhere.)"""
    axes = axes_for(part, batch, part.paged_axes)
    b = axes or None
    return (P(b, None, None, None), P(b, None), P(b),
            P(None, None, None, None))


# ---------------------------------------------------------------------------
# Paged decode attention (the serving hot path)
# ---------------------------------------------------------------------------
#
# Serving keeps KV in a fixed pool of fixed-size pages
# (``src/repro/serving/paging.py``); a sequence owns an ordered page list and
# the decode step attends one q token against its own pages only. The page
# table plays exactly the role the visit schedule plays in training: it is a
# host-built int32 array, carried in via scalar prefetch, whose entries the
# index maps read to decide which KV tile each grid step loads — pages are
# the visit schedule one level up. Page 0 is the reserved *null page*
# (garbage scratch): table rows are 0-padded past a sequence's allocation,
# and every slot the mask rules out contributes exactly zero (the same
# explicit p-masking trick that makes block skipping bitwise inert).


def _paged_kernel(tbl_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr, *, ps, G, hd, window, scale, npages):
    b, j = pl.program_id(0), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)  # [G, hd]
    k = k_ref[0, 0].astype(jnp.float32)  # [ps, hd]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    # positions stored in page j of this sequence; the current token (at
    # position length-1) is already written, so valid = pos < length, plus
    # the sliding window lower bound when set
    pos = j * ps + jax.lax.broadcasted_iota(jnp.int32, (1, ps), 1)
    length = len_ref[b]
    mask = pos < length
    if window:
        mask &= pos > length - 1 - window
    s = jnp.where(mask, s, NEG_INF)
    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_scr[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    v = v_ref[0, 0].astype(jnp.float32)
    acc_new = acc_scr[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new
    acc_scr[...] = acc_new

    @pl.when(j == npages - 1)
    def _epilogue():
        o_ref[0, 0] = (acc_new / jnp.maximum(l_new, 1e-30)).astype(o_ref.dtype)


def _paged_decode_pallas(q, k_pages, v_pages, page_table, lengths, *,
                         window, interpret):
    B, KV, G, hd = q.shape
    ps = k_pages.shape[1]
    npages = page_table.shape[1]
    scale = 1.0 / math.sqrt(hd)
    # kernel layout: pages travel [n_pages, KV, ps, hd] so the (page, head)
    # tile is contiguous per grid step
    kp = k_pages.transpose(0, 2, 1, 3)
    vp = v_pages.transpose(0, 2, 1, 3)
    q_spec = pl.BlockSpec((1, 1, G, hd), lambda b, h, j, tbl, lens: (b, h, 0, 0))
    kv_spec = pl.BlockSpec((1, 1, ps, hd),
                           lambda b, h, j, tbl, lens: (tbl[b, j], h, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, KV, npages),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[q_spec],
        scratch_shapes=[pltpu.VMEM((G, 1), jnp.float32),
                        pltpu.VMEM((G, 1), jnp.float32),
                        pltpu.VMEM((G, hd), jnp.float32)])
    kernel = functools.partial(_paged_kernel, ps=ps, G=G, hd=hd,
                               window=window, scale=scale, npages=npages)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, KV, G, hd), q.dtype)],
        interpret=interpret,
    )(page_table.astype(jnp.int32), lengths.astype(jnp.int32), q, kp, vp)
    return out[0]


def _paged_decode_xla(q, k_pages, v_pages, page_table, lengths, *, window):
    """Gather fallback: dense jnp ops only, so GSPMD plans still lower."""
    B, KV, G, hd = q.shape
    ps = k_pages.shape[1]
    npages = page_table.shape[1]
    # [B, npages, ps, KV, hd] -> [B, npages*ps, KV, hd]
    kg = k_pages[page_table].reshape(B, npages * ps, KV, hd)
    vg = v_pages[page_table].reshape(B, npages * ps, KV, hd)
    s = jnp.einsum("bkgh,bskh->bkgs", q.astype(jnp.float32),
                   kg.astype(jnp.float32)) / math.sqrt(hd)
    pos = jnp.arange(npages * ps)[None, :]
    mask = pos < lengths[:, None]
    if window:
        mask &= pos > (lengths[:, None] - 1 - window)
    s = jnp.where(mask[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bkgs,bskh->bkgh", p, vg)


def paged_decode_attention(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                           page_table: jax.Array, lengths: jax.Array, *,
                           window: int = 0, impl: str = "xla") -> jax.Array:
    """One-token GQA attention against a paged KV cache.

    q ``[B, H, hd]`` (the new token per sequence slot, RoPE applied);
    k/v pages ``[n_pool_pages, page_size, KV, hd]``; ``page_table``
    ``[B, max_pages]`` int32 page ids per slot (0 = the reserved null page,
    padding past the allocation); ``lengths`` ``[B]`` int32 sequence lengths
    *including* the current token (already written to its page).
    Returns ``[B, H, hd]``.

    ``impl='pallas'`` grids over (B, KV, max_pages) with the page table as
    scalar prefetch — each grid step DMAs exactly one owned page;
    ``impl='xla'`` is the dense-gather fallback that lowers under GSPMD.
    """
    B, H, hd = q.shape
    KV = k_pages.shape[2]
    assert H % KV == 0, (H, KV)
    G = H // KV
    qg = q.reshape(B, KV, G, hd)
    if impl == "pallas":
        local = functools.partial(_paged_decode_pallas, window=window,
                                  interpret=pallas_interpret())
        part = active_partitioning()
        if part is not None:
            q_spec, tbl_spec, len_spec, pool_spec = paged_specs(part, B)
            local = shard_wrap(
                local, part,
                in_specs=(q_spec, pool_spec, pool_spec, tbl_spec, len_spec),
                out_specs=q_spec)
        o = local(qg, k_pages, v_pages, page_table, lengths)
    else:
        o = _paged_decode_xla(qg, k_pages, v_pages, page_table, lengths,
                              window=window)
    return o.reshape(B, H, hd)


# ---------------------------------------------------------------------------
# Public API (model-layer layout)
# ---------------------------------------------------------------------------


def gqa_flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True, window: int = 0,
                        block_q: int = DEFAULT_BLOCK_Q,
                        block_kv: int = DEFAULT_BLOCK_KV,
                        skip_blocks: bool = True) -> jax.Array:
    """Fused GQA flash attention.

    q ``[B, S, H, hd]``, k/v ``[B, S, KV, hd]`` -> ``[B, S, H, hd]``.
    Rows attend by absolute sequence position (the training layout, where
    ``positions == arange(S)``); ``window`` is the sliding-window width
    (0 = none) and only applies with ``causal=True`` in the model layer.
    Block sizes are clamped to divide S; ``skip_blocks=False`` runs the
    full (unskipped) grid — the oracle of the block-skip tests.
    """
    B, S, H, hd = q.shape
    KV = k.shape[2]
    assert H % KV == 0, (H, KV)
    G = H // KV
    bq = clamp_block(block_q, S)
    bkv = clamp_block(block_kv, S)
    scale = 1.0 / math.sqrt(hd)
    qg = q.reshape(B, S, KV, G, hd).transpose(0, 2, 3, 1, 4).reshape(B * KV, G, S, hd)
    kg = k.transpose(0, 2, 1, 3).reshape(B * KV, S, hd)
    vg = v.transpose(0, 2, 1, 3).reshape(B * KV, S, hd)
    fn = _flash_fn(bool(causal), int(window), bq, bkv, scale,
                   pallas_interpret(), bool(skip_blocks))
    part = active_partitioning()
    if part is not None:
        # shard_map OUTSIDE the custom_vjp: jax differentiates through the
        # mapped region, so the dq/dk/dv sweeps run under the same specs as
        # the forward (batch-local -> bitwise vs the single-device call)
        q_spec, kv_spec = flash_specs(part, B * KV)
        fn = shard_wrap(fn, part, in_specs=(q_spec, kv_spec, kv_spec),
                        out_specs=q_spec)
    o = fn(qg, kg, vg)
    return o.reshape(B, KV, G, S, hd).transpose(0, 3, 1, 2, 4).reshape(B, S, H, hd)
