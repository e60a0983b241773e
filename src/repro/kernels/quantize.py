"""Row-wise linear quantize→dequantize Pallas kernels + code bit-packing.

The paper argues row-wise quantization is the production choice because each
row carries its own (min, scale) metadata and the dequantize-reduce-quantize
in the all-to-all reduce-scatter parallelizes per row (§6.3 "Global v.s.
Row-wise"). The per-row min/max is an XLA reduction; the encode kernel then
fuses code assignment and dequantization in one VMEM pass per
[block_rows, BLOCK_COLS] tile.
Codes are emitted alongside the dequantized values so the wire format
(bit-packed uint8 codes + fp32 row metadata) is materialized for the
collective layer; :func:`rowwise_dequantize` is the receiver side (codes +
metadata -> values, the reconstruction both the reduce and the EF residual
see). :func:`pack_codes` / :func:`unpack_codes` implement the on-the-wire
byte layout: for bits in {1, 2, 4, 8}, 8/bits codes share one byte.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.sharding import PartitionSpec as P

from repro.kernels.partition import KernelPartitioning, axes_for


def rowwise_specs(part: KernelPartitioning, rows: int) -> tuple[P, P]:
    """(matrix_spec [rows, n], meta_spec [rows, 1]) for the shard_mapped
    encode/decode: rows are independent (each carries its own lo/scale), so
    the row axis shards over the preference — worker-stacked leaves fold K
    into rows before the kernel, hence ('pod', 'data'). Columns stay whole
    (the per-row min/max reduction spans them). Padding to block_rows
    multiples happens inside the mapped region, so per-row arithmetic is
    unchanged by the split."""
    axes = axes_for(part, rows, part.quantize_axes)
    r = axes or None
    return P(r, None), P(r, None)


# Rows per tile: a multiple of 32, the sublane tiling of the u8 codes.
DEFAULT_BLOCK_ROWS = 32
# Lanes per tile when a row is longer: a whole-leaf row (the non-rowwise
# layout, one row per worker) runs to tens of millions of columns and never
# fits VMEM, so both kernels tile the column axis too.
BLOCK_COLS = 8192


def _tiles(m: int, n: int, block_rows: int) -> tuple[int, int]:
    """(rows, cols) of one tile: the whole axis when it is short, else the
    block (the array is then padded to a multiple of it)."""
    return (m if m <= block_rows else block_rows,
            n if n <= BLOCK_COLS else BLOCK_COLS)


def _pad(x: jax.Array, br: int, bc: int) -> jax.Array:
    m, n = x.shape
    return jnp.pad(x, ((0, -m % br), (0, -n % bc)))


def _rowwise_quant_kernel(x_ref, lo_ref, scale_ref, deq_ref, code_ref):
    x = x_ref[...].astype(jnp.float32)  # [bm, bn]
    lo, scale = lo_ref[...], scale_ref[...]
    q = jnp.round((x - lo) / scale)
    # Mosaic casts between f32 and u8 only through i32
    code_ref[...] = q.astype(jnp.int32).astype(jnp.uint8)
    deq_ref[...] = (lo + q * scale).astype(deq_ref.dtype)


def rowwise_quantize(
    x: jax.Array,
    bits: int = 4,
    *,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: bool,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """x: [m, n] -> (dequantized, codes u8, lo [m, 1], scale [m, 1]).

    The per-row min/max is one XLA reduction ahead of the kernel (a row may
    span many column tiles); the kernel then encodes and dequantizes each
    [block_rows, BLOCK_COLS] tile in one VMEM pass. Any [m, n] is accepted:
    padding to whole tiles happens here, after the statistics."""
    assert bits <= 8, "codes are u8 on the wire"
    m, n = x.shape
    x32 = x.astype(jnp.float32)
    lo = jnp.min(x32, axis=1, keepdims=True)
    hi = jnp.max(x32, axis=1, keepdims=True)
    scale = (hi - lo) / ((1 << bits) - 1)
    scale = jnp.where(scale <= 0.0, 1.0, scale)
    br, bc = _tiles(m, n, block_rows)
    xp = _pad(x, br, bc)
    mp, np_ = xp.shape
    lop, sp = _pad(lo, br, 1), _pad(scale, br, 1)
    tile = pl.BlockSpec((br, bc), lambda i, j: (i, j))
    meta = pl.BlockSpec((br, 1), lambda i, j: (i, 0))
    deq, codes = pl.pallas_call(
        _rowwise_quant_kernel,
        grid=(mp // br, np_ // bc),
        in_specs=[tile, meta, meta],
        out_specs=[tile, tile],
        out_shape=[
            jax.ShapeDtypeStruct((mp, np_), x.dtype),
            jax.ShapeDtypeStruct((mp, np_), jnp.uint8),
        ],
        interpret=interpret,
    )(xp, lop, sp)
    return deq[:m, :n], codes[:m, :n], lo, scale


def _rowwise_dequant_kernel(code_ref, lo_ref, scale_ref, out_ref):
    # u8 -> f32 through i32, as in the encoder
    q = code_ref[...].astype(jnp.int32).astype(jnp.float32)  # [bm, bn]
    out_ref[...] = (lo_ref[...] + q * scale_ref[...]).astype(out_ref.dtype)


def rowwise_dequantize(
    codes: jax.Array,
    lo: jax.Array,
    scale: jax.Array,
    *,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: bool,
    out_dtype=jnp.float32,
) -> jax.Array:
    """The receiver side: (codes u8 [m, n], lo [m, 1], scale [m, 1]) -> values.

    One VMEM pass per [block_rows, BLOCK_COLS] tile; bit-identical to the
    jnp reconstruction ``lo + codes * scale`` (same ops, same order)."""
    m, n = codes.shape
    br, bc = _tiles(m, n, block_rows)
    cp = _pad(codes, br, bc)
    mp, np_ = cp.shape
    tile = pl.BlockSpec((br, bc), lambda i, j: (i, j))
    meta = pl.BlockSpec((br, 1), lambda i, j: (i, 0))
    (out,) = pl.pallas_call(
        _rowwise_dequant_kernel,
        grid=(mp // br, np_ // bc),
        in_specs=[tile, meta, meta],
        out_specs=[tile],
        out_shape=[jax.ShapeDtypeStruct((mp, np_), out_dtype)],
        interpret=interpret,
    )(cp, _pad(lo, br, 1), _pad(scale, br, 1))
    return out[:m, :n]


# ---------------------------------------------------------------------------
# Wire byte layout: bit-packing of quantization codes
# ---------------------------------------------------------------------------


def packed_width(n: int, bits: int) -> int:
    """Bytes per row of n codes at the given width (ceil; 1 byte/code when
    bits does not divide 8)."""
    if 8 % bits:
        return n
    per = 8 // bits
    return (n + per - 1) // per


def pack_codes(codes: jax.Array, bits: int) -> jax.Array:
    """[..., n] u8 codes -> [..., packed_width(n, bits)] u8 wire bytes.

    For bits in {1, 2, 4, 8} exactly 8/bits codes share one byte: the row is
    cut into 8/bits contiguous segments of w = packed_width bytes, and
    segment i fills bits [i*bits, (i+1)*bits) of every byte (byte j holds
    codes j, j+w, j+2w, ...). Contiguous segments keep the lane axis wide on
    a TPU, where an interleaved [..., w, 8/bits] view pads its last axis to
    128 lanes. Other widths ship one code per byte. Lossless:
    :func:`unpack_codes` inverts it exactly.
    """
    if 8 % bits:
        return codes
    per = 8 // bits
    n = codes.shape[-1]
    w = packed_width(n, bits)
    if w * per > n:
        codes = jnp.pad(codes, [(0, 0)] * (codes.ndim - 1) + [(0, w * per - n)])
    packed = codes[..., :w]
    for i in range(1, per):
        packed = packed | (codes[..., i * w:(i + 1) * w] << jnp.uint8(i * bits))
    return packed


def unpack_codes(packed: jax.Array, bits: int, n: int) -> jax.Array:
    """Inverse of :func:`pack_codes`: [..., packed] u8 -> [..., n] u8 codes."""
    if 8 % bits:
        return packed[..., :n]
    per = 8 // bits
    mask = jnp.uint8((1 << bits) - 1)
    parts = [(packed >> jnp.uint8(i * bits)) & mask for i in range(per)]
    return jnp.concatenate(parts, axis=-1)[..., :n]
