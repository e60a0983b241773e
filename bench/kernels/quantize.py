"""Operations and bytes of one call of the Pallas rowwise quantize and
dequantize kernels (``repro.kernels.quantize``), the two ends of the
compressed pseudogradient's wire.

The quantizer reads x [m, n] in float32 and each row's lo and scale
[m, 1], and writes the reconstruction [m, n] in float32 and the codes
[m, n] in uint8; the dequantizer reads codes, lo and scale and writes the
reconstruction. In the trace they are told apart by what they return: a
(float32, uint8) pair of one shape, or one float32 [m, n] from uint8 codes.
A handful of elementwise operations per element is nothing beside the
bytes, so the operations are not counted and the bound is the bytes: every
operand read once and every result written once, at the tiled sizes the
kernel is called with.
"""
from __future__ import annotations


def kind(results: list, operands: list) -> str | None:
    if (len(results) == 2 and [dt for dt, _ in results] == ["f32", "u8"]
            and results[0][1] == results[1][1] and len(results[0][1]) == 2):
        return "quantize"
    if (len(results) == 1 and results[0][0] == "f32" and len(results[0][1]) == 2
            and operands and operands[0] == ("u8", results[0][1])):
        return "dequantize"
    return None


def cost(results: list, operands: list):
    """(0, bytes) of one call, or None when it is neither kernel."""
    from bench.trace import nbytes

    which = kind(results, operands)
    if which is None:
        return None
    m, n = results[0][1]
    meta = 2 * nbytes(("f32", (m, 1)))
    if which == "quantize":
        return 0.0, nbytes(("f32", (m, n))) + meta + sum(nbytes(r) for r in results)
    return 0.0, nbytes(("u8", (m, n))) + meta + nbytes(results[0])
