"""Operations and bytes of one call of the Pallas flash-attention kernels
(``repro.kernels.flash_attention``): the causal forward sweep and the two
backward sweeps, dq and dk/dv, of grouped-query attention.

A call works on q [BKV, G, S, hd] (BKV: batch times key/value heads; G:
query heads per key/value head) and k, v [BKV, S, hd]. In the trace the
three are told apart by their types: each takes the block schedule (int32)
first and q second; the forward takes q, k, v and returns o with its
log-sum-exp [BKV, G, S, 1] in float32; dq and dk/dv take do, the
log-sum-exp and its row sums besides, and return one array shaped as q or
two shaped as k. Causal attention needs S (S + 1) / 2 (query,
key) pairs per query head, and each product over a pair costs 2 hd FLOPs:
the forward makes two (q.k, p.v), dq three (q.k again, do.v, ds.k), dk/dv
four (q.k again, p.do, do.v, ds.q). Blocks above the diagonal that the
schedule visits are not required work and are not counted. Bytes: every
operand read once and every result written once, as the trace types them.
"""
from __future__ import annotations

PRODUCTS = {"fwd": 2, "dq": 3, "dkv": 4}


def kind(results: list, operands: list) -> str | None:
    """Which sweep a custom call is, or None: the schedule (int32 [n, 4])
    comes first, then q, k, v (and for the backward do, the log-sum-exp and
    its row sums)."""
    if not operands or operands[0][0] != "s32" or len(operands[0][1]) != 2:
        return None
    dims = [d for _, d in results]
    if (len(operands) == 4 and len(results) == 2 and len(dims[0]) == 4
            and results[1] == ("f32", dims[0][:3] + (1,))):
        return "fwd"
    if len(operands) == 7 and len(results) == 1 and len(dims[0]) == 4:
        return "dq"
    if len(operands) == 7 and len(results) == 2 and len(dims[0]) == 3 and dims[0] == dims[1]:
        return "dkv"
    return None


def cost(results: list, operands: list):
    """(FLOPs, bytes) of one call, or None when it is not one of the three."""
    from bench.trace import nbytes

    which = kind(results, operands)
    if which is None:
        return None
    bkv, G, S, hd = operands[1][1]
    flops = 2.0 * hd * PRODUCTS[which] * bkv * G * S * (S + 1) / 2
    return flops, sum(nbytes(x) for x in operands) + sum(nbytes(x) for x in results)
