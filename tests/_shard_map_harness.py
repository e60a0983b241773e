"""Child process for tests/test_shard_map.py (NOT a test file itself).

Forces an 8-host-device world BEFORE importing jax, builds the
(pod=2, data=2, model=2) debug mesh, and runs every kernel three ways in
this one process:

* plain jit with no routing installed — on an 8-device world this executes
  on device 0 only, i.e. it IS the single-device Pallas path;
* jit under ``kernel_partitioning(kernel_specs(mesh))`` inside the mesh —
  the shard_mapped multi-device path;
* the jitted jnp oracle from :mod:`repro.kernels.ref`.

The shard_mapped outputs must be **bitwise** equal to the single-device
Pallas outputs (padding happens inside the mapped region on local shapes,
so sharding never changes any element's arithmetic) and allclose to the
oracle. The flash VJP runs under the production composition —
``vmap(spmd_axis_name='pod')`` over workers + ``lax.scan`` + ``remat`` —
and asserts the batch-local grads (dq/dk/dv) bitwise; the fused SSD's VJP
runs under the same composition, its five gradients bitwise.

Prints one JSON object on the last stdout line.
"""
import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 " + os.environ.get("XLA_FLAGS", "")
)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

# ruff: noqa: E402  (XLA_FLAGS must precede any jax-touching import)
import json

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.kernels import ops, ref
from repro.kernels.flash_attention import gqa_flash_attention, paged_decode_attention
from repro.kernels.partition import kernel_partitioning
from repro.kernels.ssd import ssd_intra_chunk
from repro.launch.mesh import make_debug_mesh
from repro.launch.sharding import kernel_specs
from repro.models.ssm import ssd_intra_chunk_xla

MESH = make_debug_mesh(data=2, model=2, pod=2)
PARTS = kernel_specs(MESH)


def bitwise(a, b) -> bool:
    return all(
        bool((np.asarray(x) == np.asarray(y)).all())
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def close(a, b, tol=2e-5) -> bool:
    return all(
        bool(np.allclose(np.asarray(x), np.asarray(y), rtol=tol, atol=tol))
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def sharded(fn, *args):
    """Run ``jit(fn)`` with the kernel routing installed on the mesh."""
    with kernel_partitioning(PARTS), jax.set_mesh(MESH):
        return jax.tree.map(lambda x: np.asarray(x), jax.jit(fn)(*args))


def single(fn, *args):
    """Plain jit, no routing: the single-device Pallas path (device 0)."""
    return jax.tree.map(lambda x: np.asarray(x), jax.jit(fn)(*args))


def main() -> dict:
    out: dict = {"devices": jax.device_count(),
                 "mesh": dict(zip(MESH.axis_names, MESH.devices.shape))}
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)

    # -- flash attention forward -------------------------------------------
    B, S, H, KV, hd = 4, 64, 4, 2, 16
    q = jax.random.normal(k1, (B, S, H, hd), jnp.float32)
    k = jax.random.normal(k2, (B, S, KV, hd), jnp.float32)
    v = jax.random.normal(k3, (B, S, KV, hd), jnp.float32)

    def flash(q, k, v):
        return gqa_flash_attention(q, k, v, causal=True, block_q=16, block_kv=32)

    one = single(flash, q, k, v)
    out["flash_fwd"] = {
        "bitwise": bitwise(sharded(flash, q, k, v), one),
        "vs_ref": close(one, single(
            lambda q, k, v: ref.gqa_attention_ref(q, k, v, causal=True), q, k, v)),
    }

    # -- flash VJP under vmap(spmd)+scan+remat -----------------------------
    Kw = 2
    qk = jax.random.normal(k1, (Kw, B, S, H, hd), jnp.float32)
    kk = jax.random.normal(k2, (Kw, B, S, KV, hd), jnp.float32)
    vk = jax.random.normal(k3, (Kw, B, S, KV, hd), jnp.float32)

    def loss_one(q, k, v):
        @jax.checkpoint
        def step(c, _):
            return c + jnp.sum(flash(q, k, v) ** 2), None

        tot, _ = jax.lax.scan(step, 0.0, jnp.arange(2))
        return tot

    def grads(spmd):
        g = jax.grad(loss_one, argnums=(0, 1, 2))
        return (jax.vmap(g, spmd_axis_name=spmd) if spmd else jax.vmap(g))

    gref = single(grads(None), qk, kk, vk)
    with kernel_partitioning(PARTS), jax.set_mesh(MESH):
        shard = NamedSharding(MESH, P("pod"))
        args = [jax.device_put(x, shard) for x in (qk, kk, vk)]
        gout = jax.tree.map(lambda x: np.asarray(x),
                            jax.jit(grads("pod"))(*args))
    out["flash_vjp"] = {
        name: bool((a == b).all())
        for name, a, b in zip(("dq", "dk", "dv"), gref, gout)}
    out["flash_vjp"]["bitwise"] = all(out["flash_vjp"].values())

    # -- wire quantize / dequantize ----------------------------------------
    x = jax.random.normal(k1, (32, 40), jnp.float32)

    def quant(x):
        return ops.quantize_rowwise(x, bits=4)

    rq = single(quant, x)
    deq_ref, _, lo_ref, scale_ref = single(
        lambda x: ref.rowwise_quantize_ref(x, 4), x)
    out["quantize"] = {
        "bitwise": bitwise(sharded(quant, x), rq),
        "vs_ref": close((rq[0], rq[2], rq[3]), (deq_ref, lo_ref, scale_ref)),
    }

    def deq(c, lo, s):
        return ops.dequantize_rowwise(c, lo, s)

    rd = single(deq, rq[1], rq[2], rq[3])
    out["dequantize"] = {
        "bitwise": bitwise(sharded(deq, rq[1], rq[2], rq[3]), rd),
        "vs_ref": close(rd, single(ref.rowwise_dequantize_ref,
                                   rq[1], rq[2], rq[3])),
    }

    # -- Newton-Schulz (L=4 stack: local bsz 2 on the 2-way 'data' axis,
    #    so BOTH paths take _ns_stack's vmap branch) ------------------------
    g = jax.random.normal(k2, (4, 24, 16), jnp.float32)

    def ns(g):
        return ops.ns_orthogonalize(g, block=8)

    rn = single(ns, g)
    out["ns_orthogonalize"] = {
        "bitwise": bitwise(sharded(ns, g), rn),
        "vs_ref": close(rn, single(ref.ns_orthogonalize_ref, g), tol=5e-2),
    }

    # -- fused outer update -------------------------------------------------
    t = jax.random.normal(k1, (24, 32), jnp.float32)
    p = jax.random.normal(k2, (24, 32), jnp.float32)
    u = jax.random.normal(k3, (24, 32), jnp.float32)

    def outer(t, p, u):
        return ops.nesterov_update(t, p, u, lr=0.7, momentum=0.9, block=64)

    ro = single(outer, t, p, u)
    out["outer_update"] = {
        "bitwise": bitwise(sharded(outer, t, p, u), ro),
        "vs_ref": close(ro, single(
            lambda t, p, u: ref.nesterov_update_ref(t, p, u, lr=0.7, momentum=0.9),
            t, p, u)),
    }

    # -- paged decode over a ragged page table ------------------------------
    pool, ps = 16, 8
    qp = jax.random.normal(k1, (4, 4, 16), jnp.float32)
    kp = jax.random.normal(k2, (pool, ps, 2, 16), jnp.float32)
    vp = jax.random.normal(k3, (pool, ps, 2, 16), jnp.float32)
    tbl = jnp.array([[1, 2, 0], [3, 0, 0], [4, 5, 6], [7, 0, 0]], jnp.int32)
    lens = jnp.array([12, 5, 22, 8], jnp.int32)

    def paged(q, kp, vp, tbl, lens):
        return paged_decode_attention(q, kp, vp, tbl, lens, impl="pallas")

    rp = single(paged, qp, kp, vp, tbl, lens)
    out["paged_decode"] = {
        "bitwise": bitwise(sharded(paged, qp, kp, vp, tbl, lens), rp),
        "vs_ref": close(rp, single(ref.paged_attention_ref,
                                   qp, kp, vp, tbl, lens)),
    }

    # -- intra-chunk SSD VJP under vmap(spmd)+remat (K=2 workers, B=4
    #    sequences of 2 chunks: the batch axis splits over 'data') ----------
    xs = jax.random.normal(k1, (Kw, 4, 2, 128, 2, 64), jnp.float32)
    bs = jax.random.normal(k2, (Kw, 4, 2, 128, 64), jnp.float32)
    cs = jax.random.normal(k3, (Kw, 4, 2, 128, 64), jnp.float32)
    dts = jax.nn.softplus(jax.random.normal(k1, (Kw, 4, 2, 128, 2)) - 3.0)
    dAs = dts * -jnp.array([1.0, 16.0])
    w = jax.random.normal(k2, xs.shape[1:])  # a linear loss: dY = w exactly

    def ssd_loss(x, dt, b, c, dA):
        y = jax.checkpoint(lambda *a: ssd_intra_chunk(
            *a[:4], jnp.cumsum(a[4], axis=2)))(x, dt, b, c, dA)
        return jnp.sum(w * y)

    def ssd_grads(loss, spmd):
        g = jax.grad(loss, argnums=(0, 1, 2, 3, 4))
        return jax.vmap(g, spmd_axis_name=spmd) if spmd else jax.vmap(g)

    ssd_args = (xs, dts, bs, cs, dAs)
    gs = single(ssd_grads(ssd_loss, None), *ssd_args)
    with kernel_partitioning(PARTS), jax.set_mesh(MESH):
        shard = NamedSharding(MESH, P("pod"))
        args = [jax.device_put(x, shard) for x in ssd_args]
        gsh = jax.tree.map(np.asarray, jax.jit(ssd_grads(ssd_loss, "pod"))(*args))

    def ssd_loss_ref(x, dt, b, c, dA):
        return jnp.sum(w * ssd_intra_chunk_xla(x, dt, b, c, dA))

    with jax.default_matmul_precision("highest"):
        gr = single(ssd_grads(ssd_loss_ref, None), *ssd_args)
    # the kernel rounds MXU operands to bf16 (tests/test_ssd_kernel.py)
    rel = max(float(np.max(np.abs(a - b)) / np.max(np.abs(b))) for a, b in zip(gs, gr))
    out["ssd"] = {"bitwise": bitwise(gsh, gs), "vs_ref": rel < 2.0 ** -6}
    return out


if __name__ == "__main__":
    print(json.dumps(main()))
