"""Mosaic compiles every main-path Pallas kernel for a TPU v5e.

The rest of the suite runs the kernels in the Pallas interpreter, which
accepts layouts, casts and tile shapes that the TPU kernel compiler refuses.
These tests compile each kernel with ``interpret=False`` for a v5e chip that
is described (``jax.experimental.topologies``) but not attached, at the
widths of ``smollm-135m`` (d_model 576, 9 query / 3 KV heads of 64, d_ff
1536, 30 layers) and the chip-smoke shapes (K=4 workers, 4 sequences of 2048
per worker). Nothing runs: a pass means the compiler accepted the kernel and
its VMEM footprint, not that it is fast or right (the interpret-mode tests
and the chip run cover results). The fused SSD kernel compiles at the widths
of ``mamba2-370m`` (8 sequences of 2048 in chunks of 256, 32 heads of 64,
state 128, vmapped over K=1) and of ``zamba2-2.7b`` (80 heads, state 64).

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and the suite runs under
several xdist workers that each import every test file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import (
    DEFAULT_BLOCK_KV,
    DEFAULT_BLOCK_Q,
    _flash_fn,
    _paged_decode_pallas,
)
from repro.kernels.matmul import matmul_epilogue
from repro.kernels.outer_update import fused_nesterov_update
from repro.kernels.quantize import rowwise_dequantize, rowwise_quantize
from repro.kernels import ssd
from repro.kernels.ssd import ssd_intra_chunk

# smollm-135m widths and the chip-smoke batch
K, B, S = 4, 4, 2048
L, D, FF = 30, 576, 1536
KV, G, HD = 3, 3, 64


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler installed here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip; keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(shp, dt, sharding=sharding)
            for shp, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_flash_fwd_and_bwd_compile(one_chip, dtype):
    """Forward, dq sweep and dk/dv sweep at G=3, hd=64, S=2048 with the
    default 512 x 1024 blocks and the causal block-skip schedule."""
    fn = _flash_fn(True, 0, 512, 1024, HD ** -0.5, False, True)

    def fwd_bwd(q, k, v):
        o, vjp = jax.vjp(fn, q, k, v)
        return o, vjp(jnp.sin(o.astype(jnp.float32)).astype(o.dtype))

    dt = jnp.dtype(dtype)
    _compile(fwd_bwd, one_chip, ((B * KV, G, S, HD), dt),
             ((B * KV, S, HD), dt), ((B * KV, S, HD), dt))


def test_flash_vmapped_bf16_compiles_at_default_blocks(one_chip):
    """The training cell's call: bf16 operands, vmapped over K=4 workers,
    [B*KV, G, S, hd] per worker, at the default blocks; forward, dq and
    dk/dv sweeps."""
    fn = _flash_fn(True, 0, DEFAULT_BLOCK_Q, DEFAULT_BLOCK_KV, HD ** -0.5,
                   False, True)

    def fwd_bwd(q, k, v):
        o, vjp = jax.vjp(fn, q, k, v)
        return o, vjp(jnp.sin(o.astype(jnp.float32)).astype(o.dtype))

    dt = jnp.bfloat16
    _compile(jax.vmap(fwd_bwd), one_chip, ((K, B * KV, G, S, HD), dt),
             ((K, B * KV, S, HD), dt), ((K, B * KV, S, HD), dt))


def test_paged_decode_compiles(one_chip):
    """One decode token per slot against a 128-page pool of 16-slot pages
    (the serve CLI's defaults)."""
    def decode(q, kp, vp, tbl, lens):
        return _paged_decode_pallas(q, kp, vp, tbl, lens, window=0,
                                    interpret=False)

    _compile(decode, one_chip, ((4, KV, G, HD), jnp.bfloat16),
             ((128, 16, KV, HD), jnp.bfloat16),
             ((128, 16, KV, HD), jnp.bfloat16),
             ((4, 32), jnp.int32), ((4,), jnp.int32))


@pytest.mark.parametrize("m,k,n", [(640, 1536, 640), (640, 640, 640),
                                   (640, 640, 1536)])
def test_ns_matmul_compiles(one_chip, m, k, n):
    """The three matmuls of one Newton-Schulz iteration on the 576 x 1536
    MLP matrix, padded to 128-multiples as ``ops.matmul`` does."""
    def mm(a, b, d):
        return matmul_epilogue(a, b, d, alpha=2.0, beta=-1.5, interpret=False)

    _compile(mm, one_chip, ((m, k), jnp.float32), ((k, n), jnp.float32),
             ((m, n), jnp.float32))


# K-folded wire rows: rowwise (one row per matrix row of every worker) and
# whole-leaf (one row per worker, the default non-rowwise layout)
WIRE_SHAPES = [(K * L * D, FF), (K, L * D * FF), (K, 49152 * D)]


@pytest.mark.parametrize("m,n", WIRE_SHAPES)
def test_rowwise_quantize_compiles(one_chip, m, n):
    def quant(x):
        return rowwise_quantize(x, 4, interpret=False)

    _compile(quant, one_chip, ((m, n), jnp.float32))


@pytest.mark.parametrize("m,n", WIRE_SHAPES)
def test_rowwise_dequantize_compiles(one_chip, m, n):
    def deq(codes, lo, scale):
        return rowwise_dequantize(codes, lo, scale, interpret=False)

    _compile(deq, one_chip, ((m, n), jnp.uint8), ((m, 1), jnp.float32),
             ((m, 1), jnp.float32))


def test_outer_update_compiles(one_chip):
    """The fused Nesterov outer update over the flattened MLP stack."""
    n = L * D * FF

    def outer(t, p, u):
        return fused_nesterov_update(t, p, u, lr=0.7, momentum=0.9,
                                     interpret=False)

    _compile(outer, one_chip, ((n,), jnp.float32), ((n,), jnp.float32),
             ((n,), jnp.float32))


# mamba2-370m's SSD at the benchmark cell's batch: 8 x 2048 in chunks of 256
SSD_B, SSD_CC, SSD_Q, SSD_P = 8, 8, 256, 64


@pytest.mark.parametrize("H,N", [(32, 128), (80, 64)], ids=["mamba2", "zamba2"])
def test_ssd_fwd_and_bwd_compile(one_chip, H, N):
    """The fused SSD forward and backward kernels, vmapped over K=1."""
    def fwd_bwd(*args):
        y, vjp = jax.vjp(lambda *z: ssd_intra_chunk(*z, interpret=False), *args)
        return y, vjp(jnp.sin(y))

    lead = (1, SSD_B, SSD_CC, SSD_Q)
    compiled = _compile(jax.vmap(fwd_bwd), one_chip, ((*lead, H, SSD_P), jnp.float32),
                        ((*lead, H), jnp.float32), ((*lead, N), jnp.float32),
                        ((*lead, N), jnp.float32), ((*lead, H), jnp.float32))
    assert compiled.as_text().count("tpu_custom_call") == 2


def test_ssd_layer_holds_no_chunk_square_buffer(one_chip, monkeypatch):
    """One mamba2-370m layer's ``grad(checkpoint(mamba_forward))`` on the
    kernel path: no f32 ``[..., 256, 256]`` buffer outside the kernel
    (the XLA path holds ``f32[8,8,32,256,256]``)."""
    from repro.configs import get_config
    from repro.models import ssm

    # steer the backend checks, which see the CPU here, to the TPU's path
    monkeypatch.setattr(ssm, "pallas_interpret", lambda: False)
    monkeypatch.setattr(ssd, "pallas_interpret", lambda: False)
    cfg = get_config("mamba2-370m")
    S = SSD_CC * SSD_Q
    assert ssm.ssd_path(cfg, S) == "pallas"
    params = jax.eval_shape(lambda: ssm.init_mamba(jax.random.PRNGKey(0), cfg))

    def loss(p, x):
        y = jax.checkpoint(lambda p, x: ssm.mamba_forward(p, cfg, x))(p, x)
        return jnp.sum(y.astype(jnp.float32))

    shapes = [((1, *a.shape), a.dtype) for a in jax.tree.leaves(params)]
    treedef = jax.tree.structure(params)

    def grad(x, *leaves):
        return jax.vmap(jax.grad(loss))(jax.tree.unflatten(treedef, leaves), x)

    compiled = _compile(grad, one_chip, ((1, SSD_B, S, cfg.d_model), jnp.bfloat16),
                        *shapes)
    text = compiled.as_text()
    square = re.findall(r"f32\[[0-9,]*256,256\]", text)
    assert not square, sorted(set(square))
    kernels = re.findall(r'tpu_custom_call.*op_name="([^"]*)"', text)
    assert kernels and all("repro.ssd" in n for n in kernels), kernels


def test_ssd_layer_described_cost(one_chip):
    """``benchmarks/ssd_layer.py --described``, the static reading of one
    mamba2-370m layer on both SSD paths: the f32 ``[..., 256, 256]``
    tensors and most of the ``exp`` work leave the XLA program."""
    from benchmarks import ssd_layer

    out = ssd_layer.described()
    assert "f32[8,8,32,256,256]" in out["xla"]["f32_chunk_square_shapes"]
    assert out["pallas"]["f32_chunk_square_shapes"] == []
    assert out["pallas"]["transcendentals"] < out["xla"]["transcendentals"] / 3
