"""Record the small chip trace of Pallas kernels that ``bench/tests/test_kernels.py``
reads.

    python3 bench/tests/record_kernel_trace.py <out_dir>

On one TPU, inside a ``bench.window`` span: one jitted program that runs the
flash-attention forward and both backward sweeps (causal, q [2, 512, 6, 64]
over 3 key/value heads), then one that quantizes a [256, 576] float32
matrix to 4-bit codes row by row and reconstructs it. Writes
``<out_dir>/kernels.xplane.pb`` and prints the text of every
``custom-call`` event on the device.
"""
from __future__ import annotations

import glob
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402


def main(out_dir: str) -> int:
    if jax.devices()[0].platform != "tpu":
        print("record_kernel_trace: JAX found no TPU", file=sys.stderr)
        return 2
    from repro.kernels.flash_attention import gqa_flash_attention
    from repro.kernels.ops import dequantize_rowwise, quantize_rowwise

    @jax.jit
    def attention_grads(q, k, v):
        def loss(q, k, v):
            o = gqa_flash_attention(q, k, v, causal=True, block_q=256, block_kv=256)
            return jnp.sum(o.astype(jnp.float32))
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    @jax.jit
    def wire(x):
        _, codes, lo, scale = quantize_rowwise(x, bits=4)
        return dequantize_rowwise(codes, lo, scale)

    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(keys[0], (2, 512, 6, 64), jnp.bfloat16)
    k = jax.random.normal(keys[1], (2, 512, 3, 64), jnp.bfloat16)
    v = jax.random.normal(keys[2], (2, 512, 3, 64), jnp.bfloat16)
    x = jax.random.normal(keys[3], (256, 576), jnp.float32)
    jax.block_until_ready((attention_grads(q, k, v), wire(x)))
    tmp = tempfile.mkdtemp(prefix="record-kernel-trace-")
    with jax.profiler.trace(tmp):
        with jax.profiler.TraceAnnotation("bench.window"):
            jax.block_until_ready(attention_grads(q, k, v))
            jax.block_until_ready(wire(x))
    path = sorted(glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True))[-1]
    os.makedirs(out_dir, exist_ok=True)
    shutil.copy(path, os.path.join(out_dir, "kernels.xplane.pb"))
    shutil.rmtree(tmp, ignore_errors=True)
    data = jax.profiler.ProfileData.from_file(os.path.join(out_dir, "kernels.xplane.pb"))
    for plane in data.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if "custom-call" in e.name:
                    print(line.name, e.duration_ns, e.name[:1200])
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
