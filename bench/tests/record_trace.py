"""Record the small chip trace that ``bench/tests/test_trace.py`` reads.

    python3 bench/tests/record_trace.py <out_dir>

On one TPU: inside a ``bench.window`` span, a jitted program named
``stacked`` (the input pipeline's name) inside a ``bench.datagen`` span,
then a few matrix products inside ``bench.dispatch`` spans, with host sleeps
between them so the device idles. Writes ``<out_dir>/small.xplane.pb`` and
prints every plane's lines with their event counts.
"""
from __future__ import annotations

import glob
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp


def main(out_dir: str) -> int:
    if jax.devices()[0].platform != "tpu":
        print("record_trace: JAX found no TPU", file=sys.stderr)
        return 2

    @jax.jit
    def stacked(x):
        return jnp.cumsum(x, axis=0)

    @jax.jit
    def step(a, b):
        return jnp.tanh(a @ b) @ b

    a = jnp.ones((2048, 2048), jnp.bfloat16)
    b = jnp.ones((2048, 2048), jnp.bfloat16) / 2048
    x = jnp.ones((4096, 512), jnp.float32)
    stacked(x).block_until_ready()
    step(a, b).block_until_ready()
    tmp = tempfile.mkdtemp(prefix="record-trace-")
    with jax.profiler.trace(tmp):
        with jax.profiler.TraceAnnotation("bench.window"):
            with jax.profiler.TraceAnnotation("bench.datagen"):
                stacked(x).block_until_ready()
            for _ in range(3):
                with jax.profiler.TraceAnnotation("bench.dispatch"):
                    a = step(a, b)
                a.block_until_ready()
                time.sleep(0.01)
    path = sorted(glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True))[-1]
    os.makedirs(out_dir, exist_ok=True)
    shutil.copy(path, os.path.join(out_dir, "small.xplane.pb"))
    shutil.rmtree(tmp, ignore_errors=True)
    data = jax.profiler.ProfileData.from_file(os.path.join(out_dir, "small.xplane.pb"))
    for plane in data.planes:
        lines = [(line.name, len(list(line.events))) for line in plane.lines]
        print(plane.name, lines)
        for line in plane.lines:
            names = sorted({e.name for e in line.events})[:12]
            print("   ", line.name, names)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
