"""Mamba2's intra-chunk SSD at mamba2-370m's training shape: the XLA path
against the fused Pallas kernel (``repro.kernels.ssd``).

    PYTHONPATH=src python -m benchmarks.ssd_layer              # on a TPU
    PYTHONPATH=src python -m benchmarks.ssd_layer --described  # no chip

The shape is the benchmark cell ``mamba2-370m.muloco-k1-h15``'s: one worker
(vmapped, K=1), 8 sequences of 2048 in chunks of 256, 32 heads of 64, state
128. Both modes print one JSON object.

On a TPU, for each path (``xla``, ``pallas``):

* ``block_<path>_ms``: the intra-chunk block alone, forward and VJP;
* ``layer_<path>_ms`` and ``layer_<path>_temp_gb``: one layer's
  ``grad(checkpoint(mamba_forward))``, its time and XLA's temporary bytes;
* ``block_err_<path>``: max |diff| / max |want| of Y_diag and its five
  gradients against the XLA block at ``HIGHEST`` precision (float32);
* ``layer_err_<path>``: the same measure for each parameter gradient and
  the input's, against the XLA path at ``HIGHEST``.

With ``--described`` nothing runs: the layer compiles for a described v5e
(``jax.experimental.topologies``), and each path reports XLA's cost
analysis (flops, transcendentals, bytes accessed; a kernel's inside is
invisible to it), the temporary bytes, the f32 ``[..., 256, 256]`` shapes
in the program, and the sum of XLA's own cycle estimates over its
operations.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import re
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.kernels import ssd
from repro.models import ssm

K, B, S = 1, 8, 2048
PATHS = ("xla", "pallas")


@contextlib.contextmanager
def _path(path: str):
    """Run ``mamba_forward``'s intra-chunk block on ``path``, compiled."""
    saved = ssm.ssd_path, ssd.pallas_interpret
    ssm.ssd_path = lambda cfg, S: path
    ssd.pallas_interpret = lambda: False
    try:
        yield
    finally:
        ssm.ssd_path, ssd.pallas_interpret = saved


def _layer_grad(cfg, w=None):
    def loss(p, x):
        y = jax.checkpoint(lambda p, x: ssm.mamba_forward(p, cfg, x))(p, x)
        y = y.astype(jnp.float32)
        return jnp.sum(y if w is None else w * y)
    return jax.jit(jax.vmap(jax.grad(loss, argnums=(0, 1))))


def _ms(fn, *args, n=10, reps=5) -> float:
    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(reps):
        t = time.perf_counter()
        for _ in range(n):
            out = fn(*args)
        jax.block_until_ready(out)
        times.append((time.perf_counter() - t) / n * 1e3)
    return float(np.median(times))


def _err(got, want) -> float:
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-30))


def _block_inputs(cfg):
    Cc, Q, H, P, N = S // cfg.ssm_chunk, cfg.ssm_chunk, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    lead = (K, B, Cc, Q)

    def bf16_values(k, shape):
        return jax.random.normal(k, shape).astype(jnp.bfloat16).astype(jnp.float32)

    x = bf16_values(ks[0], (*lead, H, P))
    Bc, Cm = bf16_values(ks[1], (*lead, N)), bf16_values(ks[2], (*lead, N))
    dt = jax.nn.softplus(jax.random.normal(ks[3], (*lead, H)) - 3.0)
    dA = dt * -jnp.linspace(1.0, 16.0, H)
    dy = jax.random.normal(ks[4], (*lead, H, P))
    return (x, dt, Bc, Cm, dA), dy


def _block(path):
    def fn(x, dt, Bc, Cm, dA):
        if path == "xla":
            return ssm.ssd_intra_chunk_xla(x, dt, Bc, Cm, dA)
        return ssd.ssd_intra_chunk(x, dt, Bc, Cm, jnp.cumsum(dA, axis=2), interpret=False)

    def fwd_bwd(*args):
        y, vjp = jax.vjp(fn, *args[:-1])
        return (y, *vjp(args[-1]))
    return jax.jit(jax.vmap(fwd_bwd))


def on_chip() -> dict:
    cfg = get_config("mamba2-370m")
    out = {"device": jax.devices()[0].device_kind}
    args, dy = _block_inputs(cfg)
    with jax.default_matmul_precision("highest"):
        want = _block("xla")(*args, dy)
    for path in PATHS:
        got = _block(path)(*args, dy)
        out[f"block_err_{path}"] = [_err(g, w) for g, w in zip(got, want)]
        out[f"block_{path}_ms"] = _ms(_block(path), *args, dy)
    del want, got

    keys = jax.random.split(jax.random.PRNGKey(3), K)
    params = jax.vmap(lambda k: ssm.init_mamba(k, cfg))(keys)
    x = (jax.random.normal(jax.random.PRNGKey(4), (K, B, S, cfg.d_model)) * 0.5).astype(jnp.bfloat16)
    w = jax.random.normal(jax.random.PRNGKey(5), (B, S, cfg.d_model))
    with _path("xla"), jax.default_matmul_precision("highest"):
        want = _layer_grad(cfg, w)(params, x)
    for path in PATHS:
        with _path(path):
            fn = _layer_grad(cfg, w)
            got = fn(params, x)
            out[f"layer_err_{path}"] = {k: _err(got[0][k], want[0][k]) for k in got[0]}
            out[f"layer_err_{path}"]["x"] = _err(got[1], want[1])
            out[f"layer_{path}_ms"] = _ms(fn, params, x)
            out[f"layer_{path}_temp_gb"] = (
                fn.lower(params, x).compile().memory_analysis().temp_size_in_bytes / 1e9)
    return out


def described() -> dict:
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    jax.config.update("jax_enable_compilation_cache", False)  # a described compile cannot be read back
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    cfg = get_config("mamba2-370m")
    params = jax.eval_shape(lambda: jax.vmap(lambda k: ssm.init_mamba(k, cfg))(
        jax.random.split(jax.random.PRNGKey(0), K)))
    params = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), params)
    x = jax.ShapeDtypeStruct((K, B, S, cfg.d_model), jnp.bfloat16, sharding=one)
    out = {"device": "described v5e"}
    for path in PATHS:
        with _path(path):
            compiled = _layer_grad(cfg).lower(params, x).compile()
        text = compiled.as_text()
        cost = compiled.cost_analysis()
        cycles = [int(c) for c in re.findall(r'"estimated_cycles":"(\d+)"', text)]
        out[path] = {
            **{k: cost.get(k) for k in ("flops", "transcendentals", "bytes accessed")},
            "temp_gb": compiled.memory_analysis().temp_size_in_bytes / 1e9,
            "f32_chunk_square_shapes": sorted(set(re.findall(r"f32\[[0-9,]*256,256\]", text))),
            "estimated_mcycles": sum(cycles) / 1e6,
        }
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--described", action="store_true",
                    help="compile for a described v5e and report XLA's cost analysis; runs nothing")
    args = ap.parse_args()
    if not args.described and jax.default_backend() != "tpu":
        raise SystemExit("needs a TPU (or --described)")
    print(json.dumps(described() if args.described else on_chip()))


if __name__ == "__main__":
    main()
