"""The fused intra-chunk SSD kernel (interpret mode) against the jnp
expression it replaces, ``repro.models.ssm.ssd_intra_chunk_xla``, forward
and VJP in float32; and ``ssd_path``, which picks it.

The kernel rounds every MXU operand to bf16 (f32 accumulation); the oracle
runs at HIGHEST precision. A bf16 operand carries a relative rounding of
2^-9, and a product sums up to Q such terms of either sign, so errors are
compared with the largest magnitude of each output: they measure 2e-3 to
5e-3 here, and TOL = 2^-6 leaves three times that room.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tracing
from repro.configs import get_config
from repro.kernels import ssd
from repro.models import ssm

TOL = 2.0 ** -6

# (name, Q, H, P, N, heads per grid step at most)
CASES = [
    ("mamba2", 128, 4, 64, 128, 8),  # mamba2-370m's head dim and state
    ("mamba2-two-groups", 128, 4, 64, 128, 2),  # dC/dB/dA summed over groups
    ("zamba2", 128, 4, 64, 64, 8),  # zamba2-2.7b's N=64
    ("p32", 128, 8, 32, 64, 8),  # heads of four sublane tiles
    ("p128", 128, 2, 128, 128, 8),  # heads of sixteen
]


def _inputs(key, B, C, Q, H, P, N, lead=()):
    """Model-like chunk inputs: x, B and C are bf16 values (the model
    upcasts them), dt a softplus, dA = dt * A with A in [-16, -1]."""
    ks = jax.random.split(key, 5)

    def bf(k, shape):
        return jax.random.normal(k, shape).astype(jnp.bfloat16).astype(jnp.float32)

    xc = bf(ks[0], (*lead, B, C, Q, H, P))
    Bc = bf(ks[1], (*lead, B, C, Q, N))
    Cc = bf(ks[2], (*lead, B, C, Q, N))
    dt = jax.nn.softplus(jax.random.normal(ks[3], (*lead, B, C, Q, H)) - 3.0)
    dA = dt * -jnp.linspace(1.0, 16.0, H)
    dy = jax.random.normal(ks[4], (*lead, B, C, Q, H, P))
    return (xc, dt, Bc, Cc, dA), dy


def _kernel(xc, dt, Bc, Cc, dA):
    """The kernel as ``mamba_forward`` calls it, on the model's inputs."""
    return ssd.ssd_intra_chunk(xc, dt, Bc, Cc, jnp.cumsum(dA, axis=2), interpret=True)


def _rel(got, want) -> float:
    got, want = np.asarray(got), np.asarray(want)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


@pytest.mark.parametrize("name,Q,H,P,N,max_hg", CASES, ids=[c[0] for c in CASES])
def test_kernel_matches_xla_expression(monkeypatch, name, Q, H, P, N, max_hg):
    monkeypatch.setattr(ssd, "MAX_HEADS_PER_STEP", max_hg)
    args, dy = _inputs(jax.random.PRNGKey(len(name)), 1, 2, Q, H, P, N)
    with jax.default_matmul_precision("highest"):
        want, vjp_want = jax.vjp(ssm.ssd_intra_chunk_xla, *args)
        grads_want = vjp_want(dy)
    got, vjp_got = jax.vjp(_kernel, *args)
    assert got.dtype == jnp.float32 and got.shape == want.shape
    assert _rel(got, want) < TOL
    for what, g, w in zip(("x", "dt", "B", "C", "dA"), vjp_got(dy), grads_want):
        assert g.dtype == jnp.float32, what
        assert _rel(g, w) < TOL, (what, _rel(g, w))


def test_kernel_under_vmap():
    """vmap over K workers (the engine vmaps even K=1) gives each worker's
    own call, forward and VJP."""
    args, dy = _inputs(jax.random.PRNGKey(3), 1, 2, 128, 4, 64, 128, lead=(2,))

    def fwd_bwd(*a):
        y, vjp = jax.vjp(_kernel, *a[:-1])
        return y, vjp(a[-1])

    y, grads = jax.vmap(fwd_bwd)(*args, dy)
    for k in range(2):
        y_k, grads_k = fwd_bwd(*(a[k] for a in args), dy[k])
        np.testing.assert_allclose(y[k], y_k, rtol=1e-6, atol=1e-6)
        for g, g_k in zip(grads, grads_k):
            np.testing.assert_allclose(g[k], g_k, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("interpret,arch,S,want", [
    (False, "mamba2-370m", 2048, "pallas"),
    (False, "zamba2-2.7b", 4096, "pallas"),
    (True, "mamba2-370m", 2048, "xla"),  # off-TPU: the interpreter is no path
    (False, "mamba2-370m", 2048 + 64, "xla"),  # S not whole chunks
    (False, "mamba2-370m-reduced", 64, "xla"),  # chunk 8, head dim 16
])
def test_ssd_path(monkeypatch, interpret, arch, S, want):
    if arch.endswith("-reduced"):
        cfg = get_config(arch.removesuffix("-reduced"))
        cfg = dataclasses.replace(cfg, ssm_chunk=8, ssm_head_dim=16, ssm_state=16)
    else:
        cfg = get_config(arch)
    monkeypatch.setattr(ssm, "pallas_interpret", lambda: interpret)
    assert ssm.ssd_path(cfg, S) == want


def _small_cfg():
    return dataclasses.replace(get_config("mamba2-370m"), d_model=128, n_layers=1,
                               ssm_state=64, ssm_chunk=128, dtype="float32")


def test_cpu_mamba_forward_takes_xla_path():
    """On the CPU, ``mamba_forward`` runs the jnp expression: no kernel in
    its program, at full width or at a shape the kernel suits."""
    for cfg, S in ((get_config("mamba2-370m"), 512), (_small_cfg(), 256)):
        assert ssm.ssd_path(cfg, S) == "xla"
        p = jax.eval_shape(lambda: ssm.init_mamba(jax.random.PRNGKey(0), cfg))
        x = jax.ShapeDtypeStruct((1, S, cfg.d_model), jnp.float32)
        jaxpr = str(jax.make_jaxpr(lambda p, x: ssm.mamba_forward(p, cfg, x))(p, x))
        assert "pallas_call" not in jaxpr


def test_mamba_forward_kernel_path_matches_xla_path(monkeypatch):
    """The whole layer with the kernel forced in (interpreted) against the
    same layer on the XLA path: output and parameter gradients."""
    cfg = _small_cfg()
    p = ssm.init_mamba(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 256, cfg.d_model))

    w = jax.random.normal(jax.random.PRNGKey(2), x.shape)

    def loss(p, x):  # linear, so both paths take the same cotangent
        return jnp.sum(w * ssm.mamba_forward(p, cfg, x))

    with jax.default_matmul_precision("highest"):
        want = jax.value_and_grad(loss)(p, x)
    monkeypatch.setattr(ssm, "ssd_path", lambda cfg, S: "pallas")
    got = jax.value_and_grad(loss)(p, x)
    assert abs(float(got[0]) - float(want[0])) < TOL * abs(float(want[0])) + 1e-3
    for k in want[1]:
        # a_log and dt_bias, one number per head, sum dA's gradient over
        # every position with both signs: bf16 rounding of dY and x leaves
        # them 0.002-0.017 off on four seeds, the other leaves under 1e-3
        tol = 4 * TOL if k in ("a_log", "dt_bias") else TOL
        assert _rel(got[1][k], want[1][k]) < tol, (k, _rel(got[1][k], want[1][k]))


def test_xla_path_runs_under_the_ssd_scope():
    """The intra-chunk block's operations carry ``repro.ssd`` (forward and
    backward), and the chunk states and inter-chunk scan do not."""
    cfg = _small_cfg()
    p = jax.eval_shape(lambda: ssm.init_mamba(jax.random.PRNGKey(0), cfg))
    x = jax.ShapeDtypeStruct((1, 256, cfg.d_model), jnp.float32)
    text = jax.jit(jax.grad(lambda p, x: jnp.sum(ssm.mamba_forward(p, cfg, x)))).lower(
        p, x).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    assert any(tracing.SSD in n for n in names)
    assert any("while" in n and tracing.SSD not in n for n in names)
