"""Fused Nesterov outer update kernel (paper Eq. 3).

    u'     = mu * u + eta * psi
    theta' = theta - mu * u' - eta * psi

One elementwise VMEM pass producing both outputs — on TPU this halves the
HBM traffic of the outer step vs materializing u' then re-reading it, which
matters because the outer step touches 3 full parameter copies.

The kernel sits behind the ``nesterov`` outer transform
(:mod:`repro.optim.nesterov`): ``DiLoCoConfig.outer_kernel=True`` /
``--outer-kernel`` routes the terminal ``apply`` of the pseudogradient chain
through :func:`repro.kernels.ops.nesterov_update` instead of pure XLA.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def outer_update_spec(part, shape: tuple[int, ...]):
    """Shape-preserving shard_map spec for one outer-update operand.

    Mirrors the outer-state ZeRO layout of
    :func:`repro.launch.sharding.param_spec` (``outer=True``) exactly:
    matrices shard dim -2 over ('pod','data') (falling back to 'data', then
    replicated, on non-divisible dims) and dim -1 over 'model' when the
    partitioning says the arch is TP-friendly; vectors/scalars replicate.
    Matching the committed sharding is what keeps the donated TrainState
    aliased through the round/superstep programs — a flat global reshape
    would force a reshard and lose the ``input_output_alias`` entries (the
    update is elementwise, so flattening happens per-shard inside the
    mapped region instead)."""
    from jax.sharding import PartitionSpec as P

    sizes = part.axis_sizes()
    nd = len(shape)
    if nd <= 1:
        return P(*([None] * nd))

    def div(dim: int, k: int) -> bool:
        return k > 0 and dim % k == 0 and dim >= k

    pod, data = sizes.get("pod", 0), sizes.get("data", 0)
    spec: list = [None] * nd
    if pod and div(shape[-2], pod * data):
        spec[-2] = ("pod", "data")
    elif div(shape[-2], data):
        spec[-2] = "data"
    if part.outer_tp and div(shape[-1], sizes.get("model", 0)):
        spec[-1] = "model"
    return P(*spec)


def _nesterov_kernel(theta_ref, psi_ref, u_ref, theta_out_ref, u_out_ref, *, lr, momentum):
    psi = psi_ref[...].astype(jnp.float32)
    u_new = momentum * u_ref[...] + lr * psi
    theta = theta_ref[...].astype(jnp.float32)
    theta_out_ref[...] = (theta - momentum * u_new - lr * psi).astype(theta_out_ref.dtype)
    u_out_ref[...] = u_new


def fused_nesterov_update(
    theta: jax.Array,
    psi: jax.Array,
    u: jax.Array,
    *,
    lr: float,
    momentum: float,
    block: int = 1024,
    interpret: bool,
) -> tuple[jax.Array, jax.Array]:
    """Flat [n] arrays (n % block == 0; ops.py pads) -> (theta', u')."""
    (n,) = theta.shape
    assert n % block == 0
    kernel = functools.partial(_nesterov_kernel, lr=lr, momentum=momentum)
    return pl.pallas_call(
        kernel,
        grid=(n // block,),
        in_specs=[
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((block,), lambda i: (i,)),
        ],
        out_specs=[
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((block,), lambda i: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n,), theta.dtype),
            jax.ShapeDtypeStruct((n,), jnp.float32),
        ],
        interpret=interpret,
    )(theta, psi, u)
