"""Production mesh builders.

Single pod:  (data=16, model=16)            — 256 chips (one v5e pod)
Multi-pod:   (pod=2, data=16, model=16)     — 512 chips

The `pod` axis IS the DiLoCo worker axis: fast ICI inside a pod carries the
per-step FSDP/tensor-parallel collectives; the slow cross-pod links carry
only the every-H-steps pseudogradient all-reduce.

Functions (not module constants) so importing never touches jax device
state — the dry-run must set XLA_FLAGS before any device query.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def _auto_mesh(shape: tuple[int, ...], axes: tuple[str, ...]) -> Mesh:
    """A mesh whose axes are all ``Auto``: GSPMD propagates shardings, and
    the bare-PartitionSpec ``with_sharding_constraint`` hints of
    :func:`repro.models.common.shard_hint` are legal (``jax.make_mesh``
    defaults to ``Explicit`` axes, which reject them)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_debug_mesh(data: int = 1, model: int = 1, pod: int = 0) -> Mesh:
    """Small mesh over however many (host) devices exist — for tests."""
    if pod:
        return _auto_mesh((pod, data, model), ("pod", "data", "model"))
    return _auto_mesh((data, model), ("data", "model"))


def mesh_axis_sizes(mesh: Mesh) -> dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))
