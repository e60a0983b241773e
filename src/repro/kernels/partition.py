"""Kernel partitioning: shard_map routing for every Pallas call site.

Pallas calls carry no GSPMD partitioning rules, so a bare ``pl.pallas_call``
inside a jit that spans a multi-device mesh fails to lower — which is why
every fused kernel used to fall back to XLA on the production mesh. The fix
is the maxtext-DiLoCo combination: wrap the kernel call in
``jax.shard_map`` with explicit PartitionSpecs, so GSPMD sees
an opaque per-device region and each device runs the kernel on its local
block. All six kernels are embarrassingly parallel over the axes we shard
(batch*kv-head rows for flash attention, sequences for the SSD,
quantize rows, stacked Newton-Schulz matrices, elementwise outer updates in
the state's own layout, serving batch slots), so the shard_mapped result is bitwise-identical to the
single-device call — padding to block multiples happens *inside* the mapped
region, on local shapes, so splitting an axis never changes any row's
arithmetic.

The routing lives in a ContextVar installed by the StepPlan machinery
(:func:`repro.launch.sharding.kernel_specs` builds the
:class:`KernelPartitioning`, ``launch/steps.py`` installs it around every
step fn), mirroring the ``activation_sharding`` pattern in
``models/common.py``: the kernel wrappers in ``kernels/ops.py`` /
``kernels/flash_attention.py`` consult :func:`active_partitioning` at trace
time and shard_map themselves when a mesh is routed. With no context
installed the kernels behave exactly as before (single-device pallas_call),
so the CPU test path is unchanged.

Axis preferences degrade gracefully: :func:`axes_for` takes the longest
*prefix* of the preferred mesh axes whose product divides the dim being
sharded, falling back to full replication (which always lowers) when
nothing divides. Scalar-prefetch operands that must stay whole — the flash
visit schedule (a closed-over trace constant) and the paged-KV pool — are
replicated; the page *table* is co-sharded with its batch-slot axis so each
device indexes its own slots against the replicated pool.
"""
from __future__ import annotations

import dataclasses
from contextvars import ContextVar
from typing import Any, Callable

import jax
from jax.sharding import Mesh


@dataclasses.dataclass(frozen=True)
class KernelPartitioning:
    """Mesh + per-kernel axis preferences for shard_mapping Pallas calls.

    Each ``*_axes`` tuple is an ordered mesh-axis preference for the axis
    that kernel shards (see ``docs/architecture.md`` "Kernels on the mesh"):

    * ``flash_axes``   — the fused [B*KV, S, G, hd] batch-head axis. B-major
      ordering means ('data', 'model') aligns with batch->data, kv->model.
      The worker axis K is NOT listed: ``inner_step`` vmaps with
      ``spmd_axis_name='pod'``, and shard_map's batching rule inserts 'pod'
      into the specs at the vmapped dim.
    * ``quantize_axes`` — wire-quantize rows ([K-folded rows, n]; K folds
      into the row axis before the kernel, hence 'pod' leads).
    * ``ns_axes``      — the stacked-matrix batch axis of Newton-Schulz
      ([L*heads..., m, n]); whole matrices stay local (replicated-or-rowwise
      per label — stacks that don't divide run replicated).
    * ``paged_axes``   — the serving batch-slot axis of paged decode (the
      page table rides along; the KV pool is replicated).

    The fused outer update has no axis preference here: its specs are
    shape-preserving and mirror the outer-state ZeRO layout directly
    (:func:`repro.kernels.outer_update.outer_update_spec`); ``outer_tp``
    records whether that layout shards dim -1 over 'model' (the
    tensor-parallel-friendliness of the arch, decided by ``kernel_specs``).
    """

    mesh: Mesh
    flash_axes: tuple[str, ...] = ("data", "model")
    quantize_axes: tuple[str, ...] = ("pod", "data")
    ns_axes: tuple[str, ...] = ("data",)
    paged_axes: tuple[str, ...] = ("data",)
    outer_tp: bool = True

    def axis_sizes(self) -> dict[str, int]:
        return dict(zip(self.mesh.axis_names, self.mesh.devices.shape))


_KERNEL_PARTS: ContextVar[KernelPartitioning | None] = ContextVar(
    "kernel_parts", default=None)


class kernel_partitioning:
    """Context manager routing kernel calls through shard_map.

    ``parts=None`` is a no-op (so call sites can install unconditionally)::

        with kernel_partitioning(kernel_specs(mesh, cfg)):
            loss = train_step(state, batch)   # pallas calls shard_map'd
    """

    def __init__(self, parts: KernelPartitioning | None):
        self.parts = parts
        self._toks: list = []  # stack: instances are re-entered every trace

    def __enter__(self):
        self._toks.append(_KERNEL_PARTS.set(self.parts))
        return self

    def __exit__(self, *exc):
        _KERNEL_PARTS.reset(self._toks.pop())
        return False


def active_partitioning() -> KernelPartitioning | None:
    """The installed routing, or None (single-device kernel behavior)."""
    return _KERNEL_PARTS.get()


def axes_for(part: KernelPartitioning, dim: int,
             prefer: tuple[str, ...]) -> tuple[str, ...]:
    """Longest prefix of ``prefer`` whose mesh-size product divides ``dim``.

    Prefix (not subset) semantics keep the major-to-minor alignment of the
    composite axis; an empty result means replicate (always lowers)."""
    sizes = part.axis_sizes()
    chosen: list[str] = []
    prod = 1
    for name in prefer:
        n = sizes.get(name, 1)
        if n <= 1:
            continue
        if dim % (prod * n):
            break
        chosen.append(name)
        prod *= n
    return tuple(chosen)


def shard_wrap(fn: Callable, part: KernelPartitioning,
               in_specs: Any, out_specs: Any) -> Callable:
    """shard_map ``fn`` on the routed mesh.

    ``check_vma=False``: the kernel bodies are opaque to shard_map's
    varying-axes checker (pallas_call has no rule for it), and every
    wrapped kernel is batch-local — no cross-device reduction ever happens
    inside the mapped region."""
    return jax.shard_map(fn, mesh=part.mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
