"""Sharding rules: map every pytree leaf to a PartitionSpec on the
production mesh.

Scheme (DESIGN.md §3):
  * worker-stacked trees (leading K): K -> 'pod';
  * weight matrices [..., m, n]: m -> 'data' (FSDP / ZeRO-3), n -> 'model'
    (tensor parallel); MoE expert banks [..., E, m, n]: E -> 'model'
    (expert parallel), m -> 'data';
  * outer/DiLoCo state (params, Nesterov u, EF residuals) has no K axis and
    is sharded over ('pod','data') x 'model' — ZeRO-sharding the *outer*
    optimizer over pods, which is what lets 100B+ configs hold 4 param
    copies;
  * KV caches / SSM states: batch -> 'data', longest remaining
    divisible axis (cache length / heads) -> 'model';
  * every rule falls back to replication when a dim is not divisible.
"""
from __future__ import annotations

from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.utils.tree import tree_map_with_path

PyTree = Any


def _div(dim: int, n: int) -> bool:
    return n > 0 and dim % n == 0 and dim >= n


def _axis(mesh_sizes: dict[str, int], name: str, dim: int):
    return name if name in mesh_sizes and _div(dim, mesh_sizes[name]) else None


def param_spec(path: str, shape: tuple[int, ...], mesh_sizes: dict[str, int],
               outer: bool = False, tensor_parallel: bool = True,
               expert_parallel: bool = False) -> P:
    """Spec for one (non-K-stacked) parameter/optimizer-state leaf.

    ``outer=True`` additionally folds the 'pod' axis into the fsdp dim
    (outer-state ZeRO over pods). ``tensor_parallel=False`` drops the model
    axis from weights (TP-unfriendly archs: heads not divisible by the model
    axis — they use sequence parallelism instead). ``expert_parallel`` shards
    MoE banks E->model (serving layout: weights stay resident, tokens move).
    """
    nd = len(shape)
    fsdp: Any = ("pod", "data") if (outer and "pod" in mesh_sizes) else "data"
    fsdp_size = mesh_sizes.get("data", 1) * (mesh_sizes.get("pod", 1) if (outer and "pod" in mesh_sizes) else 1)

    def fsdp_axis(dim):
        return fsdp if _div(dim, fsdp_size) else ("data" if _div(dim, mesh_sizes.get("data", 0)) else None)

    if nd <= 1:
        return P(*([None] * nd))
    spec = [None] * nd
    if expert_parallel and nd >= 3 and ("experts" in path):
        spec[-3] = _axis(mesh_sizes, "model", shape[-3])
        spec[-2] = fsdp_axis(shape[-2])
        return P(*spec)
    # Matrices (incl. MoE expert banks [..., E, m, n]): trailing dims get
    # (fsdp, model); the expert dim stays unsharded so dispatch buffers with
    # d-passthrough sharding contract without resharding the weight bank.
    spec[-2] = fsdp_axis(shape[-2])
    if tensor_parallel:
        spec[-1] = _axis(mesh_sizes, "model", shape[-1])
    return P(*spec)


def worker_spec(path: str, shape: tuple[int, ...], mesh_sizes: dict[str, int],
                tensor_parallel: bool = True) -> P:
    """Spec for a K-stacked leaf: K -> 'pod', rest per param_spec."""
    inner = param_spec(path, shape[1:], mesh_sizes, outer=False,
                       tensor_parallel=tensor_parallel)
    pod = "pod" if ("pod" in mesh_sizes and _div(shape[0], mesh_sizes["pod"])) else None
    return P(pod, *inner)


def cache_spec(shape: tuple[int, ...], batch: int, mesh_sizes: dict[str, int]) -> P:
    """KV-cache / SSM-state leaf: batch -> 'data', longest other -> 'model'."""
    spec = [None] * len(shape)
    data_n = mesh_sizes.get("data", 0)
    model_n = mesh_sizes.get("model", 0)
    b_idx = None
    for i, d in enumerate(shape):
        if d == batch and _div(d, data_n):
            b_idx = i
            spec[i] = "data"
            break
    best, best_dim = None, 0
    for i, d in enumerate(shape):
        if i == b_idx or i == 0 and len(shape) > 3:
            # skip the layer-stack axis (leading, scanned) and the batch axis
            continue
        if _div(d, model_n) and d > best_dim:
            best, best_dim = i, d
    if best is not None:
        spec[best] = "model"
    return P(*spec)


# ---------------------------------------------------------------------------
# Tree-level builders
# ---------------------------------------------------------------------------


def params_shardings(mesh: Mesh, params: PyTree, outer: bool = False,
                     tensor_parallel: bool = True, expert_parallel: bool = False) -> PyTree:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return tree_map_with_path(
        lambda p, x: NamedSharding(mesh, param_spec(
            p, x.shape, sizes, outer=outer, tensor_parallel=tensor_parallel,
            expert_parallel=expert_parallel)), params
    )


def worker_shardings(mesh: Mesh, tree: PyTree, tensor_parallel: bool = True) -> PyTree:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return tree_map_with_path(
        lambda p, x: NamedSharding(mesh, worker_spec(
            p, x.shape, sizes, tensor_parallel=tensor_parallel)), tree
    )


def diloco_state_shardings(mesh: Mesh, state: PyTree, tensor_parallel: bool = True) -> PyTree:
    """Shardings for the full TrainState pytree (see diloco_init).

    Returns a pytree of NamedShardings with the same structure as ``state``
    (TrainState in, TrainState out), usable directly as jit in_shardings.
    """

    def for_group(key, sub):
        if key in ("worker_params", "inner_state", "ef"):
            return worker_shardings(mesh, sub, tensor_parallel=tensor_parallel)
        if key in ("outer_params", "outer_opt"):
            return params_shardings(mesh, sub, outer=True,
                                    tensor_parallel=tensor_parallel)
        if key == "pending":
            # delayed-sync FIFO: [d, ...]-stacked pseudogradients. The tiny
            # FIFO depth stays unsharded; the payload keeps the outer-state
            # ZeRO layout so the shift + descent never reshard.
            sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
            return tree_map_with_path(
                lambda p, x: NamedSharding(mesh, P(None, *param_spec(
                    p, x.shape[1:], sizes, outer=True,
                    tensor_parallel=tensor_parallel))), sub)
        # counters + the [K] elastic participation mask: replicated
        return jax.tree.map(lambda x: NamedSharding(mesh, P()), sub)

    if hasattr(state, "map_groups"):  # TrainState
        return state.map_groups(for_group)
    return {key: for_group(key, sub) for key, sub in state.items()}


def batch_shardings(mesh: Mesh, batch: PyTree, k_stacked: bool = True,
                    leading_scan: int = 0) -> PyTree:
    """``leading_scan`` counts leading scanned axes left unsharded: 1 for
    [H, K, B, ...] round-stacked batches (the engine's fused round input),
    2 for the superstep's [R, H, K, B, ...]; K and B follow the per-step
    rule either way. (``True`` is accepted as 1 for the older bool form.)"""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n_lead = int(leading_scan)

    def spec(path, x):
        nd = len(x.shape)
        shape = x.shape
        lead: tuple = ()
        if n_lead:
            lead, shape, nd = (None,) * n_lead, x.shape[n_lead:], nd - n_lead
        if k_stacked:
            pod = "pod" if ("pod" in sizes and _div(shape[0], sizes["pod"])) else None
            data = "data" if (nd > 1 and _div(shape[1], sizes.get("data", 0))) else None
            return NamedSharding(mesh, P(*lead, pod, data, *([None] * (nd - 2))))
        data = "data" if _div(shape[0], sizes.get("data", 0)) else None
        return NamedSharding(mesh, P(*lead, data, *([None] * (nd - 1))))

    return tree_map_with_path(spec, batch)


def cache_shardings(mesh: Mesh, cache: PyTree, batch: int) -> PyTree:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return jax.tree.map(
        lambda x: NamedSharding(mesh, cache_spec(x.shape, batch, sizes)), cache
    )


def replicated(mesh: Mesh, tree: PyTree) -> PyTree:
    return jax.tree.map(lambda x: NamedSharding(mesh, P()), tree)


# ---------------------------------------------------------------------------
# Kernel partitioning (shard_map routing for the Pallas call sites)
# ---------------------------------------------------------------------------


def kernel_specs(mesh: Mesh | None, cfg=None):
    """The per-kernel shard_map routing for a plan's mesh — ONE place maps
    the plan-level layout (``diloco_state_shardings`` / ``batch_shardings``
    above) onto the block-local axes each kernel shards:

    * flash attention: the fused [B*KV, ...] batch-head axis over
      ('data', 'model') — B rides 'data' exactly like ``batch_shardings``
      puts it there, KV-heads ride 'model' like ``param_spec`` puts head
      projections there; the worker axis K arrives via
      ``vmap(spmd_axis_name='pod')`` on top. TP-unfriendly archs (heads
      don't divide the model axis — the same test ``tp_friendly`` applies
      to the activation rules) drop 'model' and shard batch only.
    * wire quantize/dequantize: K-folded rows over ('pod', 'data').
    * Newton–Schulz: the stacked-matrix axis over ('data',),
      replicated-or-rowwise per label (stacks that don't divide lower
      replicated).
    * outer update: shape-preserving specs mirroring the outer-state ZeRO
      layout itself (``outer_update_spec``), with dim -1 on 'model' only
      for TP-friendly archs (``outer_tp``) — matching the committed
      sharding is what keeps the donated TrainState aliased.
    * paged decode: batch slots (plus their page-table rows) over ('data',),
      KV pool and visit schedules replicated.
    * intra-chunk SSD: the batch axis over ('data',), K via 'pod' as for
      flash.

    Returns None on single-device worlds (kernels keep their plain
    single-device pallas_call path).
    """
    if mesh is None or mesh.devices.size <= 1:
        return None
    from repro.kernels.partition import KernelPartitioning

    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    flash: tuple[str, ...] = ("data", "model")
    outer_tp = True
    if cfg is not None and sizes.get("model", 1) > 1:
        heads = getattr(cfg, "n_kv_heads", 0) or getattr(cfg, "n_heads", 0)
        if heads % sizes["model"]:
            # TP-unfriendly: keep attention replicated over 'model' so the
            # (unavoidable) gather happens at the layer boundary, not per
            # kernel call — same reasoning as activation_rules' attn_kv pin
            flash = ("data",)
        # outer_tp must track the STATE layout, not the kernel preference:
        # diloco_state_shardings drops 'model' for TP-unfriendly archs
        # (tp_friendly), and the outer-update specs must match the committed
        # sharding exactly or donation loses the aliased state buffers
        from repro.launch.steps import tp_friendly

        outer_tp = tp_friendly(cfg, mesh)
    return KernelPartitioning(mesh=mesh, flash_axes=flash, outer_tp=outer_tp)
