"""DiLoCo / MuLoCo: the paper's algorithm as a composable JAX module.

Algorithm 1/2 of the paper, faithfully:

  * K workers each run H local steps of the **inner optimizer**
    (AdamW -> DiLoCo, Muon -> MuLoCo) on their own data shard;
  * every H steps, worker deltas Δ_k = θ_outer − θ_k are (optionally
    EF-compressed and) averaged into the pseudogradient Ψ;
  * the **outer** Nesterov-SGD applies Ψ to the outer params, which are then
    broadcast back to all workers.

Worker state is stacked on a leading K axis. On the production mesh this axis
is sharded over `pod`, so the H inner steps incur **zero cross-pod traffic**
and the Ψ-average is the only cross-pod all-reduce — DiLoCo's communication
pattern expressed purely through shardings. On CPU the same code simulates
any K via vmap. Streaming (partitioned) sync and compressed collectives plug
in through :mod:`repro.core.streaming` / :mod:`repro.core.collectives`.

State lives in :class:`repro.engine.TrainState` (a registered pytree), and
execution goes through :class:`repro.engine.TrainEngine`, which compiles
:func:`diloco_round` once as a donated, jitted program — scanned over R
rounds per dispatch by the superstep executor, of which single-round
execution is the degenerate R=1 case. The DP baseline is the degenerate
``dp_config`` (K=1, H=1, no outer) of the same round.

Both optimizers are transform chains (:mod:`repro.optim.transform`): the
inner step is a ``descend``-wrapped chain from :func:`make_optimizer`, and
the whole pseudogradient path (Δ -> compress/EF -> reduce -> outer descent)
is the chain declared by :func:`make_outer` and executed by ``outer_step``.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.core.collectives import (
    measured_sync_bytes,
    reduce_mean,
    segment_sync_update,
)
from repro.core.compression import CompressionConfig, compress, error_feedback
from repro.core.health import HealthConfig, health_init, health_update
from repro.core.streaming import masked_update, streaming_masks
from repro.models.api import Model
from repro.optim import (
    OptimizerConfig,
    chain,
    make_inner_optimizer,
    make_outer_transform,
)
from repro.tracing import FWD_BWD, INNER_OPT, OUTER_SYNC, OUTER_UPDATE, PSEUDOGRAD, REDUCE

PyTree = Any


@dataclasses.dataclass(frozen=True)
class DiLoCoConfig:
    n_workers: int = 8  # K
    sync_interval: int = 30  # H
    inner_name: str = "muon"  # 'muon' -> MuLoCo, 'adamw' -> DiLoCo
    outer_name: str = "nesterov"  # 'nesterov' (paper) | 'sgd'
    outer_lr: float = 0.7  # eta_out (paper Fig. 22 optima)
    outer_momentum: float = 0.9  # mu
    compression: CompressionConfig = dataclasses.field(default_factory=CompressionConfig)
    streaming_partitions: int = 1  # J (1 = no streaming)
    ns_impl: str = "jnp"
    # Route the outer descent through the fused Pallas outer-update kernel
    # (kernels/outer_update.py): one elementwise VMEM pass for (theta', u').
    outer_kernel: bool = False
    # False -> the degenerate data-parallel config: no outer Nesterov, the
    # synced params are simply the (K-mean of the) worker params. With
    # K=1, H=1 this IS the plain inner optimizer — DP AdamW / DP Muon run
    # through the exact same round function as DiLoCo/MuLoCo.
    outer_enabled: bool = True
    # Elastic execution: allocate a [K] participation mask in the TrainState
    # (all-ones at init; the driver overwrites it per round). A dropped
    # worker (mask 0) freezes in place for the round — no inner steps, no
    # wire packet, EF residual untouched — and the pseudogradient mean runs
    # over the surviving subset. False keeps the legacy state leaf set and
    # the bit-exact dense program.
    elastic: bool = False
    # Delayed/overlapped outer sync: round r computes its pseudogradient
    # Psi_r (communication + EF happen at r) but the outer descent applies
    # Psi_{r-d} from the TrainState's `pending` FIFO — round r+1's inner
    # steps start from params that have not yet seen Psi_r, masking sync
    # latency (SNOO-style staleness). 0 = lockstep (bit-exact legacy path).
    sync_delay: int = 0
    # In-program health sentinel (core/health.py): when enabled the round
    # emits a per-round anomaly-flag metric (non-finite loss/psi, loss spike
    # vs a running EMA carried in the TrainState) that the driver's
    # RecoveryPolicy reacts to. Disabled (default) adds no state leaf and no
    # traced ops — the lowered program is unchanged.
    health: HealthConfig = dataclasses.field(default_factory=HealthConfig)

    @property
    def is_muloco(self) -> bool:
        return self.inner_name == "muon"


def dp_config(inner_name: str, ns_impl: str = "jnp") -> DiLoCoConfig:
    """The DP baseline as a degenerate DiLoCo config (K=1, H=1, no outer)."""
    return DiLoCoConfig(n_workers=1, sync_interval=1, inner_name=inner_name,
                        outer_lr=1.0, outer_momentum=0.0, outer_enabled=False,
                        ns_impl=ns_impl)


def make_optimizer(dcfg: DiLoCoConfig, inner_cfg: OptimizerConfig):
    kw = {"ns_impl": dcfg.ns_impl} if dcfg.inner_name != "adamw" else {}
    return make_inner_optimizer(dcfg.inner_name, inner_cfg, **kw)


# ---------------------------------------------------------------------------
# The outer optimizer: a declared pseudogradient chain
# ---------------------------------------------------------------------------


class OuterOptimizer:
    """The pseudogradient path Δ -> compress/EF -> reduce -> outer descent as
    ONE declared transform chain (``self.tx``), replacing the inline branches
    the pre-transform ``outer_step`` hand-wired.

    Chain state is the stage tuple ``(ef_residuals | (), (), outer_opt)``;
    the TrainState keeps storing the EF residuals and the outer-transform
    state in its ``ef`` / ``outer_opt`` fields (they shard differently:
    K-stacked vs ZeRO over pods), and this wrapper packs/unpacks them around
    the chain. ``step`` also owns the streaming-mask merge semantics, which
    are stage-specific: candidate params and outer momentum merge under the
    partition mask, untouched partitions keep their EF residuals.
    """

    def __init__(self, dcfg: DiLoCoConfig, state_dtype="float32"):
        ccfg = dcfg.compression
        self.dcfg = dcfg
        self.state_dtype = jnp.dtype(state_dtype)
        self.has_ef = bool(ccfg.error_feedback and ccfg.kind != "none")
        self.has_wire = ccfg.kind != "none"
        self.worker_stage = error_feedback(ccfg) if self.has_ef else compress(ccfg)
        self.terminal = make_outer_transform(
            dcfg.outer_name, dcfg.outer_lr, dcfg.outer_momentum,
            state_dtype=self.state_dtype, kernel=dcfg.outer_kernel)
        self.tx = chain(self.worker_stage, reduce_mean(ccfg), self.terminal)

    # -- state construction --------------------------------------------------

    def init_opt(self, params: PyTree) -> PyTree:
        """Outer-transform state (no K axis; ZeRO-sharded on the mesh)."""
        return self.terminal.init(params)

    def init_ef(self, params: PyTree, n_workers: int) -> PyTree | None:
        """K-stacked EF residuals, or None when the config never uses them.

        Matches the legacy allocation rule: residuals exist whenever
        ``error_feedback=True`` (even with ``kind='none'``, where the chain
        skips the EF stage)."""
        if not self.dcfg.compression.error_feedback:
            return None
        template = jax.tree.map(
            lambda p: jnp.zeros((n_workers, *p.shape), self.state_dtype), params)
        return error_feedback(self.dcfg.compression).init(template)

    # -- the sync ------------------------------------------------------------

    @jax.named_scope(REDUCE)
    def reduce(self, params: PyTree, deltas: PyTree, ef: PyTree | None,
               mask: PyTree | None = None,
               participation: jax.Array | None = None):
        """The communication half of the sync: worker stage (compress/EF) +
        the pseudogradient all-reduce, NO outer descent. Returns
        ``(psi, new_ef)``.

        A streaming segment (``mask`` present) with wire compression routes
        through :func:`repro.core.collectives.segment_sync_update` instead
        of the dense stages: the concrete mask subsets the wire rows, so the
        simulated buffers themselves shrink to the segment's share. Masks
        are closure constants of the jitted round — a traced mask falls back
        to the full-size masked encode.

        An elastic ``participation`` mask ([K] {0,1}, traced) restricts the
        reduce to surviving workers (threaded into
        :func:`repro.core.collectives.reduce_mean`) and **freezes** dropped
        workers' EF residuals: their packets were never sent, so their
        residuals must come back bit-identical, not EF-decayed.
        """
        ccfg = self.dcfg.compression
        concrete_mask = mask is not None and not any(
            isinstance(m, jax.core.Tracer) for m in jax.tree.leaves(mask))
        if concrete_mask and self.has_wire:
            psi, seg_ef = segment_sync_update(
                deltas, ef if self.has_ef else None, mask, ccfg,
                participation=participation)
            new_ef = seg_ef if self.has_ef else ef
        else:
            sub = chain(self.worker_stage, reduce_mean(ccfg, participation))
            psi, sub_state = sub.update(
                deltas, (ef if self.has_ef else (), ()), params)
            new_ef = sub_state[0] if self.has_ef else ef
        if participation is not None and self.has_ef and ef is not None:
            pk = participation.astype(jnp.float32)
            new_ef = jax.tree.map(
                lambda ne, oe: jnp.where(
                    pk.reshape((pk.shape[0],) + (1,) * (ne.ndim - 1)) > 0,
                    ne, oe.astype(ne.dtype)),
                new_ef, ef)
        return psi, new_ef

    @jax.named_scope(OUTER_UPDATE)
    def descend(self, params: PyTree, psi: PyTree, opt_state: PyTree):
        """The terminal half: outer transform update + parameter descent on
        an already-reduced pseudogradient. Returns ``(new_params, new_opt)``.
        Split from :meth:`reduce` so the delayed-sync mode can apply a
        *stale* psi while the fresh one enters the pending FIFO."""
        psi, opt_after = self.terminal.update(psi, opt_state, params)
        return self.terminal.apply(params, psi, opt_after)

    def step(self, params: PyTree, deltas: PyTree, opt_state: PyTree,
             ef: PyTree | None, mask: PyTree | None = None,
             participation: jax.Array | None = None):
        """Run the full chain on (masked) deltas; returns
        ``(new_params, new_opt_state, new_ef, psi)``. Exactly
        :meth:`reduce` followed by :meth:`descend` — the same op sequence
        the one-shot ``self.tx`` chain produced — plus the streaming-mask
        merge semantics, which are stage-specific: candidate params and
        outer momentum merge under the partition mask, untouched partitions
        keep their EF residuals.
        """
        psi, new_ef = self.reduce(params, deltas, ef, mask=mask,
                                  participation=participation)
        cand_params, new_opt = self.descend(params, psi, opt_state)
        if mask is None:
            return cand_params, new_opt, new_ef, psi
        new_params = masked_update(mask, cand_params, params)
        new_opt = self.terminal.mask_state(mask, new_opt, opt_state)
        if self.has_ef:  # untouched partitions keep their residuals
            new_ef = jax.tree.map(
                lambda m, ne, oe: jnp.where((m[None] if m.ndim else m) > 0, ne, oe),
                mask, new_ef, ef)
        return new_params, new_opt, new_ef, psi


def make_outer(dcfg: DiLoCoConfig, state_dtype="float32") -> OuterOptimizer:
    """Build the declared pseudogradient chain for a DiLoCo config."""
    return OuterOptimizer(dcfg, state_dtype=state_dtype)


# ---------------------------------------------------------------------------
# State
# ---------------------------------------------------------------------------


def diloco_init(model: Model, dcfg: DiLoCoConfig, inner_cfg: OptimizerConfig, rng: jax.Array) -> PyTree:
    # imported lazily: repro.engine builds on repro.core, not the reverse
    from repro.engine.state import TrainState

    if dcfg.sync_delay:
        if not dcfg.outer_enabled:
            raise ValueError("sync_delay requires the outer optimizer "
                             "(outer_enabled=False has no pseudogradient to delay)")
        if dcfg.streaming_partitions > 1:
            raise ValueError("sync_delay cannot be combined with streaming "
                             "(J>1) segment syncs")
    params = model.init(rng)
    K = dcfg.n_workers
    worker_params = jax.tree.map(lambda p: jnp.broadcast_to(p[None], (K, *p.shape)), params)
    opt = make_optimizer(dcfg, inner_cfg)
    inner_state = jax.vmap(opt.init)(worker_params)
    outer = make_outer(dcfg, state_dtype=inner_cfg.state_dtype)
    # the pending FIFO starts as zeros: the first sync_delay rounds apply a
    # zero pseudogradient (the outer params hold still while the pipeline
    # fills), exactly the cold-start a delayed production sync would see
    pending = (jax.tree.map(
        lambda p: jnp.zeros((dcfg.sync_delay, *p.shape), jnp.float32), params)
        if dcfg.sync_delay else None)
    return TrainState(
        outer_params=params,
        outer_opt=outer.init_opt(params),
        worker_params=worker_params,
        inner_state=inner_state,
        round=jnp.zeros((), jnp.int32),
        ef=outer.init_ef(params, K),
        participation=(jnp.ones((K,), jnp.float32) if dcfg.elastic else None),
        pending=pending,
        health=health_init(dcfg.health),
    )


def _updated(state: PyTree, **kw) -> PyTree:
    """Functional update working on both TrainState and legacy dict states."""
    if hasattr(state, "replace"):
        return state.replace(**kw)
    new = dict(state)
    new.update(kw)
    return new


# ---------------------------------------------------------------------------
# Inner step (runs every step; no cross-worker communication)
# ---------------------------------------------------------------------------


def inner_step(model: Model, opt, state: PyTree, batch: PyTree,
               spmd_axis: str | None = None,
               participation: jax.Array | None = None) -> tuple[PyTree, dict]:
    """One local optimizer step on every worker. batch leaves: [K, B/K, ...].

    ``spmd_axis='pod'`` tells GSPMD the vmapped worker axis lives on the pod
    mesh axis, so activation sharding constraints inside the model compose
    with the worker dimension on the production mesh.

    An elastic ``participation`` mask ([K] {0,1}) freezes dropped workers in
    place: their params and inner-optimizer state come back bit-identical
    (``where`` on the mask) and the reported loss is the mean over the
    surviving workers only. The all-ones mask selects every new value
    elementwise, so it is bitwise-equal to the maskless program."""

    def one(params_k, inner_k, batch_k):
        with jax.named_scope(FWD_BWD):
            (loss, metrics), grads = jax.value_and_grad(model.loss, has_aux=True)(params_k, batch_k)
        with jax.named_scope(INNER_OPT):
            new_p, new_s = opt.step(params_k, grads, inner_k)
        return new_p, new_s, loss

    new_wp, new_is, losses = jax.vmap(one, spmd_axis_name=spmd_axis)(
        state["worker_params"], state["inner_state"], batch)
    if participation is None:
        loss = jnp.mean(losses)
    else:
        pk = participation.astype(jnp.float32)

        def freeze(new, old):
            pb = pk.reshape((pk.shape[0],) + (1,) * (new.ndim - 1))
            return jnp.where(pb > 0, new, old)

        new_wp = jax.tree.map(freeze, new_wp, state["worker_params"])
        new_is = jax.tree.map(freeze, new_is, state["inner_state"])
        # reciprocal form: bitwise == jnp.mean for the all-ones mask
        loss = jnp.sum(pk * losses) * (1.0 / jnp.maximum(jnp.sum(pk), 1.0))
    new_state = _updated(state, worker_params=new_wp, inner_state=new_is)
    return new_state, {"loss": loss, "loss_per_worker": losses}


# ---------------------------------------------------------------------------
# Outer step (the only cross-worker communication)
# ---------------------------------------------------------------------------


@jax.named_scope(PSEUDOGRAD)
def compute_deltas(state: PyTree) -> PyTree:
    """Δ_k = θ_outer − θ_k, stacked [K, ...] (paper Alg. 1 line 9)."""
    return jax.tree.map(
        lambda o, w: o.astype(jnp.float32)[None] - w.astype(jnp.float32),
        state["outer_params"], state["worker_params"],
    )


_FROM_STATE = object()  # sentinel: outer_step reads participation off the state


def outer_step(dcfg: DiLoCoConfig, state: PyTree, mask: PyTree | None = None,
               outer: OuterOptimizer | None = None,
               participation: jax.Array | None = _FROM_STATE) -> tuple[PyTree, PyTree]:
    """Communicate + outer update (+ worker reset). Returns (state, Ψ).

    The pseudogradient path Δ -> compress/EF -> reduce -> outer descent runs
    through the declared :class:`OuterOptimizer` chain (built from ``dcfg``
    when not supplied — the engine builds it once and threads it through).

    Elastic execution reads the [K] participation mask from the TrainState
    (pass ``participation=None`` explicitly to force the dense program — the
    all-ones branch of :func:`diloco_round`'s runtime cond does this so the
    full-participation round is the *literal* maskless computation, bitwise):
    dropped workers' deltas are excluded from the reduce, their EF residuals
    come back frozen, and every worker — dropped ones included — resets to
    the new outer params (rejoin IS the broadcast; a dropped worker did no
    inner steps, so overwriting its frozen replica is unobservable).

    With ``dcfg.sync_delay = d > 0`` the fresh pseudogradient Ψ_r enters the
    ``pending`` FIFO while the descent applies ``pending[0]`` = Ψ_{r-d}:
    round r+1 starts from params that have not yet absorbed Ψ_r, which is
    what lets a real deployment overlap the sync with the next round's
    compute. Communication, EF accumulation, and byte accounting all happen
    at round r — only the *application* is late.

    With ``dcfg.outer_enabled=False`` (the DP degenerate config) the synced
    params are simply the K-mean of the worker params: no outer transform, no
    compression, no worker reset — at K=1 this is exactly the plain inner
    optimizer, through the same code path as DiLoCo/MuLoCo.
    """
    from repro.core.collectives import participation_mean

    if participation is _FROM_STATE:
        participation = state.get("participation")
    deltas = compute_deltas(state)
    if not dcfg.outer_enabled:
        if mask is not None:
            raise ValueError(
                "streaming (partitioned) sync requires the outer optimizer; "
                "outer_enabled=False cannot be combined with streaming_partitions > 1")
        if participation is None or dcfg.n_workers == 1:
            # legacy dense program (a K=1 elastic mask is always all-ones)
            psi = jax.tree.map(lambda d: jnp.mean(d, axis=0), deltas)
            new_outer = jax.tree.map(
                lambda o, w: jnp.mean(w.astype(jnp.float32), axis=0).astype(o.dtype)
                if w.shape[0] > 1 else w[0],
                state["outer_params"], state["worker_params"],
            )
        else:
            psi = jax.tree.map(
                lambda d: participation_mean(d, participation), deltas)
            new_outer = jax.tree.map(
                lambda o, w: participation_mean(
                    w.astype(jnp.float32), participation).astype(o.dtype),
                state["outer_params"], state["worker_params"],
            )
        # broadcast the averaged params back so workers stay synced (at K=1
        # this is the identity; at K>1 it is every-H parameter averaging —
        # without it the replicas would silently drift apart forever)
        new_workers = jax.tree.map(
            lambda o, w: jnp.broadcast_to(o[None].astype(w.dtype), w.shape),
            new_outer, state["worker_params"],
        )
        return _updated(state, outer_params=new_outer, worker_params=new_workers,
                        round=state["round"] + 1), psi
    if mask is not None:
        deltas = jax.tree.map(lambda m, d: m[None] * d if m.ndim else m * d, mask, deltas)

    outer = outer or make_outer(dcfg)
    if dcfg.sync_delay:
        if mask is not None:
            raise ValueError("sync_delay cannot be combined with streaming "
                             "(J>1) segment syncs")
        pending = state.get("pending")
        if pending is None:
            raise ValueError("sync_delay > 0 needs the pending FIFO in the "
                             "TrainState; build it with diloco_init on a "
                             "config with the same sync_delay")
        psi, new_ef = outer.reduce(state["outer_params"], deltas,
                                   state.get("ef"),
                                   participation=participation)
        stale_psi = jax.tree.map(lambda q: q[0], pending)
        new_outer, new_opt = outer.descend(state["outer_params"], stale_psi,
                                           state["outer_opt"])
        new_pending = jax.tree.map(
            lambda q, pn: jnp.concatenate(
                [q[1:], pn[None].astype(q.dtype)], axis=0),
            pending, psi)
    else:
        new_pending = None
        new_outer, new_opt, new_ef, psi = outer.step(
            state["outer_params"], deltas, state["outer_opt"], state.get("ef"),
            mask=mask, participation=participation)

    # broadcast synced params back to workers (masked portions only)
    @jax.named_scope(OUTER_UPDATE)
    def reset(o, w, m=None):
        ob = jnp.broadcast_to(o[None].astype(w.dtype), w.shape)
        if m is None:
            return ob
        mm = m[None] if m.ndim else m
        return (mm * ob.astype(jnp.float32) + (1 - mm) * w.astype(jnp.float32)).astype(w.dtype)

    if mask is None:
        new_workers = jax.tree.map(reset, new_outer, state["worker_params"])
    else:
        new_workers = jax.tree.map(lambda o, w, m: reset(o, w, m), new_outer, state["worker_params"], mask)

    updates: dict = dict(outer_params=new_outer, outer_opt=new_opt,
                         worker_params=new_workers)
    if new_ef is not None:
        updates["ef"] = new_ef
    if new_pending is not None:
        updates["pending"] = new_pending
    updates["round"] = state["round"] + 1
    return _updated(state, **updates), psi


# ---------------------------------------------------------------------------
# Full round(s): H inner steps + sync (jit-able end to end)
# ---------------------------------------------------------------------------


def diloco_round(model: Model, dcfg: DiLoCoConfig, opt, state: PyTree, batches: PyTree,
                 masks: list[PyTree] | None = None,
                 spmd_axis: str | None = None,
                 outer: OuterOptimizer | None = None) -> tuple[PyTree, dict]:
    """One communication round: H inner steps then outer sync(s).

    This is THE round function: ``lax.scan`` over the H inner steps with the
    outer sync (and, for streaming, the J per-segment partition syncs —
    statically unrolled, since each segment carries a different mask) folded
    into the same traced program. The sync itself is not hand-wired here: it
    is the declared pseudogradient transform chain Δ -> compress/EF ->
    reduce -> outer descent built by :func:`make_outer` and threaded through
    ``outer_step``. :class:`repro.engine.TrainEngine` wraps this function in
    the superstep executor (``lax.scan`` over R rounds per dispatch,
    :mod:`repro.engine.superstep`), compiles it once, donated, and every
    training path (train / dryrun / bench / examples) executes it.

    ``batches`` leaves: [H, K, B/K, ...]. With streaming (J>1) the round is J
    segments of H/J steps, each followed by a partition-j sync — peak
    bandwidth drops by J while the sync period per partition stays H.

    Returns ``(state, {"loss": f32[H], "psi": pseudogradient_tree,
    "comm_bytes": f32[], "active_workers": f32[], "staleness": f32[]})`` for
    every J; with J>1 the ``psi`` leaves are the mask-combined per-segment
    pseudogradients (each parameter's entry comes from the segment that
    synced it), so the signature is identical to the J==1 path.
    ``comm_bytes`` is the round's measured per-worker wire traffic — read
    off the actual wire buffer shapes/dtypes the sync(s) move
    (:func:`repro.core.collectives.measured_sync_bytes`), summed over the J
    segment syncs (each segment ships its partition's share). On an elastic
    round the dense total is scaled by the surviving-worker fraction
    ``sum(p)/K`` — dropped workers' packets are never encoded, so they are
    not charged. The metric travels as f32 (x64 is disabled), so above
    ~16.7 MB/round it carries ~7 significant digits; exact integers come
    from calling ``measured_sync_bytes`` directly. ``active_workers`` is
    the round's surviving-worker count (== K on non-elastic rounds) and
    ``staleness`` the config's ``sync_delay``, threaded out so the driver
    can log them per round.
    """
    H, J = dcfg.sync_interval, dcfg.streaming_partitions
    participation = state.get("participation")
    if dcfg.sync_delay and J > 1:
        raise ValueError("sync_delay cannot be combined with streaming "
                         "(J>1) segment syncs")

    def sync_bytes(mask=None) -> int:
        return measured_sync_bytes(state["outer_params"], dcfg.compression,
                                   dcfg.n_workers, mask=mask,
                                   outer_enabled=dcfg.outer_enabled)

    def comm_metric(dense_bytes: int) -> jax.Array:
        """Dense per-worker wire bytes, fraction-scaled on elastic rounds.

        The ``c * (sum(p)/K)`` op order matters: ``sum(p)/K`` is exactly 1.0
        for the all-ones mask at any K, so the dense program's
        ``asarray(bytes)`` value comes back bit-identical."""
        c = jnp.asarray(dense_bytes, jnp.float32)
        if participation is None:
            return c
        p = participation.astype(jnp.float32)
        return c * (jnp.sum(p) / jnp.float32(dcfg.n_workers))

    active = (jnp.sum(participation.astype(jnp.float32))
              if participation is not None
              else jnp.asarray(float(dcfg.n_workers), jnp.float32))
    staleness = jnp.asarray(float(dcfg.sync_delay), jnp.float32)

    def scan_inner(state, seg_batches, part):
        # carry only what the inner steps mutate: outer params/opt, EF
        # residuals and the round counter are loop-invariant and stay out of
        # the while-loop state.
        def body(carry, b):
            sub = {"worker_params": carry[0], "inner_state": carry[1]}
            sub, m = inner_step(model, opt, sub, b, spmd_axis=spmd_axis,
                                participation=part)
            return (sub["worker_params"], sub["inner_state"]), m["loss"]

        (wp, ins), losses = jax.lax.scan(
            body, (state["worker_params"], state["inner_state"]), seg_batches)
        return _updated(state, worker_params=wp, inner_state=ins), losses

    if J <= 1:
        comm = sync_bytes()

        def run_round(state, part):
            state, losses = scan_inner(state, batches, part)
            with jax.named_scope(OUTER_SYNC):
                state, psi = outer_step(dcfg, state, outer=outer,
                                        participation=part)
            return state, losses, psi

        def finish(state, losses, psi):
            # health sentinel rides AFTER the participation cond so the flag
            # sees the round's final losses/psi whichever branch produced
            # them; with no health leaf this is the identity (zero ops)
            health = state.get("health")
            info = {"loss": losses, "psi": psi,
                    "comm_bytes": comm_metric(comm),
                    "active_workers": active, "staleness": staleness}
            if health is not None:
                new_health, flag = health_update(dcfg.health, health, losses, psi)
                state = _updated(state, health=new_health)
                info["health"] = flag
            return state, info

        if participation is None:
            state, losses, psi = run_round(state, None)
        else:
            # Runtime two-way dispatch: the full-participation round executes
            # the LITERAL dense program (same ops, same fusions — the masked
            # program's extra selects perturb XLA fusion by 1 ulp even under
            # an all-ones mask), so elastic configs stay bitwise-equal to the
            # maskless path whenever nobody dropped. Only genuinely degraded
            # rounds pay for the masked computation.
            state, losses, psi = jax.lax.cond(
                jnp.all(participation > 0),
                lambda st: run_round(st, None),
                lambda st: run_round(st, participation),
                state)
        return finish(state, losses, psi)

    if H % J:
        raise ValueError(
            f"streaming requires the partition count to divide the sync "
            f"interval: J={J} does not divide H={H}")
    if masks is None:
        raise ValueError(
            "streaming (J>1) requires partition masks; build them with "
            "make_streaming_masks(state, dcfg)")
    seg = H // J
    comm = sum(sync_bytes(mask=masks[j]) for j in range(J))

    def run_segments(state, part):
        all_losses = []
        psi_acc = None
        for j in range(J):
            seg_batches = jax.tree.map(lambda b: b[j * seg : (j + 1) * seg], batches)
            state, losses = scan_inner(state, seg_batches, part)
            with jax.named_scope(OUTER_SYNC):
                state, psi_j = outer_step(dcfg, state, mask=masks[j], outer=outer,
                                          participation=part)
            # psi leaves are un-stacked (no K axis): the masks broadcast directly
            masked_j = jax.tree.map(lambda m, p: m * p, masks[j], psi_j)
            psi_acc = masked_j if psi_acc is None else jax.tree.map(jnp.add, psi_acc, masked_j)
            all_losses.append(losses)
        return state, jnp.concatenate(all_losses), psi_acc

    if participation is None:
        state, losses, psi = run_segments(state, None)
    else:
        # same two-way dispatch as J==1: all-ones -> the literal dense
        # J-segment program, any drop -> the masked program
        state, losses, psi = jax.lax.cond(
            jnp.all(participation > 0),
            lambda st: run_segments(st, None),
            lambda st: run_segments(st, participation),
            state)
    info = {"loss": losses, "psi": psi, "comm_bytes": comm_metric(comm),
            "active_workers": active, "staleness": staleness}
    health = state.get("health")
    if health is not None:  # same post-cond sentinel as the J==1 path
        new_health, flag = health_update(dcfg.health, health, losses, psi)
        state = _updated(state, health=new_health)
        info["health"] = flag
    return state, info


def make_streaming_masks(state: PyTree, dcfg: DiLoCoConfig) -> list[PyTree] | None:
    if dcfg.streaming_partitions <= 1:
        return None
    return streaming_masks(state["outer_params"], dcfg.streaming_partitions)


# ---------------------------------------------------------------------------
# Data-parallel baseline: the degenerate (K=1, H=1, no-outer) engine config.
# dp_init/dp_step are thin adapters over the same inner_step used by DiLoCo —
# one code path for DP AdamW / DP Muon and MuLoCo/DiLoCo alike.
# ---------------------------------------------------------------------------


def dp_init(model: Model, inner_name: str, inner_cfg: OptimizerConfig, rng: jax.Array):
    params = model.init(rng)
    opt = make_inner_optimizer(inner_name, inner_cfg)
    return {"params": params, "opt_state": opt.init(params)}, opt


def dp_step(model: Model, opt, state: PyTree, batch: PyTree) -> tuple[PyTree, dict]:
    """One DP step == one DiLoCo inner step at K=1 (shared implementation)."""
    stacked = {
        "worker_params": jax.tree.map(lambda p: p[None], state["params"]),
        "inner_state": jax.tree.map(lambda s: s[None], state["opt_state"]),
    }
    new, metrics = inner_step(model, opt, stacked, jax.tree.map(lambda x: x[None], batch))
    return {
        "params": jax.tree.map(lambda p: p[0], new["worker_params"]),
        "opt_state": jax.tree.map(lambda s: s[0], new["inner_state"]),
    }, {"loss": metrics["loss"]}
