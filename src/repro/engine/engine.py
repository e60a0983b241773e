"""TrainEngine: one donated, fully-jitted multi-round executor for every path.

The paper's hot loop — H inner steps + the outer sync — used to be re-wired
by hand in four places (launch/train.py, launch/dryrun.py, benchmarks,
examples), each with its own jit boundary, no buffer donation, and host
round-trips for metrics. The engine collapses them to a single builder:

  * ``TrainEngine(model, dcfg, icfg)`` compiles **one** jitted executor:
    ``lax.scan`` over the H inner steps, the outer sync — the declared
    pseudogradient transform chain of :func:`repro.core.diloco.make_outer`
    (Δ -> compress/EF -> reduce -> outer descent), plus the J streaming
    segment syncs — folded inside, and (via
    :mod:`repro.engine.superstep`) an outer ``lax.scan`` running R whole
    communication rounds per dispatch. The TrainState argument is
    **donated**, so rounds update in place instead of double-buffering the
    4 parameter-sized state copies;
  * on the production mesh the same builder threads the StepPlan shardings
    (worker axis -> 'pod', FSDP/TP within a pod) and activation rules through
    ``jax.jit``, so the CPU path and the 512-chip path lower from the same
    code;
  * the DP baseline is the degenerate config ``dp_config(inner)`` (K=1, H=1,
    no outer), and the single-round ``engine.step`` is the degenerate R=1
    case of the same superstep builder: DP AdamW / DP Muon, DiLoCo/MuLoCo,
    and single- vs multi-round dispatch all share one executor;
  * dispatch is asynchronous — metrics come back as device buffers
    (``[R, H]`` losses, ``[R]`` eval losses), and
    :mod:`repro.engine.driver` drains them on the host once per superstep
    while the next superstep is already running.
"""
from __future__ import annotations

from typing import Any, Callable

import jax

from repro.core.diloco import (
    DiLoCoConfig,
    diloco_init,
    diloco_round,
    dp_config,
    make_optimizer,
    make_outer,
)
from repro.engine.state import TrainState
from repro.engine.superstep import build_superstep_fn
from repro.models.api import Model
from repro.optim import OptimizerConfig
from repro.tracing import EVAL

PyTree = Any


def build_round_fn(model: Model, dcfg: DiLoCoConfig, opt,
                   masks: list[PyTree] | None = None,
                   rules: dict | None = None,
                   spmd_axis: str | None = None,
                   outer=None, kernel_parts=None) -> Callable:
    """The un-jitted round callable shared by the engine and the dry-run
    StepPlans: H inner steps + sync(s) in one traceable program, with the
    activation-sharding rules (if any) and the kernel shard_map routing
    (``kernel_parts``, see :func:`repro.launch.sharding.kernel_specs`)
    installed around the whole round — both are trace-time contexts, so one
    installation covers every inner step, the wire stages, and the outer
    sync. ``outer`` is the declared pseudogradient chain (built from
    ``dcfg`` when omitted)."""

    def round_fn(state: PyTree, batches: PyTree) -> tuple[PyTree, dict]:
        from contextlib import nullcontext

        from repro.kernels.partition import kernel_partitioning
        from repro.models.common import activation_sharding

        act = activation_sharding(rules) if rules is not None else nullcontext()
        with act, kernel_partitioning(kernel_parts):
            return diloco_round(model, dcfg, opt, state, batches,
                                masks=masks, spmd_axis=spmd_axis, outer=outer)

    return round_fn


class TrainEngine:
    """Compiles and executes DiLoCo/MuLoCo (or DP) rounds.

    Usage::

        engine = TrainEngine(model, dcfg, icfg)
        state = engine.init(jax.random.PRNGKey(0))
        for r in range(rounds):
            state, info = engine.step(state, batches_for_round(stream, r, H))

        # or R rounds in ONE dispatch (leaves [R, H, K, B, ...]):
        state, out = engine.superstep(state, batches_for_span(stream, 0, H, R))

    ``step``/``superstep`` donate the incoming state; never reuse a state you
    passed in. For overlapping dispatch with host-side metrics draining use
    :func:`repro.engine.driver.run_rounds`.
    """

    def __init__(self, model: Model, dcfg: DiLoCoConfig, icfg: OptimizerConfig,
                 *, mesh=None, donate: bool = True,
                 rules: dict | None = None, spmd_axis: str | None = None,
                 kernel_parts=None):
        self.model = model
        self.dcfg = dcfg
        self.icfg = icfg
        self.opt = make_optimizer(dcfg, icfg)
        self.outer = make_outer(dcfg, state_dtype=icfg.state_dtype)
        self.mesh = mesh
        self.donate = donate
        self._rules = rules
        self._spmd_axis = spmd_axis
        if kernel_parts is None and mesh is not None:
            # default routing: shard_map the Pallas call sites on the
            # engine's mesh (None on single-device worlds)
            from repro.launch.sharding import kernel_specs

            kernel_parts = kernel_specs(mesh, getattr(model, "cfg", None))
        self.kernel_parts = kernel_parts
        self._masks = self._build_masks()
        self.round_fn = build_round_fn(model, dcfg, self.opt, masks=self._masks,
                                       rules=rules, spmd_axis=spmd_axis,
                                       outer=self.outer,
                                       kernel_parts=kernel_parts)
        # ONE eval closure serves both the in-superstep folded eval and the
        # standalone eval_loss jit — they must stay bitwise-identical (the
        # kernel routing context applies here too: folded eval runs outside
        # round_fn's context, and an un-shard_mapped pallas call would fail
        # to lower on the mesh)
        from repro.kernels.partition import kernel_partitioning

        def eval_loss_fn(params, batch):
            with kernel_partitioning(self.kernel_parts), jax.named_scope(EVAL):
                return model.loss(params, batch)[0]
        # In-program checkpoint plumbing: the superstep's io_callback lands
        # in _emit_checkpoint, which forwards to whatever sink the driver
        # installed for the current run (checkpoint_sink is host-side mutable
        # state read at EXECUTION time, so one compiled trace serves every
        # run regardless of where its checkpoints go).
        self.checkpoint_sink: Callable | None = None
        self.superstep_fn = build_superstep_fn(self.round_fn,
                                               eval_loss_fn=eval_loss_fn,
                                               checkpoint_cb=self._emit_checkpoint)
        self._jitted: Callable | None = None
        self._eval_loss = jax.jit(eval_loss_fn)
        # driver telemetry: every superstep/step dispatch increments this —
        # the single-dispatch acceptance test (and the CI smoke) pins it
        self.dispatch_count = 0

    def _emit_checkpoint(self, state_dev: PyTree) -> None:
        """Host side of the in-program checkpoint io_callback.

        Receives the scan carry as a same-structure TrainState whose leaves
        are device arrays (bit-identical to what ``jax.device_get`` of the
        live state would return at that round — the callback reads the
        carry, it never re-computes anything). The sink MUST NOT block on a
        host transfer (``np.asarray`` / ``device_get``): this runs on the
        XLA callback thread while the dispatch that fired it is still
        executing, and on the CPU backend that transfer is serviced by the
        very thread parked inside the callback custom call — it deadlocks.
        Sinks stash the arrays and let the driver convert them from the
        main thread once the dispatch has drained."""
        sink = self.checkpoint_sink
        if sink is not None:
            sink(state_dev)

    # -- construction helpers ----------------------------------------------

    def _build_masks(self) -> list[PyTree] | None:
        if self.dcfg.streaming_partitions <= 1:
            return None
        params_abs = jax.eval_shape(
            lambda: self.model.init(jax.random.PRNGKey(0)))
        from repro.core.streaming import streaming_masks

        return streaming_masks(params_abs, self.dcfg.streaming_partitions)

    def abstract_state(self) -> TrainState:
        """ShapeDtypeStruct TrainState (nothing allocated)."""
        return jax.eval_shape(
            lambda: diloco_init(self.model, self.dcfg, self.icfg,
                                jax.random.PRNGKey(0)))

    def state_shardings(self, tensor_parallel: bool = True) -> TrainState:
        """StepPlan-compatible shardings for the TrainState on ``mesh``."""
        if self.mesh is None:
            raise ValueError("engine was built without a mesh")
        from repro.launch.sharding import diloco_state_shardings

        return diloco_state_shardings(self.mesh, self.abstract_state(),
                                      tensor_parallel=tensor_parallel)

    def place_state(self, state: TrainState, tensor_parallel: bool = True) -> TrainState:
        """Commit a TrainState to the mesh under the StepPlan shardings."""
        return jax.device_put(state, self.state_shardings(tensor_parallel))

    def place_batches(self, batches: PyTree, leading_scan: int = 1) -> PyTree:
        """Commit [H, K, B, ...] round batches (K->'pod', B->'data').

        ``leading_scan`` counts the unsharded scanned axes: 1 for a round's
        [H, ...] batches, 2 for a superstep's [R, H, ...] batches."""
        if self.mesh is None:
            return batches
        from repro.launch.sharding import batch_shardings

        return jax.device_put(
            batches, batch_shardings(self.mesh, batches, k_stacked=True,
                                     leading_scan=leading_scan))

    @property
    def jitted_round(self) -> Callable:
        """THE donated, jitted executor (compiled lazily).

        One jit object serves every dispatch width: each distinct
        (R, with/without eval) signature traces the same superstep builder
        once; R == 1 without eval *is* the single-round program."""
        if self._jitted is None:
            kw: dict = {}
            if self.donate:
                kw["donate_argnums"] = (0,)
            self._jitted = jax.jit(self.superstep_fn, **kw)
        return self._jitted

    # -- execution ----------------------------------------------------------

    def init(self, rng: jax.Array) -> TrainState:
        return diloco_init(self.model, self.dcfg, self.icfg, rng)

    def step(self, state: TrainState, batches: PyTree,
             participation: PyTree | None = None) -> tuple[TrainState, dict]:
        """One communication round; async dispatch, donated state.

        The degenerate R=1 dispatch of :meth:`superstep` — same executor,
        single-round metrics (``loss`` [H] plus the round's ``psi``). On a
        mesh, the committed shardings of ``state`` (see :meth:`place_state`)
        and the batches propagate through jit, so the round lowers with the
        production layout. ``participation`` is the round's [K] elastic
        worker mask (elastic configs only)."""
        state, out = self.superstep(
            state, jax.tree.map(lambda b: b[None], batches),
            participation=(None if participation is None
                           else jax.tree.map(lambda p: p[None], participation)))
        info = {k: (v if k == "psi" else v[0]) for k, v in out.items()}
        return state, info

    def superstep(self, state: TrainState, batches: PyTree,
                  eval_batches: PyTree | None = None,
                  participation: PyTree | None = None,
                  ckpt_flags: PyTree | None = None) -> tuple[TrainState, dict]:
        """R communication rounds in ONE dispatch; donated state.

        ``batches`` leaves are round-stacked [R, H, K, B, ...]. Returns
        ``(state, {"loss": f32[R, H]})`` plus ``"eval_loss": f32[R]`` when
        ``eval_batches`` (leaves [R, B, ...]) are supplied — the post-sync
        outer params of every round are evaluated inside the same program.
        ``participation`` ([R, K] float32 {0,1}, elastic configs only)
        supplies each round's worker mask; the scan threads row r into the
        state carry before round r runs. ``ckpt_flags`` ([R] bool) marks the
        rounds whose post-round state is emitted to the host through the
        in-program io_callback (install :attr:`checkpoint_sink` first) —
        this is what lets a whole run with a checkpoint cadence execute as
        one dispatch.
        """
        import jax.numpy as jnp

        self.dispatch_count += 1
        if participation is not None:
            participation = jnp.asarray(participation, jnp.float32)
        if ckpt_flags is not None:
            ckpt_flags = jnp.asarray(ckpt_flags, bool)
        if self.mesh is not None:
            from repro.launch.sharding import batch_shardings

            with jax.set_mesh(self.mesh):
                if eval_batches is not None:
                    eval_batches = jax.device_put(
                        eval_batches, batch_shardings(
                            self.mesh, eval_batches, k_stacked=False,
                            leading_scan=1))
                return self.jitted_round(
                    state, self.place_batches(batches, leading_scan=2),
                    eval_batches, participation, ckpt_flags)
        return self.jitted_round(state, batches, eval_batches, participation,
                                 ckpt_flags)

    def eval_loss(self, params: PyTree, batch: PyTree) -> jax.Array:
        """Loss of the synced (outer) params on one un-stacked batch."""
        return self._eval_loss(params, batch)

    # -- introspection (used by the no-retrace / donation tests) ------------

    def lower(self, state: TrainState, batches: PyTree):
        """Lower the degenerate R=1 dispatch (the single-round program)."""
        return self.jitted_round.lower(
            state, jax.tree.map(lambda b: b[None], batches), None, None)


def dp_engine(model: Model, inner_name: str, icfg: OptimizerConfig,
              **kw) -> TrainEngine:
    """The data-parallel baseline as the degenerate engine config."""
    return TrainEngine(model, dp_config(inner_name), icfg, **kw)
