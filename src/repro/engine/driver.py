"""Async superstep driver: overlap dispatch with host-side metrics drain.

JAX dispatch is asynchronous: ``engine.superstep`` returns device values
immediately while the rounds execute. The driver exploits that twice over:

* **R rounds per dispatch** — with ``rounds_per_dispatch=R`` the engine runs
  R whole communication rounds inside one ``lax.scan`` program
  (:mod:`repro.engine.superstep`), so the host touches the device once per
  superstep instead of once per round. R is auto-clamped
  (:func:`repro.engine.superstep.effective_rounds_per_dispatch`) to divide
  both the remaining rounds and the checkpoint cadence, which is how
  eval/checkpoint schedules survive multi-round dispatch without any
  in-program branching.
* **late metric reads** — up to ``max_in_flight`` dispatches' metrics stay
  un-materialized: the host converts a superstep's ``[R, H]`` loss buffer
  (and ``[R]`` eval-loss / measured ``comm_bytes`` buffers) to floats — a
  blocking device read — only
  after the next superstep has already been dispatched, so data generation +
  CSV writing + logging ride for free under the accelerator's compute. The
  seed-era loops blocked on ``float(info["loss"].mean())`` every round,
  serializing host and device.

Crash safety rides the same drain: when the config arms the health sentinel
(:mod:`repro.core.health`) the per-round ``[R]`` flag buffer is drained with
the other metrics, and a :class:`repro.engine.recovery.RecoveryPolicy` turns
a nonzero flag into rollback-to-last-valid-checkpoint + skip-the-bad-span +
bounded LR-backoff escalation — all host-side, so the device program never
branches on health. A ``should_stop`` probe (SIGTERM/SIGINT in
``launch/train.py``) lets a preempted run finish its in-flight dispatches,
drain every metric, and return a checkpointable state instead of dying
mid-span, and an ``inject`` hook (``core/faults.CrashPlan``) corrupts
chosen spans so every recovery path is provable end-to-end.
"""
from __future__ import annotations

import collections
import functools
from typing import Any, Callable

import jax
import numpy as np

from repro.engine.recovery import RecoveryPolicy, TrainingAborted
from repro.engine.superstep import effective_rounds_per_dispatch
from repro.tracing import CHECKPOINT, DATAGEN, DISPATCH, DRAIN, RECOVERY, RUN_ROUNDS

PyTree = Any


class _Fault(Exception):
    """Internal: a drained health buffer carried a nonzero flag."""

    def __init__(self, round: int, code: int):
        super().__init__(f"health flag {code} at round {round}")
        self.round = round
        self.code = code


def _replace(state: PyTree, **kw) -> PyTree:
    if hasattr(state, "replace"):
        return state.replace(**kw)
    new = dict(state)
    new.update(kw)
    return new


def _with_round(state: PyTree, value: int) -> PyTree:
    """Set the on-device round counter, preserving dtype and placement."""
    old = state["round"]
    new = np.asarray(value, getattr(old, "dtype", np.int32))
    sharding = getattr(old, "sharding", None)
    if sharding is not None:
        new = jax.device_put(new, sharding)
    return _replace(state, round=new)


def _span(name: str):
    """Decorator: each call runs inside the host span ``name``."""
    return functools.partial(jax.profiler.annotate_function, name=name)


@_span(RUN_ROUNDS)
def run_rounds(engine, state, batches_for: Callable[[int], PyTree],
               rounds: int, *, start: int = 0,
               rounds_per_dispatch: int | str = 1,
               span_batches_for: Callable[[int, int], PyTree] | None = None,
               eval_batches_for: Callable[[int, int], PyTree] | None = None,
               eval_fn: Callable[[Any, int], jax.Array] | None = None,
               participation_for: Callable[[int, int], Any] | None = None,
               on_round: Callable[[dict], None] | None = None,
               on_state: Callable[[int, Any], None] | None = None,
               on_state_every: int = 1,
               checkpoint_in_program: bool = False,
               host_overhead_s: float | None = None,
               device_round_s: float | None = None,
               telemetry: dict | None = None,
               max_in_flight: int = 2,
               recovery: RecoveryPolicy | None = None,
               should_stop: Callable[[], bool] | None = None,
               inject: Callable[[int, int, PyTree, Any], tuple[PyTree, Any]] | None = None,
               ) -> tuple[Any, list[dict]]:
    """Run rounds ``start..rounds-1`` through the engine.

    ``batches_for(r)`` supplies the [H, K, B, ...] batches for round r; with
    ``rounds_per_dispatch > 1``, ``span_batches_for(r0, n)`` (when given)
    supplies the round-stacked [n, H, K, B, ...] leaves for rounds
    ``r0..r0+n-1`` in one call — otherwise the driver stacks ``batches_for``
    on host. ``eval_batches_for(r0, n)`` (optional) supplies [n, B, ...]
    eval batches; the engine then computes every round's post-sync eval loss
    *inside* the superstep program. ``eval_fn(state, r)`` is the legacy
    host-side alternative (a separately-jitted device scalar per round); it
    needs the state between rounds, so it pins the dispatch width to R=1.

    ``rounds_per_dispatch`` may be the string ``"auto"``: the dispatch cost
    model (:func:`repro.engine.superstep.auto_rounds_per_dispatch`, fed the
    measured ``host_overhead_s`` / ``device_round_s`` when supplied) picks R
    — whole-run single dispatch when unmeasured. Any resolved R replays the
    identical arithmetic bit for bit. The resolved R is re-clamped against
    the remaining span before every dispatch; on a fault-free run the clamp
    is the identity (R already divides everything), so the dispatch schedule
    is unchanged — it only bites when a rollback lands ``r0`` off-schedule.

    ``participation_for(r0, n)`` (elastic runs) supplies the [n, K] float32
    worker masks for rounds ``r0..r0+n-1``; the driver threads them into
    every dispatch and drains the per-round ``active_workers`` /
    ``staleness`` metric buffers into the records alongside the losses.

    ``on_round(metrics)`` fires per round when a superstep's metrics are
    drained to host floats. ``on_state(r, state)`` fires every
    ``on_state_every``-th round (r+1 divisible) with the new state, for
    checkpointing. By default the requested ``rounds_per_dispatch`` is
    clamped to divide that cadence, and all pending metrics are drained
    first so whatever on_round persisted (e.g. the CSV) never lags a saved
    checkpoint. With ``checkpoint_in_program=True`` the cadence clamp is
    dropped entirely: the driver passes per-round boolean ``ckpt_flags``
    into each superstep and installs a sink on the engine: the io_callback
    stashes each flagged round's carry (device arrays — converting on the
    callback thread deadlocks the CPU runtime against the running dispatch)
    and the driver replays the stash through ``on_state`` as numpy
    TrainStates once the producing dispatch has drained — R (and hence
    "auto" = the whole run) no longer needs to divide the checkpoint
    cadence. The carries are captured mid-dispatch but written after it
    completes, so a run killed mid-span keeps its previous checkpoint.

    Crash-safety hooks (all optional, all host-side):

    * ``recovery`` — a :class:`repro.engine.recovery.RecoveryPolicy`. When
      armed and a drained health buffer (the sentinel's per-round flags; see
      ``DiLoCoConfig.health``) is nonzero, the driver records NOTHING from
      the poisoned dispatch, drops every in-flight dispatch and stashed
      checkpoint carry, restores ``recovery.restore()``, advances the round
      counter to ``bad_round + 1`` (the seed-keyed data pipeline never
      replays the offending span), and keeps going — with bounded retries
      escalating through LR backoff to :class:`TrainingAborted`. Without a
      policy, nonzero flags are simply recorded (``health`` in the metrics).
    * ``should_stop`` — probed before each dispatch; when it returns True
      the driver stops dispatching, drains every in-flight superstep, and
      returns (``telemetry["preempted"]`` set) — the caller then writes its
      final checkpoint from a fully-drained state.
    * ``inject(r0, n, batches, state) -> (batches, state)`` — fault
      injection seam (``core/faults.CrashPlan.apply``): may corrupt the
      span-stacked batches or the state before the dispatch. Test/chaos
      only; None is a no-op.

    Every phase runs inside a host span of :mod:`repro.tracing` (the whole
    call, data generation, each dispatch with its first round and R, each
    drain, checkpoints and recovery), so a profiler trace names what the
    host was doing while the device idled.

    ``telemetry`` (optional dict) is filled with the resolved dispatch plan:
    ``rounds_per_dispatch``, ``dispatches`` (incremented as they happen),
    ``in_program_checkpoints`` — plus the recovery counters ``rollbacks``,
    ``skipped_rounds``, ``lr_scale``, and ``preempted``. Returns the final
    state and the per-round metrics.
    """
    span = rounds - start
    in_prog_ckpt = (checkpoint_in_program and on_state is not None
                    and bool(on_state_every) and eval_fn is None)
    cadence = on_state_every if (on_state is not None and not in_prog_ckpt) else 0
    R0 = effective_rounds_per_dispatch(
        rounds_per_dispatch if eval_fn is None else 1, span, cadence,
        start=start, host_overhead_s=host_overhead_s,
        device_round_s=device_round_s)

    pending: collections.deque = collections.deque()
    history: list[dict] = []
    H = engine.dcfg.sync_interval
    if telemetry is not None:
        telemetry.update(rounds_per_dispatch=R0, dispatches=0,
                         in_program_checkpoints=in_prog_ckpt,
                         rollbacks=0, skipped_rounds=0, lr_scale=1.0,
                         preempted=False)
    ckpt_stash: collections.deque = collections.deque()
    if in_prog_ckpt:
        # io_callback sink: the carry arrives as a device-leaf TrainState
        # with the round counter already advanced past the flagged round.
        # The sink only STASHES it — converting here (np.asarray/device_get
        # on the callback thread) deadlocks the CPU runtime against the
        # dispatch that fired the callback; flush_checkpoints converts on
        # the main thread once that dispatch has fully drained.
        def _sink(state_dev):
            ckpt_stash.append(state_dev)

        engine.checkpoint_sink = _sink

    @_span(CHECKPOINT)
    def flush_checkpoints() -> None:
        while ckpt_stash:
            st = jax.tree.map(np.asarray, ckpt_stash.popleft())
            on_state(int(st["round"]) - 1, st)

    @_span(DRAIN)
    def drain_one() -> None:
        r0, n, loss, ev, cb, aw, st, hl = pending.popleft()
        hls = None if hl is None else np.atleast_1d(np.asarray(jax.device_get(hl)))
        if hls is not None and recovery is not None and np.any(hls != 0):
            # poisoned dispatch: record nothing from it — every round after
            # the flagged one trained on corrupted state, and CSV rows for
            # rounds the rollback is about to undo would be lies
            bad = int(np.argmax(hls != 0))
            raise _Fault(r0 + bad, int(hls[bad]))
        losses = np.atleast_2d(np.asarray(jax.device_get(loss)))  # [n, H]
        evs = None if ev is None else np.atleast_1d(np.asarray(jax.device_get(ev)))
        cbs = np.atleast_1d(np.asarray(jax.device_get(cb)))  # [n]
        aws = None if aw is None else np.atleast_1d(np.asarray(jax.device_get(aw)))
        sts = None if st is None else np.atleast_1d(np.asarray(jax.device_get(st)))
        for i in range(n):
            rec = {
                "round": r0 + i,
                "step": (r0 + i + 1) * H,
                "train_loss": float(losses[i].mean()),
                "train_loss_last": float(losses[i, -1]),
                "comm_bytes": float(cbs[i]),
            }
            if aws is not None:
                rec["active_workers"] = float(aws[i])
            if sts is not None:
                rec["staleness"] = float(sts[i])
            if evs is not None:
                rec["eval_loss"] = float(evs[i])
            if hls is not None:
                rec["health"] = float(hls[i])
            history.append(rec)
            if on_round is not None:
                on_round(rec)

    rollbacks_left = recovery.max_rollbacks if recovery is not None else 0
    lr_scale = 1.0
    lr_halvings = 0
    r0 = start
    done = False
    while not done:
        try:
            while r0 < rounds:
                if should_stop is not None and should_stop():
                    if telemetry is not None:
                        telemetry["preempted"] = True
                    break
                R = effective_rounds_per_dispatch(R0, rounds - r0, cadence,
                                                  start=r0)
                # classic path: single-round dispatch + optional host eval
                classic = R == 1 and eval_batches_for is None and not in_prog_ckpt
                with jax.profiler.TraceAnnotation(DATAGEN):
                    masks = (np.asarray(participation_for(r0, R), np.float32)
                             if participation_for is not None else None)
                    if classic:
                        b = batches_for(r0)
                    elif span_batches_for is not None:
                        batches = span_batches_for(r0, R)
                    else:
                        batches = jax.tree.map(
                            lambda *bs: np.stack([np.asarray(b) for b in bs]),
                            *[batches_for(r0 + i) for i in range(R)])
                    eb = (eval_batches_for(r0, R)
                          if eval_batches_for is not None else None)
                if classic:
                    if inject is not None:
                        b1, state = inject(
                            r0, 1, jax.tree.map(lambda x: np.asarray(x)[None], b),
                            state)
                        b = jax.tree.map(lambda x: x[0], b1)
                    with jax.profiler.TraceAnnotation(DISPATCH, round=r0, rounds=R):
                        state, info = engine.step(
                            state, b,
                            participation=None if masks is None else masks[0])
                        ev = eval_fn(state, r0) if eval_fn is not None else None
                    loss, cb = info["loss"], info["comm_bytes"]
                    aw, st = info.get("active_workers"), info.get("staleness")
                    hl = info.get("health")
                else:
                    if inject is not None:
                        batches, state = inject(r0, R, batches, state)
                    flags = (np.asarray([(r0 + i + 1) % on_state_every == 0
                                         for i in range(R)], bool)
                             if in_prog_ckpt else None)
                    with jax.profiler.TraceAnnotation(DISPATCH, round=r0, rounds=R):
                        state, out = engine.superstep(state, batches, eb,
                                                      participation=masks,
                                                      ckpt_flags=flags)
                    ev = out.get("eval_loss")
                    loss, cb = out["loss"], out["comm_bytes"]
                    aw, st = out.get("active_workers"), out.get("staleness")
                    hl = out.get("health")
                if telemetry is not None:
                    telemetry["dispatches"] += 1
                # keep only the metric buffers alive; the rest (notably the
                # parameter-sized psi tree of the R=1 path) must be freeable
                # as soon as the dispatch's consumers drop it
                pending.append((r0, R, loss, ev, cb, aw, st, hl))
                if cadence and (r0 + R) % on_state_every == 0:
                    while pending:  # CSV must never lag a saved checkpoint
                        drain_one()
                    with jax.profiler.TraceAnnotation(CHECKPOINT):
                        on_state(r0 + R - 1, state)
                while len(pending) > max_in_flight:
                    drain_one()
                if in_prog_ckpt and not pending:
                    # every dispatch issued so far has drained (drain_one
                    # blocks on its metric buffers), so the stashed carries
                    # are safely readable
                    flush_checkpoints()
                r0 += R
            while pending:
                drain_one()
            done = True
        except _Fault as fault:
            with jax.profiler.TraceAnnotation(RECOVERY):
                # Everything in flight descends from the poisoned state: drop
                # the metric buffers unread and the stashed checkpoint carries
                # unwritten (a poisoned carry must never become a "valid"
                # checkpoint on disk).
                pending.clear()
                ckpt_stash.clear()
                if rollbacks_left <= 0:
                    if (recovery.scale_lr is not None
                            and lr_halvings < recovery.max_lr_halvings):
                        lr_halvings += 1
                        lr_scale *= recovery.lr_backoff
                        new_engine = recovery.scale_lr(lr_scale)
                        if new_engine is not None:
                            if in_prog_ckpt:
                                engine.checkpoint_sink = None
                                new_engine.checkpoint_sink = _sink
                            engine = new_engine
                        rollbacks_left = recovery.max_rollbacks
                        if telemetry is not None:
                            telemetry["lr_scale"] = lr_scale
                        print(f"recovery: rollback budget exhausted; inner LR "
                              f"backed off to x{lr_scale:g}")
                    else:
                        raise TrainingAborted(
                            f"health flag {fault.code} at round {fault.round}: "
                            f"rollback and LR-backoff budgets exhausted") from None
                rollbacks_left -= 1
                restored = recovery.restore()
                if restored is None:
                    raise TrainingAborted(
                        f"health flag {fault.code} at round {fault.round} but no "
                        f"valid checkpoint to roll back to") from None
                state, ckpt_round = restored
                skip_to = fault.round + 1
                state = _with_round(state, skip_to)
                if telemetry is not None:
                    telemetry["rollbacks"] += 1
                    telemetry["skipped_rounds"] += skip_to - ckpt_round
                print(f"recovery: round {fault.round} flagged (code {fault.code}); "
                      f"rolled back to checkpoint round {ckpt_round}, resuming at "
                      f"round {skip_to}")
                r0 = skip_to
    if in_prog_ckpt:
        # the sink belongs to THIS run; drop it so a later run without
        # in-program checkpoints can never fire a stale on_state
        jax.block_until_ready(jax.tree.leaves(state))
        flush_checkpoints()
        engine.checkpoint_sink = None
    return state, history
