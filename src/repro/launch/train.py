"""End-to-end training driver on the unified TrainEngine.

The driver is a thin scheduler around :class:`repro.engine.TrainEngine`:
``--rounds-per-dispatch R`` communication rounds (each H inner steps + the
outer pseudogradient-chain sync, streaming segments included) run as ONE
donated, jitted superstep that stays on device — per-round train/eval
losses come back in [R, H]/[R] device buffers and the Python layer only
generates batches, drains metrics asynchronously (the paper's smoothed-EMA
eval estimate + CSV logging ride under the accelerator's compute via
:func:`repro.engine.run_rounds`), and checkpoints. R is auto-clamped to
divide the run length and the checkpoint cadence; every dividing R replays
the identical arithmetic bit for bit. The DP baseline is the same engine
with the degenerate (K=1, H=1, no-outer) config.

Runs DiLoCo/MuLoCo on the synthetic LM data stream. On CPU this trains
reduced configs (examples/); on a TPU cluster the same driver runs the
production mesh — the engine threads the StepPlan shardings so both lower
from the same round builder.

    PYTHONPATH=src python -m repro.launch.train --arch smollm-135m --reduced \
        --inner muon --workers 4 --sync-interval 6 --rounds 20
"""
from __future__ import annotations

import argparse
import csv
import dataclasses
import os
import signal
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import (
    load_checkpoint,
    load_latest_valid,
    save_round_checkpoint,
)
from repro.configs import get_config, reduce_config
from repro.core.compression import CompressionConfig
from repro.core.diloco import DiLoCoConfig
from repro.core.faults import CrashPlan, FaultPlan, parse_drop_schedule
from repro.core.health import HealthConfig
from repro.data import DataConfig, MarkovStream, batches_for_round, batches_for_span
from repro.engine import RecoveryPolicy, TrainEngine, run_rounds
from repro.models import build_model
from repro.optim import INNER_OPTIMIZERS, OUTER_OPTIMIZERS, OptimizerConfig

# paper §5 / App. F: smoothed eval loss
def smoothed_eval_loss(losses: list[float], steps: list[int], H: int, alpha: float = 0.2) -> float:
    s = None
    prev_t = None
    for loss, t in zip(losses, steps):
        if t % H:
            continue
        if s is None:
            s, prev_t = loss, t
            continue
        a = 1.0 - jnp.exp(-alpha * (t - prev_t) / H)
        s = float(a) * loss + (1.0 - float(a)) * s
        prev_t = t
    return s if s is not None else (losses[-1] if losses else float("nan"))


def make_diloco_cfg(args) -> DiLoCoConfig:
    comp = CompressionConfig(
        kind=args.compression,
        bits=args.bits,
        topk_frac=args.topk_frac,
        quant_mode=args.quant_mode,
        rowwise=args.rowwise,
        error_feedback=args.error_feedback,
        collective="gather" if args.compression == "topk" else "a2a_rs_ag",
    )
    # elastic execution is switched on by any fault knob: a drop probability,
    # a scripted drop schedule, or a delayed outer sync — the participation
    # mask + pending FIFO only enter the program when actually requested, so
    # the default path lowers the exact pre-elastic program
    elastic = args.drop_prob > 0 or bool(args.drop_schedule)
    return DiLoCoConfig(
        n_workers=args.workers,
        sync_interval=args.sync_interval,
        inner_name=args.inner,
        outer_name=args.outer,
        outer_lr=args.outer_lr,
        outer_momentum=args.outer_momentum,
        compression=comp,
        streaming_partitions=args.streaming,
        ns_impl=args.ns_impl,
        outer_kernel=args.outer_kernel,
        elastic=elastic,
        sync_delay=args.sync_delay,
        health=HealthConfig(
            enabled=args.health_sentinel == "on",
            spike_factor=args.health_spike_factor,
            warmup_rounds=args.health_warmup,
        ),
    )


def make_fault_plan(args, n_workers: int) -> FaultPlan | None:
    """The host-side participation-mask generator, or None for lockstep."""
    schedule = parse_drop_schedule(args.drop_schedule) if args.drop_schedule else None
    plan = FaultPlan(n_workers=n_workers, drop_prob=args.drop_prob,
                     schedule=schedule, seed=args.drop_seed)
    return None if plan.is_trivial else plan


def parse_mesh(spec: str):
    """'DxM' or 'PxDxM' -> a debug mesh over the host devices (P -> 'pod')."""
    from repro.launch.mesh import make_debug_mesh

    dims = [int(d) for d in spec.lower().split("x")]
    if len(dims) == 2:
        return make_debug_mesh(dims[0], dims[1])
    if len(dims) == 3:
        return make_debug_mesh(dims[1], dims[2], pod=dims[0])
    raise SystemExit(f"--mesh {spec!r}: expected DxM or PxDxM")


def train(args) -> dict:
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduce_config(cfg)
    # plumb --seq-len into the model config (single source of truth for the
    # data pipeline; clamps the sliding window so W never exceeds S) and the
    # attention execution knobs (--attn-impl routes the fused Pallas
    # flash-attention kernel exactly like --ns-impl routes Newton-Schulz)
    seq_len = args.seq_len or cfg.max_seq_len or 128
    cfg = cfg.replace(
        max_seq_len=seq_len,
        sliding_window=min(cfg.sliding_window, seq_len) if cfg.sliding_window else 0,
        attn_impl=args.attn_impl,
    )
    # block-size resolution order: autotune table (bitwise-gated best-known
    # configs, --autotune off restores the raw constants) < explicit CLI
    # overrides (None = not passed)
    from repro.kernels.autotune import configure, tuned_model_config

    configure(enabled=args.autotune == "on", table_path=args.autotune_table)
    if args.autotune == "on":
        cfg = tuned_model_config(cfg, seq_len)
    overrides = {k: v for k, v in (
        ("blockwise_threshold", args.blockwise_threshold),
        ("attn_block_q", args.attn_block_q),
        ("attn_block_kv", args.attn_block_kv)) if v is not None}
    if overrides:
        cfg = cfg.replace(**overrides)
    model = build_model(cfg)

    dcfg = make_diloco_cfg(args)
    total_steps = args.rounds * args.sync_interval
    icfg = OptimizerConfig(
        lr=args.lr, weight_decay=args.weight_decay, schedule=args.schedule,
        warmup_steps=max(total_steps // 100, 5), total_steps=total_steps,
        ns_period=args.ns_period,
    )

    # --mesh runs the SAME driver under the StepPlan layout: state and
    # batches committed to the mesh shardings, the worker axis vmapped over
    # 'pod', and every Pallas call site shard_mapped via the engine's
    # kernel_specs routing (so --attn-impl/--ns-impl/--outer-kernel pallas
    # are legal on multi-device worlds)
    mesh = parse_mesh(args.mesh) if args.mesh else None
    ekw: dict = {}
    if mesh is not None:
        from repro.launch.mesh import mesh_axis_sizes
        from repro.launch.steps import activation_rules, tp_friendly

        ekw = {"mesh": mesh,
               "rules": activation_rules(mesh, args.batch_per_worker, cfg,
                                         train=True),
               "spmd_axis": ("pod" if mesh_axis_sizes(mesh).get("pod", 0) > 1
                             else None)}
    engine = TrainEngine(model, dcfg, icfg, **ekw)
    rng = jax.random.PRNGKey(args.seed)
    state = engine.init(rng)
    # the state sharding pytree: on a mesh the resume path MUST re-place the
    # loaded leaves under the StepPlan layout (the default device_put would
    # silently land everything on one device and the first dispatch would
    # reshard — or OOM — at runtime)
    shardings = (engine.state_shardings(
        tensor_parallel=tp_friendly(cfg, mesh)) if mesh is not None else None)
    if mesh is not None:
        state = jax.device_put(state, shardings)

    start_round = 0
    resumed_from = None
    if args.resume == "auto":
        got = load_latest_valid(args.out, engine.abstract_state(),
                                shardings=shardings)
        if got is not None:
            state, start_round, resumed_from = got
    elif args.resume and os.path.exists(args.resume):
        state, start_round = load_checkpoint(args.resume, engine.abstract_state(),
                                             shardings=shardings)
        resumed_from = args.resume
    if resumed_from is not None:
        if mesh is not None:
            # assert the resumed leaves actually sit under the plan layout
            for leaf, want in zip(jax.tree.leaves(state),
                                  jax.tree.leaves(shardings)):
                assert leaf.sharding.is_equivalent_to(want, leaf.ndim), (
                    f"resumed leaf placed under {leaf.sharding}, "
                    f"expected {want}")
        print(f"resumed from {resumed_from} at round {start_round}")
        print(f"resume telemetry: resumed_from={os.path.basename(resumed_from)} "
              f"start_round={start_round}")

    data = MarkovStream(DataConfig(
        vocab=cfg.vocab, seq_len=cfg.max_seq_len,
        batch_per_worker=args.batch_per_worker, n_workers=dcfg.n_workers,
        seed=args.seed,
    ))
    eval_data = MarkovStream(DataConfig(
        vocab=cfg.vocab, seq_len=cfg.max_seq_len,
        batch_per_worker=args.batch_per_worker, n_workers=1, seed=args.seed + 10_000,
    ))

    def eval_batches_for(r0, n):
        # [n, B, S] held-out batches, one per round; the engine evaluates the
        # post-sync outer params inside the superstep program itself
        return jax.tree.map(lambda x: x[:, 0], eval_data.batch_stack(r0, n))

    os.makedirs(args.out, exist_ok=True)
    csv_path = os.path.join(args.out, "metrics.csv")
    header = ["round", "step", "train_loss", "eval_loss", "comm_bytes",
              "active_workers", "staleness", "health", "rollbacks", "wall_s"]
    losses, steps = [], []
    # Resume: reload the killed run's rows up to start_round so (a) the
    # smoothed-EMA eval estimate continues from the SAME history the
    # uninterrupted run would have (losses are logged at %.9g — exact f32
    # round-trip via np.float32, so the smoothing replays bit-identically)
    # and (b) the rewritten CSV drops any rows past the checkpoint we
    # restored (rounds the dead process logged but whose state was lost) —
    # the keystone invariant is a resumed metrics.csv tail byte-identical to
    # the uninterrupted run's.
    prior_rows: list[list[str]] = []
    if start_round > 0 and os.path.exists(csv_path):
        with open(csv_path, newline="") as f:
            rdr = csv.reader(f)
            for row in rdr:
                if row and row[0].isdigit() and int(row[0]) < start_round:
                    prior_rows.append(row)
        for row in prior_rows:
            losses.append(float(np.float32(row[3])))
            steps.append(int(row[1]))
    t_start = time.time()
    with open(csv_path, "w", newline="") as f:
        writer = csv.writer(f)
        writer.writerow(header)
        writer.writerows(prior_rows)
        f.flush()

        fault_plan = make_fault_plan(args, dcfg.n_workers)
        crash = CrashPlan(nan_round=args.inject_nan_round,
                          spike_round=args.inject_spike_round,
                          kill_round=args.inject_kill_round)
        telemetry: dict = {}

        def on_round(rec):
            losses.append(rec["eval_loss"])
            steps.append(rec["step"])
            # comm_bytes is the round's *measured* per-worker wire traffic,
            # drained from the engine's [R] device buffer (actual wire-buffer
            # sizes, not the modeled compression ratio); active_workers /
            # staleness are the elastic evidence (== K / 0 on lockstep runs),
            # health the sentinel's flag bitmask (0 when the sentinel is off)
            # and rollbacks the recovery count so far
            aw = rec.get("active_workers", float(dcfg.n_workers))
            st = rec.get("staleness", float(dcfg.sync_delay))
            writer.writerow([rec["round"], rec["step"], f"{rec['train_loss']:.9g}",
                             f"{rec['eval_loss']:.9g}", f"{rec['comm_bytes']:.0f}",
                             f"{aw:.0f}", f"{st:.0f}",
                             f"{rec.get('health', 0.0):.0f}",
                             telemetry.get("rollbacks", 0),
                             f"{time.time()-t_start:.1f}"])
            f.flush()
            if args.verbose:
                print(f"round {rec['round']:4d} step {rec['step']:6d} "
                      f"train {rec['train_loss']:.4f} eval {rec['eval_loss']:.4f} "
                      f"comm {rec['comm_bytes']:.2e}B active {aw:.0f}")
            # the SIGKILL injection fires only after the row is durably out:
            # the dead process leaves exactly a real crash's on-disk trail
            crash.maybe_kill(rec["round"])

        def on_state(r, st):
            save_round_checkpoint(args.out, st, r + 1,
                                  keep=args.keep_checkpoints)

        recovery = None
        if dcfg.health.enabled and args.checkpoint_every:
            template = engine.abstract_state()

            def restore():
                got = load_latest_valid(args.out, template, shardings=shardings)
                return None if got is None else (got[0], got[1])

            def scale_lr(scale):
                # escalation: rebuild the engine with the inner LR backed off
                # (same model/mesh/config — only icfg.lr changes)
                return TrainEngine(
                    model, dcfg, dataclasses.replace(icfg, lr=args.lr * scale),
                    **ekw)

            recovery = RecoveryPolicy(restore=restore,
                                      max_rollbacks=args.health_max_rollbacks,
                                      scale_lr=scale_lr)
            if start_round == 0 and not os.path.exists(
                    os.path.join(args.out, "ckpt_0.npz")):
                # a round-0 fault needs something to roll back to
                on_state(-1, state)

        # a poisoning injection edits state at a dispatch boundary; pin R=1
        # so the boundary IS the target round
        rpd = (1 if crash.needs_single_round_dispatch
               else args.rounds_per_dispatch)

        # Preemption: SIGTERM/SIGINT flip a flag the driver probes before
        # each dispatch; in-flight work finishes, metrics drain, and the
        # final checkpoint below makes the run resumable with --resume auto.
        stop = {"flag": False}

        def _graceful(signum, frame):
            stop["flag"] = True
            print(f"signal {signum}: draining in-flight dispatches, then "
                  f"writing a resumable checkpoint")

        old_handlers = {}
        for sig in (signal.SIGTERM, signal.SIGINT):
            try:
                old_handlers[sig] = signal.signal(sig, _graceful)
            except ValueError:  # not the main thread (in-process tests)
                pass
        try:
            state, _history = run_rounds(
                engine, state,
                lambda r: batches_for_round(data, r, dcfg.sync_interval),
                args.rounds, start=start_round,
                rounds_per_dispatch=rpd,
                participation_for=fault_plan.masks if fault_plan is not None else None,
                span_batches_for=lambda r0, n: batches_for_span(
                    data, r0, dcfg.sync_interval, n),
                eval_batches_for=eval_batches_for,
                on_round=on_round,
                on_state=on_state if args.checkpoint_every else None,
                on_state_every=args.checkpoint_every,
                checkpoint_in_program=args.checkpoint_in_program,
                telemetry=telemetry,
                recovery=recovery,
                should_stop=lambda: stop["flag"],
                inject=None if crash.is_trivial else crash.apply,
            )
        finally:
            for sig, handler in old_handlers.items():
                signal.signal(sig, handler)

    if telemetry.get("preempted"):
        done = int(jax.device_get(state["round"]))
        path = save_round_checkpoint(args.out, state, done,
                                     keep=args.keep_checkpoints)
        print(f"preempted after round {done - 1}: wrote "
              f"{os.path.basename(path)}; resume with --resume auto")

    # the dispatch evidence line the CI single-dispatch smoke greps: with
    # --rounds-per-dispatch auto and no cadence pinning the whole run is ONE
    # donated device program, so dispatches must read 1
    print(f"dispatch telemetry: dispatches={telemetry.get('dispatches')} "
          f"rounds_per_dispatch={telemetry.get('rounds_per_dispatch')} "
          f"in_program_checkpoints={telemetry.get('in_program_checkpoints')} "
          f"rollbacks={telemetry.get('rollbacks')} "
          f"skipped_rounds={telemetry.get('skipped_rounds')} "
          f"preempted={telemetry.get('preempted')}")
    final = smoothed_eval_loss(losses, steps, dcfg.sync_interval)
    print(f"final smoothed eval loss: {final:.4f} "
          f"(floor={data.entropy_floor_nats():.4f} nats)")
    return {"final_loss": final, "losses": losses, "steps": steps, "state": state,
            "telemetry": telemetry, "history": _history}


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--reduced", action="store_true", help="CPU-sized variant")
    ap.add_argument("--inner", default="muon", choices=list(INNER_OPTIMIZERS))
    ap.add_argument("--outer", default="nesterov", choices=list(OUTER_OPTIMIZERS))
    ap.add_argument("--ns-period", type=int, default=1,
                    help="muon_bp: orthogonalize every b steps (1 = plain Muon)")
    ap.add_argument("--outer-kernel", action="store_true",
                    help="route the outer descent through the fused Pallas kernel")
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--sync-interval", type=int, default=6)
    ap.add_argument("--rounds", type=int, default=20)
    ap.add_argument("--rounds-per-dispatch",
                    type=lambda v: v if v == "auto" else int(v),
                    default="auto",
                    help="rounds per device dispatch (superstep length R), or "
                         "'auto' (the default): the dispatch cost model picks "
                         "R — the whole run as ONE device program when "
                         "unmeasured. Auto-clamped to divide the run and the "
                         "checkpoint cadence — any dividing R is "
                         "bitwise-identical")
    ap.add_argument("--lr", type=float, default=2e-2)
    ap.add_argument("--weight-decay", type=float, default=1e-4)
    ap.add_argument("--schedule", default="cosine", choices=["cosine", "constant"])
    ap.add_argument("--outer-lr", type=float, default=0.7)
    ap.add_argument("--outer-momentum", type=float, default=0.9)
    ap.add_argument("--batch-per-worker", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=0,
                    help="0 -> the arch config's max_seq_len (128 if unset)")
    ap.add_argument("--compression", default="none", choices=["none", "topk", "quant"])
    ap.add_argument("--bits", type=int, default=4)
    ap.add_argument("--quant-mode", default="linear", choices=["linear", "statistical"])
    ap.add_argument("--rowwise", action="store_true")
    ap.add_argument("--topk-frac", type=float, default=0.1)
    ap.add_argument("--error-feedback", action="store_true")
    ap.add_argument("--streaming", type=int, default=1, help="J partitions")
    ap.add_argument("--sync-delay", type=int, default=0,
                    help="apply the pseudogradient d rounds late (delayed/"
                         "overlapped outer sync): round r reduces the fresh "
                         "pseudogradient but descends on the one from round "
                         "r-d via an in-program FIFO; 0 = lockstep")
    ap.add_argument("--drop-prob", type=float, default=0.0,
                    help="per-(round, worker) i.i.d. drop probability "
                         "(elastic execution: dropped workers freeze, ship no "
                         "wire packet, and are excluded from the reduce)")
    ap.add_argument("--drop-schedule", default=None,
                    help="scripted drops 'round:worker[;round:worker...]', "
                         "e.g. '1:2;1:3;4:0' — each worker is dropped only "
                         "for the rounds listed and rejoins at the next sync")
    ap.add_argument("--drop-seed", type=int, default=0,
                    help="seed of the per-round drop draws (masks are a pure "
                         "function of (seed, round), so any "
                         "--rounds-per-dispatch chunking sees identical "
                         "faults)")
    ap.add_argument("--ns-impl", default="jnp", choices=["jnp", "pallas"])
    ap.add_argument("--attn-impl", default="auto",
                    choices=["auto", "xla", "pallas"],
                    help="attention backend: 'auto' (the flash kernel on a "
                         "TPU at seq >= repro.models.attention.FLASH_MIN_SEQ "
                         "and a multiple of 128, else xla), 'xla' (dense/blockwise) or 'pallas' "
                         "(fused flash-attention kernel; interpret mode "
                         "off-TPU). All run on a --mesh: pallas is "
                         "shard_mapped over the mesh by the engine's kernel "
                         "routing")
    ap.add_argument("--mesh", default=None,
                    help="run sharded on a DxM or PxDxM debug mesh over the "
                         "host devices (e.g. 2x2x2 with "
                         "XLA_FLAGS=--xla_force_host_platform_device_count=8); "
                         "P is the 'pod' worker axis and must divide "
                         "--workers")
    ap.add_argument("--blockwise-threshold", type=int, default=None,
                    help="seq length at which attn_impl=xla switches from "
                         "dense softmax to blockwise online-softmax (default: "
                         "autotune table, else the config constant 4096)")
    ap.add_argument("--attn-block-q", type=int, default=None,
                    help="attention q-block rows (both impls; clamped to "
                         "divide the sequence; default: autotune table, else "
                         "the config constant 512)")
    ap.add_argument("--attn-block-kv", type=int, default=None,
                    help="attention kv-block rows (both impls; clamped to "
                         "divide the sequence; default: autotune table, else "
                         "the config constant 1024)")
    ap.add_argument("--autotune", default="on", choices=["on", "off"],
                    help="consult the committed kernel autotune table for "
                         "block sizes ('off' restores the raw constants); "
                         "entries are bitwise-gated at sweep time, so this "
                         "never changes any loss bit")
    ap.add_argument("--autotune-table", default=None,
                    help="path of the autotune JSON table (default: the "
                         "committed src/repro/kernels/autotune_table.json)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="results/train")
    ap.add_argument("--resume", default=None,
                    help="checkpoint to resume from: a file path, or 'auto' "
                         "to walk --out's round-stamped checkpoints newest to "
                         "oldest past truncated/corrupt/checksum-failing "
                         "files and restart from the freshest VALID one")
    ap.add_argument("--checkpoint-every", type=int, default=0)
    ap.add_argument("--keep-checkpoints", type=int, default=3,
                    help="retention: keep the newest N round-stamped "
                         "ckpt_<round>.npz files (older ones are pruned; the "
                         "LATEST manifest is rewritten atomically after every "
                         "save)")
    ap.add_argument("--health-sentinel", default="off", choices=["on", "off"],
                    help="in-program health sentinel: every round emits an "
                         "anomaly-flag metric (non-finite loss/psi, loss "
                         "spike vs a running EMA) drained with the other "
                         "metrics; with --checkpoint-every set, a flagged "
                         "round triggers rollback to the last valid "
                         "checkpoint + skip of the offending data span. "
                         "'off' (default) adds zero ops — the lowered "
                         "program is unchanged")
    ap.add_argument("--health-spike-factor", type=float, default=3.0,
                    help="flag a round whose mean train loss exceeds this "
                         "multiple of the running EMA")
    ap.add_argument("--health-warmup", type=int, default=3,
                    help="finite rounds observed before spike detection arms")
    ap.add_argument("--health-max-rollbacks", type=int, default=3,
                    help="rollback budget before escalation (halve the inner "
                         "LR, then abort)")
    ap.add_argument("--inject-nan-round", type=int, default=None,
                    help="fault injection: poison one worker-param element "
                         "with NaN at this round (forces "
                         "--rounds-per-dispatch 1 so the poison lands "
                         "exactly there)")
    ap.add_argument("--inject-spike-round", type=int, default=None,
                    help="fault injection: overwrite one worker-param "
                         "element with a large finite value at this round — "
                         "a silent-data-corruption loss spike (forces "
                         "--rounds-per-dispatch 1)")
    ap.add_argument("--inject-kill-round", type=int, default=None,
                    help="fault injection: SIGKILL this process the moment "
                         "the given round's metrics row hits the CSV (the "
                         "kill-resume harness; resume with --resume auto)")
    ap.add_argument("--checkpoint-in-program", action="store_true",
                    help="emit checkpoints from INSIDE the running device "
                         "program (io_callback) instead of between "
                         "dispatches, so --rounds-per-dispatch (and 'auto' = "
                         "the whole run) no longer needs to divide "
                         "--checkpoint-every")
    ap.add_argument("--verbose", action="store_true")
    return ap


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compilation_cache

    use_compilation_cache()
    train(build_parser().parse_args())
