"""System-level benchmarks: measured train-path throughput (engine vs
per-step dispatch), wallclock/bandwidth model (Tab. 9/10, Fig. 16),
scaling-law fitting (Tab. 2), kernel microbenchmarks, roofline table."""
from __future__ import annotations

import functools
import glob
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.compression import CompressionConfig
from repro.core.scaling_laws import fit_power_law
from repro.core.wallclock import RunSpec, compute_utilization, training_time_hours


def bench_train_throughput(rounds: int = 4, warmup: int = 1,
                           reps: int = 2) -> list[dict]:
    """Measured steps/s on the reduced smollm-135m config, plus an R-sweep:

      * ``per_step``  — jit(inner_step) x H + jit(outer_step), host loop with
        a blocking loss read per step (fully unfused dispatch — how the
        pre-engine analysis/dry-run paths drove training);
      * ``seed_path`` — undonated jit(diloco_round) with a blocking metrics
        read every round (what launch/train.py did pre-engine);
      * ``engine``    — the unified TrainEngine at R=1: donated fused round +
        async metrics drain via the driver (one dispatch per round);
      * ``superstep_rN`` — the same engine dispatching N rounds per superstep
        (scan-over-R), which amortizes the per-round host dispatch away.

    The shape is dispatch-sensitive (small per-step compute, long H) so the
    executor — not the matmuls — determines steps/s. Variants are measured
    ``reps`` times interleaved and the best rep is reported, which rejects
    the load spikes of a shared box.
    """
    from repro.configs import get_config, reduce_config
    from repro.core import DiLoCoConfig, diloco_round, inner_step, make_optimizer, outer_step
    from repro.data import DataConfig, MarkovStream, batches_for_round, batches_for_span
    from repro.engine import TrainEngine, run_rounds
    from repro.models import build_model
    from repro.optim import OptimizerConfig

    cfg = reduce_config(get_config("smollm-135m"))
    model = build_model(cfg)
    K, H, SEQ, BPW_ = 4, 16, 16, 1
    dcfg = DiLoCoConfig(n_workers=K, sync_interval=H, inner_name="muon")
    icfg = OptimizerConfig(lr=2e-2, weight_decay=1e-4, schedule="constant")
    stream = MarkovStream(DataConfig(vocab=cfg.vocab, seq_len=SEQ,
                                     batch_per_worker=BPW_, n_workers=K, seed=1))
    total = rounds + warmup
    round_batches = [batches_for_round(stream, r, H) for r in range(total)]
    step_batches = [stream.batch(t) for t in range(total * H)]
    # pre-generated span batches for the R-sweep (data gen stays out of the
    # timed region, as it does for the other variants)
    R_SWEEP = tuple(r for r in (2, 4) if rounds % r == 0)
    span_batches = {
        (r0, n): batches_for_span(stream, r0, H, n)
        for n in R_SWEEP for r0 in range(warmup, total, n)
    }
    opt = make_optimizer(dcfg, icfg)

    def bench_per_step() -> float:
        state = TrainEngine(model, dcfg, icfg).init(jax.random.PRNGKey(0))
        step_fn = jax.jit(functools.partial(inner_step, model, opt))
        sync_fn = jax.jit(functools.partial(outer_step, dcfg))

        def run(state, lo, hi):
            for r in range(lo, hi):
                for h in range(H):
                    state, m = step_fn(state, step_batches[r * H + h])
                    float(m["loss"])  # blocking per-step metric read
                state, _ = sync_fn(state)
            return state

        state = run(state, 0, warmup)
        t0 = time.perf_counter()
        run(state, warmup, total)
        return rounds * H / (time.perf_counter() - t0)

    def bench_seed_path() -> float:
        state = TrainEngine(model, dcfg, icfg).init(jax.random.PRNGKey(0))
        fn = jax.jit(functools.partial(diloco_round, model, dcfg, opt, masks=None))
        for r in range(warmup):
            state, info = fn(state, round_batches[r])
            float(info["loss"].mean())
        t0 = time.perf_counter()
        for r in range(warmup, total):
            state, info = fn(state, round_batches[r])
            float(info["loss"].mean())
        return rounds * H / (time.perf_counter() - t0)

    def bench_engine() -> float:
        engine = TrainEngine(model, dcfg, icfg)
        state = engine.init(jax.random.PRNGKey(0))
        state, _ = run_rounds(engine, state, lambda r: round_batches[r], warmup)
        t0 = time.perf_counter()
        state, _ = run_rounds(engine, state, lambda r: round_batches[r], total,
                              start=warmup)
        jax.block_until_ready(state["outer_params"])
        return rounds * H / (time.perf_counter() - t0)

    def bench_superstep(R: int):
        def run() -> float:
            engine = TrainEngine(model, dcfg, icfg)
            state = engine.init(jax.random.PRNGKey(0))
            state, _ = run_rounds(engine, state, lambda r: round_batches[r], warmup)
            # compile + execute the R-wide dispatch outside the timed region
            state, _ = engine.superstep(state, span_batches[(warmup, R)])
            jax.block_until_ready(state["outer_params"])
            t0 = time.perf_counter()
            state, _ = run_rounds(engine, state, lambda r: round_batches[r],
                                  total, start=warmup, rounds_per_dispatch=R,
                                  span_batches_for=lambda r0, n: span_batches[(r0, n)])
            jax.block_until_ready(state["outer_params"])
            return rounds * H / (time.perf_counter() - t0)

        return run

    single_dispatch_telemetry: dict = {}

    def bench_single_dispatch() -> float:
        # whole-span dispatch via the cost model ("auto" unmeasured = one
        # program for the run); telemetry pins the dispatch count the row's
        # derived field reports
        engine = TrainEngine(model, dcfg, icfg)
        state = engine.init(jax.random.PRNGKey(0))
        state, _ = run_rounds(engine, state, lambda r: round_batches[r], warmup)
        span = {(warmup, rounds): batches_for_span(stream, warmup, H, rounds)}
        state, _ = engine.superstep(state, span[(warmup, rounds)])
        jax.block_until_ready(state["outer_params"])
        t0 = time.perf_counter()
        state, _ = run_rounds(engine, state, lambda r: round_batches[r],
                              total, start=warmup, rounds_per_dispatch="auto",
                              span_batches_for=lambda r0, n: span[(r0, n)],
                              telemetry=single_dispatch_telemetry)
        jax.block_until_ready(state["outer_params"])
        return rounds * H / (time.perf_counter() - t0)

    variants = {"per_step": bench_per_step, "seed_path": bench_seed_path,
                "engine": bench_engine}
    variants.update({f"superstep_r{R}": bench_superstep(R) for R in R_SWEEP})
    variants["single_dispatch"] = bench_single_dispatch
    best = {name: 0.0 for name in variants}
    for _ in range(reps):
        for name, fn in variants.items():
            best[name] = max(best[name], fn())

    rows = [
        {"name": "train_throughput/per_step", "value": round(best["per_step"], 3),
         "derived": "steps_per_s"},
        {"name": "train_throughput/seed_path", "value": round(best["seed_path"], 3),
         "derived": "steps_per_s"},
        {"name": "train_throughput/engine", "value": round(best["engine"], 3),
         "derived": f"steps_per_s;"
                    f"speedup_vs_seed={best['engine'] / best['seed_path']:.2f}x;"
                    f"speedup_vs_per_step={best['engine'] / best['per_step']:.2f}x"},
    ]
    for R in R_SWEEP:
        v = best[f"superstep_r{R}"]
        rows.append({
            "name": f"train_throughput/superstep_r{R}", "value": round(v, 3),
            "derived": f"steps_per_s;rounds_per_dispatch={R};"
                       f"speedup_vs_r1_engine={v / best['engine']:.2f}x",
        })
    v = best["single_dispatch"]
    rows.append({
        "name": "train_throughput/single_dispatch", "value": round(v, 3),
        "derived": f"steps_per_s;"
                   f"dispatches={single_dispatch_telemetry.get('dispatches')};"
                   f"speedup_vs_r1_engine={v / best['engine']:.2f}x",
    })
    return rows


def bench_optimizer_sweep(rounds: int = 3, warmup: int = 1) -> list[dict]:
    """Inner-optimizer sweep at the throughput-bench shape (K=4, H=16,
    seq=16, bpw=1): measured engine steps/s per transform-chain optimizer.

    ``muon_bp`` runs at ns_period=H (one orthogonalization per round — the
    round boundary aligns with the period). On CPU the vmapped lax.cond
    lowers to select, so the NS saving shows up on accelerators; here the
    row mainly proves the variant lowers through the same donated round.
    """
    from repro.configs import get_config, reduce_config
    from repro.core import DiLoCoConfig
    from repro.data import DataConfig, MarkovStream, batches_for_round
    from repro.engine import TrainEngine, run_rounds
    from repro.models import build_model
    from repro.optim import OptimizerConfig

    cfg = reduce_config(get_config("smollm-135m"))
    model = build_model(cfg)
    K, H, SEQ, BPW_ = 4, 16, 16, 1
    stream = MarkovStream(DataConfig(vocab=cfg.vocab, seq_len=SEQ,
                                     batch_per_worker=BPW_, n_workers=K, seed=1))
    total = rounds + warmup
    round_batches = [batches_for_round(stream, r, H) for r in range(total)]

    rows = []
    for inner in ("adamw", "muon", "muon_bp"):
        icfg = OptimizerConfig(lr=2e-2, weight_decay=1e-4, schedule="constant",
                               ns_period=H if inner == "muon_bp" else 1)
        dcfg = DiLoCoConfig(n_workers=K, sync_interval=H, inner_name=inner)
        engine = TrainEngine(model, dcfg, icfg)
        state = engine.init(jax.random.PRNGKey(0))
        state, _ = run_rounds(engine, state, lambda r: round_batches[r], warmup)
        t0 = time.perf_counter()
        state, _ = run_rounds(engine, state, lambda r: round_batches[r], total,
                              start=warmup)
        jax.block_until_ready(state["outer_params"])
        sps = rounds * H / (time.perf_counter() - t0)
        rows.append({"name": f"optimizer_bench/{inner}",
                     "value": round(sps, 3), "derived": "steps_per_s"})
    return rows


def bench_compression_sweep(rounds: int = 3) -> list[dict]:
    """compression_bench: loss + *measured* wire bytes across bits/topk_frac.

    Each config trains the toy model through the engine's wire-format
    collective path (real codes + metadata + indices on the simulated wire)
    and reports the final eval loss alongside three byte accountings per
    sync per worker: measured (actual wire-buffer shapes/dtypes, the number
    the engine's per-round ``comm_bytes`` metric carries), the closed-form
    model (``collective_bytes_tree``), and the measured/dense ratio. The
    measured-vs-modeled gap is the metadata + packing overhead the ratio
    model ignores (see docs/benchmarks.md).
    """
    from benchmarks.common import TOY, train_diloco
    from repro.core import DiLoCoConfig
    from repro.core.collectives import (
        collective_bytes_tree,
        measured_compression_ratio,
        measured_sync_bytes,
    )
    from repro.models import build_model

    K, H = 2, 4
    params_abs = jax.eval_shape(
        lambda: build_model(TOY).init(jax.random.PRNGKey(0)))
    configs = [("none", CompressionConfig(kind="none"))]
    for bits in (8, 4, 2):
        configs.append((f"quant{bits}_rw_ef", CompressionConfig(
            kind="quant", bits=bits, rowwise=True, error_feedback=True)))
    configs.append(("quant4_global_ef", CompressionConfig(
        kind="quant", bits=4, error_feedback=True)))
    for frac in (0.01, 0.1):
        configs.append((f"topk{frac}_ef", CompressionConfig(
            kind="topk", topk_frac=frac, error_feedback=True,
            collective="gather")))

    rows = []
    for name, comp in configs:
        dcfg = DiLoCoConfig(n_workers=K, sync_interval=H, inner_name="muon",
                            compression=comp)
        loss, extra = train_diloco(dcfg, rounds=rounds)
        measured = measured_sync_bytes(params_abs, comp, K)
        modeled = collective_bytes_tree(params_abs, comp, K)[
            "bytes_per_sync_per_worker"]
        ratio = measured_compression_ratio(params_abs, comp, K)
        rows.append({
            "name": f"compression_bench/{name}", "value": round(loss, 4),
            "derived": (f"loss;measured_B={measured};modeled_B={modeled};"
                        f"measured_ratio={ratio:.4f};"
                        f"wall_s={extra['wall_s']:.1f}"),
        })
    return rows


def bench_serve_throughput(reps: int = 2) -> list[dict]:
    """serve_bench: useful decode tokens/s on a heterogeneous request mix,
    serving engines vs the seed loop (reduced smollm-135m, greedy).

    The workload is the one serving engines exist for: more requests than
    batch slots, prompt lengths varying 4..32 and per-request ``max_new``
    varying 4..48. Three servers per (slots, workload) shape:

      * ``per_token``  — the seed loop as a server (static batching): FIFO
        waves of ``slots`` requests, every prompt right-padded to the wave
        max (the dense path has no padding mask), prefill by stepping the
        decode path token by token, one host dispatch per generated token,
        and the whole wave held until its longest ``max_new`` finishes;
      * ``naive``      — same static waves, but the prompt prefilled in
        ONE batched dispatch (still per-token decode);
      * ``paged_ps{N}`` — the paged continuous-batching engine at page
        size N: requests admitted into freed slots mid-flight, decode
        spans of 8 tokens per donated jitted ``lax.scan`` dispatch.

    Throughput counts *useful* tokens only (sum of requested ``max_new``):
    tokens a static wave decodes for already-finished or padded slots are
    wasted work, which is precisely the waste continuous batching removes.
    Variants are measured ``reps`` times, best rep reported, one untimed
    warmup run each so compile stays out of the numbers. ``derived``
    carries each variant's speedup over the seed loop; the paged engine is
    required to clear 3x.
    """
    from repro.configs import get_config, reduce_config
    from repro.models import build_model
    from repro.serving import PagedEngine, Request, naive_generate, pages_needed

    cfg = reduce_config(get_config("smollm-135m"))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    SPAN = 8
    P_MIX = (4, 32, 8, 16)
    N_MIX = (4, 48, 8, 16)

    def workload(n_req: int) -> list[Request]:
        reqs = []
        for i in range(n_req):
            plen, nnew = P_MIX[i % len(P_MIX)], N_MIX[i % len(N_MIX)]
            toks = np.asarray(jax.random.randint(
                jax.random.PRNGKey(100 + i), (plen,), 0, cfg.vocab))
            reqs.append(Request(f"r{i}", tuple(int(t) for t in toks), nnew))
        return reqs

    rows = []
    for slots, n_req in ((2, 6), (4, 12)):
        reqs = workload(n_req)
        useful = sum(r.max_new for r in reqs)

        def t_static(batched_prefill):
            def run() -> float:
                t0 = time.perf_counter()
                for w0 in range(0, len(reqs), slots):
                    wave = reqs[w0: w0 + slots]
                    pmax = max(len(r.tokens) for r in wave)
                    prompts = np.zeros((len(wave), pmax), np.int32)
                    for i, r in enumerate(wave):
                        prompts[i, : len(r.tokens)] = r.tokens
                    out = naive_generate(model, params, jnp.asarray(prompts),
                                         max(r.max_new for r in wave),
                                         batched_prefill=batched_prefill)
                    np.asarray(out)
                return useful / (time.perf_counter() - t0)

            return run

        def t_paged(ps):
            budget = max(pages_needed(len(r.tokens) + r.max_new + SPAN, ps)
                         for r in reqs)
            engine = PagedEngine(model, params, slots=slots, page_size=ps,
                                 max_pages=1 + slots * budget,
                                 decode_steps_per_dispatch=SPAN)

            def run() -> float:
                t0 = time.perf_counter()
                engine.run(reqs)
                return useful / (time.perf_counter() - t0)

            return run

        variants = {"per_token": t_static(False), "naive": t_static(True),
                    "paged_ps8": t_paged(8), "paged_ps16": t_paged(16)}
        best = {}
        for name, fn in variants.items():
            fn()  # warmup: compile outside the timed reps
            best[name] = max(fn() for _ in range(reps))
        for name, tps in best.items():
            rows.append({
                "name": f"serve_bench/slots{slots}_req{n_req}/{name}",
                "value": round(tps, 1),
                "derived": f"useful_tok_per_s;speedup_vs_per_token="
                           f"{tps / best['per_token']:.2f}x",
            })
        # acceptance: paged continuous batching >= 3x the seed loop
        assert max(best["paged_ps8"], best["paged_ps16"]) >= 3 * best["per_token"], best
    return rows


def bench_fault_bench(rounds: int = 5) -> list[dict]:
    """fault_bench: elastic-DiLoCo degradation curves on the toy model.

    Two curve families per worker count K in {2, 4}, both through the real
    engine (donated fused round, participation mask / pending FIFO in the
    program — not a host-side simulation):

      * ``staleness``  — final eval loss vs ``sync_delay`` d in {0, 1, 2}
        (delayed outer sync, full participation): how much convergence the
        overlap window costs when the pseudogradient lands d rounds late;
      * ``drop``       — final eval loss vs i.i.d. per-round drop
        probability p in {0, 0.25, 0.5} (lockstep sync): how much worker
        churn costs when dropped workers freeze and the reduce averages the
        survivors. ``derived`` carries the realized mean active-worker
        count and the mean per-round wire fraction, which the elastic
        comm_bytes metric scales by construction.

    The d=0 / p=0 anchors of the two families are the same dense run, so
    the curves share a baseline by construction.
    """
    from benchmarks.common import LR, TOY, eval_loss, make_stream
    from repro.core import DiLoCoConfig
    from repro.core.faults import FaultPlan
    from repro.data import batches_for_round
    from repro.engine import TrainEngine, run_rounds
    from repro.models import build_model
    from repro.optim import OptimizerConfig

    H = 4

    def run(K: int, sync_delay: int = 0, drop_prob: float = 0.0):
        dcfg = DiLoCoConfig(n_workers=K, sync_interval=H, inner_name="muon",
                            elastic=drop_prob > 0, sync_delay=sync_delay)
        model = build_model(TOY)
        icfg = OptimizerConfig(lr=LR["muon"], weight_decay=1e-4,
                               schedule="cosine", total_steps=rounds * H)
        engine = TrainEngine(model, dcfg, icfg)
        state = engine.init(jax.random.PRNGKey(0))
        stream = make_stream(K)
        plan = FaultPlan(n_workers=K, drop_prob=drop_prob, seed=7)
        state, hist = run_rounds(
            engine, state,
            lambda r: batches_for_round(stream, r, H), rounds,
            participation_for=plan.masks if drop_prob > 0 else None)
        active = [h.get("active_workers", float(K)) for h in hist]
        return (eval_loss(model, state["outer_params"]),
                float(np.mean(active)) if active else float(K))

    rows = []
    for K in (2, 4):
        for d in (0, 1, 2):
            loss, _ = run(K, sync_delay=d)
            rows.append({"name": f"fault_bench/staleness/K{K}/d{d}",
                         "value": round(loss, 4),
                         "derived": f"loss;sync_delay={d}"})
        for p in (0.0, 0.25, 0.5):
            loss, mean_active = run(K, drop_prob=p)
            rows.append({"name": f"fault_bench/drop/K{K}/p{p}",
                         "value": round(loss, 4),
                         "derived": (f"loss;drop_prob={p};"
                                     f"mean_active={mean_active:.2f};"
                                     f"wire_frac={mean_active / K:.3f}")})
    return rows


def bench_tab10_wallclock() -> list[dict]:
    """Tab. 10: idealized 15B training hours across bandwidths."""
    rows = []
    n = 15.23e9
    base = dict(n_params=n, n_active_params=n, seq_len=2048, n_steps=145_000)
    specs = {
        "dp_adamw_bs2M": RunSpec(**base, batch_tokens=2.1e6, sync_interval=1,
                                 optimizer_overhead=0.0),
        "dp_muon_bs4M": RunSpec(**base, batch_tokens=4.2e6, sync_interval=1),
        "diloco_k1_bs1M": RunSpec(**base, batch_tokens=1e6, sync_interval=30,
                                  optimizer_overhead=0.0),
        "muloco_k1_bs16M": RunSpec(**base, batch_tokens=16.8e6, sync_interval=30),
        "diloco_k16_bs4M": RunSpec(**base, batch_tokens=4.2e6, sync_interval=30,
                                   n_workers=16, optimizer_overhead=0.0),
        "muloco_k16_bs8M": RunSpec(**base, batch_tokens=8.4e6, sync_interval=30,
                                   n_workers=16),
    }
    # steps scale inversely with batch (fixed token budget 304.6B)
    for name, s in specs.items():
        steps = 304.6e9 / s.batch_tokens
        s = RunSpec(**{**s.__dict__, "n_steps": steps})
        for bw in (10e9, 100e9, 1600e9, 12800e9):
            rows.append({
                "name": f"tab10/{name}/bw={bw / 1e9:.0f}Gbit",
                "value": round(training_time_hours(s, bw), 2),
                "derived": "hours",
            })
    return rows


def bench_fig16_utilization() -> list[dict]:
    """Fig. 16: compute utilization vs bandwidth, per method/compression.

    The 4-bit entry uses the *measured* compression ratio (real wire
    buffers on a representative parameter tree — codes + row metadata +
    packing padding) instead of the bits/32 model; the gap between the two
    is documented in docs/benchmarks.md.
    """
    from repro.configs import get_config, reduce_config
    from repro.core.collectives import measured_compression_ratio
    from repro.models import build_model

    rows = []
    n = 3.07e9
    base = dict(n_params=n, n_active_params=n, seq_len=2048, n_steps=1,
                batch_tokens=2e6)
    cfg = reduce_config(get_config("smollm-135m"))
    params_abs = jax.eval_shape(
        lambda: build_model(cfg).init(jax.random.PRNGKey(0)))
    q4 = CompressionConfig(kind="quant", bits=4, rowwise=True)
    methods = {
        "dp": RunSpec(**base, sync_interval=1),
        "diloco_h30": RunSpec(**base, sync_interval=30),
        "diloco_h30_4bit": RunSpec(**base, sync_interval=30,
                                   compression_ratio=measured_compression_ratio(
                                       params_abs, q4, n_workers=1)),
    }
    for name, s in methods.items():
        for bw in (1e9, 10e9, 100e9, 1000e9):
            rows.append({
                "name": f"fig16/{name}/bw={bw / 1e9:.0f}Gbit",
                "value": round(compute_utilization(s, bw), 4),
                "derived": "utilization",
            })
    return rows


def bench_tab2_scaling_forms() -> list[dict]:
    """Tab. 2: residuals of L(C)=aC^a vs +irreducible on held-out scale."""
    rng = np.random.default_rng(0)
    C = np.logspace(18.5, 22.5, 6)
    true = 5.2e3 * C ** -0.197 + 1.711
    L = true * np.exp(rng.normal(0, 0.002, C.shape))
    train_C, train_L = C[:-1], L[:-1]
    rows = []
    for label, kw in (("simple", dict(irr=0.0)), ("irr", dict(fit_irr=True))):
        fit = fit_power_law(train_C, train_L, restarts=64, **kw)
        holdout = float(fit.residuals(C[-1:], L[-1:])[0])
        rows.append({
            "name": f"tab2/{label}",
            "value": round(holdout, 5),
            "derived": f"alpha={fit.alpha:.4f};irr={fit.irr:.3f}",
        })
    assert rows[1]["value"] <= rows[0]["value"]  # paper: +irr extrapolates better
    return rows


def _time(fn, *args, iters=5) -> float:
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
        jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6


def bench_kernel_micro() -> list[dict]:
    """Pallas kernels (interpret mode) vs jnp reference — us/call."""
    from repro.kernels import ops, ref

    rows = []
    g = jax.random.normal(jax.random.PRNGKey(0), (256, 512), jnp.float32)
    ns_p = jax.jit(lambda x: ops.ns_orthogonalize(x))
    ns_r = jax.jit(lambda x: ref.ns_orthogonalize_ref(x))
    rows.append({"name": "kernel/ns_pallas_interpret", "value": round(_time(ns_p, g), 1),
                 "derived": "us_per_call"})
    rows.append({"name": "kernel/ns_jnp_ref", "value": round(_time(ns_r, g), 1),
                 "derived": "us_per_call"})
    x = jax.random.normal(jax.random.PRNGKey(1), (512, 1024), jnp.float32)
    q_p = jax.jit(lambda x: ops.quantize_rowwise(x, 4)[0])
    q_r = jax.jit(lambda x: ref.rowwise_quantize_ref(x, 4)[0])
    rows.append({"name": "kernel/quant_pallas_interpret", "value": round(_time(q_p, x), 1),
                 "derived": "us_per_call"})
    rows.append({"name": "kernel/quant_jnp_ref", "value": round(_time(q_r, x), 1),
                 "derived": "us_per_call"})
    return rows


def bench_attention_sweep() -> list[dict]:
    """attention_bench: seq x impl x window sweep of the attention backends.

    Times one full-sequence ``attend`` call (the per-layer training hot
    path) for the three execution paths — dense XLA softmax, blockwise XLA
    with schedule skipping, and the fused Pallas flash-attention kernel
    (interpret mode on CPU, so its absolute time measures the interpreter,
    not TPU perf — the row exists to track the schedule, not the clock).
    ``derived`` reports the visit schedule's fraction of the dense block
    grid and the achieved fraction of dense-attention FLOP throughput
    (``visited_fraction * t_dense / t``): > 1 means block skipping bought
    real wall-clock on top of what dense does.
    """
    from repro.kernels.flash_attention import visited_fraction
    from repro.models import ModelConfig
    from repro.models.attention import attend, init_attention

    B, H, KV, hd = 2, 4, 2, 16
    d = 64
    rows = []
    for S in (128, 256):
        for window in (0, S // 4):
            base = ModelConfig(n_layers=1, d_model=d, n_heads=H, n_kv_heads=KV,
                               head_dim=hd, d_ff=d, vocab=64, dtype="float32",
                               qk_norm=False, sliding_window=window,
                               attn_block_q=64, attn_block_kv=64)
            p = init_attention(jax.random.PRNGKey(0), base)
            x = jax.random.normal(jax.random.PRNGKey(1), (B, S, d), jnp.float32)
            pos = jnp.arange(S)
            impls = {
                "xla_dense": base.replace(blockwise_threshold=S + 1),
                "xla_blockwise": base.replace(blockwise_threshold=S),
                "pallas": base.replace(attn_impl="pallas"),
            }
            frac = visited_fraction(S, 64, 64, causal=True, window=window)
            t_dense = None
            for name, cfg in impls.items():
                fn = jax.jit(lambda x, cfg=cfg: attend(p, cfg, x, pos))
                us = _time(fn, x)
                if t_dense is None:
                    t_dense = us
                rows.append({
                    "name": f"attention_bench/S{S}_w{window}/{name}",
                    "value": round(us, 1),
                    "derived": (f"us_per_call;visited_frac={frac:.3f};"
                                f"frac_of_dense_flops="
                                f"{frac * t_dense / us:.3f}"),
                })
    return rows


_MESH_KERNEL_CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import time

import jax
import jax.numpy as jnp

from repro.kernels import ops, ref
from repro.kernels.flash_attention import gqa_flash_attention
from repro.kernels.partition import kernel_partitioning
from repro.launch.mesh import make_debug_mesh
from repro.launch.sharding import kernel_specs

mesh = make_debug_mesh(data=2, model=2, pod=2)
parts = kernel_specs(mesh)


def timeit(fn, iters=5):
    jax.block_until_ready(fn())
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn()
        jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6


k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
B, S, H, KV, hd = 4, 128, 4, 2, 32
q = jax.random.normal(k1, (B, S, H, hd), jnp.float32)
k = jax.random.normal(k2, (B, S, KV, hd), jnp.float32)
v = jax.random.normal(k3, (B, S, KV, hd), jnp.float32)
x = jax.random.normal(k1, (256, 512), jnp.float32)
g = jax.random.normal(k2, (4, 64, 48), jnp.float32)
t = jax.random.normal(k1, (256, 128), jnp.float32)
p = jax.random.normal(k2, (256, 128), jnp.float32)
u = jax.random.normal(k3, (256, 128), jnp.float32)
cases = {
    "flash": (
        lambda: gqa_flash_attention(q, k, v, causal=True, block_q=32, block_kv=64),
        lambda: ref.gqa_attention_ref(q, k, v, causal=True)),
    "quantize": (lambda: ops.quantize_rowwise(x, 4)[0],
                 lambda: ref.rowwise_quantize_ref(x, 4)[0]),
    "ns": (lambda: ops.ns_orthogonalize(g, block=16),
           lambda: ref.ns_orthogonalize_ref(g)),
    "outer_update": (
        lambda: ops.nesterov_update(t, p, u, lr=0.7, momentum=0.9),
        lambda: ref.nesterov_update_ref(t, p, u, lr=0.7, momentum=0.9)),
}
out = {"_partitioning": {
    "flash_axes": list(parts.flash_axes),
    "quantize_axes": list(parts.quantize_axes),
    "ns_axes": list(parts.ns_axes),
    "outer_tp": parts.outer_tp,
}}
for name, (pallas_fn, xla_fn) in cases.items():
    with kernel_partitioning(parts), jax.set_mesh(mesh):
        t_sm = timeit(jax.jit(pallas_fn))
    with jax.set_mesh(mesh):
        t_xla = timeit(jax.jit(xla_fn))
    out[name] = {"shard_map_us": t_sm, "xla_us": t_xla}
print(json.dumps(out))
"""


def bench_mesh_kernels() -> list[dict]:
    """mesh_kernel_bench: shard_mapped Pallas vs XLA on an 8-host-device mesh.

    Spawns a child with ``--xla_force_host_platform_device_count=8`` (XLA
    pins the device count at first init, so this process keeps its single
    device) and a (pod=2, data=2, model=2) mesh, then times each kernel
    two ways under the mesh: the shard_mapped Pallas path (kernel routing
    installed) and the GSPMD-partitioned jnp/XLA reference.

    CPU dispatch proxy: Pallas runs in interpret mode here, so absolute
    times measure interpreter + per-shard dispatch overhead, not TPU kernel
    perf — the rows exist to prove every kernel *executes* shard_mapped on
    a mesh and to track the dispatch-level cost of the routing; the
    speedup column only becomes a perf claim on real accelerators.
    """
    import os
    import subprocess
    import sys

    if jax.default_backend() != "cpu":
        # this process already holds the accelerator, so a child that needs
        # the devices would fail or hang; the multi-chip path is checked by
        # `python chip_smoke.py --four-chips` in one process instead
        raise RuntimeError(
            f"mesh_kernel_bench runs its 8-device child on virtual CPU "
            f"devices only (backend here: {jax.default_backend()}); on the "
            f"chip run `python chip_smoke.py --four-chips`")
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    res = subprocess.run([sys.executable, "-c", _MESH_KERNEL_CHILD],
                         capture_output=True, text=True, env=env, timeout=900)
    if res.returncode != 0:
        raise RuntimeError(f"mesh kernel child failed: {res.stderr[-2000:]}")
    data = json.loads(res.stdout.strip().splitlines()[-1])
    parts = data.pop("_partitioning", {})
    print(f"# mesh_kernel_bench partitioning: {parts}", file=sys.stderr,
          flush=True)
    rows = []
    for kernel, rec in data.items():
        speedup = rec["xla_us"] / max(rec["shard_map_us"], 1e-9)
        rows.append({
            "name": f"mesh_kernel_bench/{kernel}/shard_map",
            "value": round(rec["shard_map_us"], 1),
            "derived": (f"us_per_call;cpu_dispatch_proxy;"
                        f"speedup_vs_xla={speedup:.3f}"),
        })
        rows.append({
            "name": f"mesh_kernel_bench/{kernel}/xla",
            "value": round(rec["xla_us"], 1),
            "derived": "us_per_call;cpu_dispatch_proxy",
        })
    return rows


def bench_roofline_table(dryrun_dir: str = "results/dryrun") -> list[dict]:
    """The 40-combination baseline roofline table from the dry-run records."""
    rows = []
    for path in sorted(glob.glob(f"{dryrun_dir}/*.json")):
        for rec in json.load(open(path)):
            if rec["status"] != "ok":
                if rec["status"] == "skipped":
                    rows.append({"name": f"roofline/{rec['arch']}/{rec['shape']}/{rec['mesh']}",
                                 "value": "skip", "derived": rec["reason"]})
                continue
            r = rec["roofline"]
            rows.append({
                "name": f"roofline/{rec['arch']}/{rec['shape']}/{rec['plan']}/{rec['mesh']}",
                "value": f"{max(r['compute_s'], r['memory_s'], r['collective_s']):.3e}",
                "derived": (f"dom={r['dominant']};C={r['compute_s']:.2e};"
                            f"M={r['memory_s']:.2e};X={r['collective_s']:.2e};"
                            f"useful={r['useful_flops_ratio']:.2f};"
                            f"peakGiB={rec['memory']['peak_per_chip_gib']}"),
            })
    return rows
