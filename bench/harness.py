"""One run of one cell: set-up, the measured window, the readings, the check.

The run is built as ``repro.launch.train.train`` builds it, from the CLI
flags the cell's traffic file states (``build_parser``, ``make_diloco_cfg``,
``TrainEngine``, ``MarkovStream``):

* set-up: the engine and its state from the seed (weights made on the
  device), the round program and the data programs compiled or read from
  the persistent cache, and round 0 dispatched through the window's own
  call and feed (``engine.superstep`` on ``span_batches_for(0, 1)`` and
  ``eval_batches_for(0, 1)``). Round 0's losses and the state it leaves are
  what the check compares with the reference;
* the window: ``repro.engine.run_rounds`` from round 1 with
  ``rounds_per_dispatch=1``, in-program eval and a ``should_stop`` that ends
  dispatching after the whole rounds that fit ``seconds`` at round 0's
  pace. It opens at the first dispatch and closes when the last round's
  state is ready, so it holds whole rounds; ``train_tokens_per_s`` is every
  token of those rounds over that time;
* then the peak device memory is read, the program's state is freed, and
  the reference follows round 0 from the same seed.
"""
from __future__ import annotations

import gc
import importlib.util
import os
import tempfile
import time
from types import SimpleNamespace

import jax
import numpy as np

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))


class CompileClock:
    """Counts XLA backend compiles and persistent-cache hits and misses
    while open, and sums the compile seconds."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    CACHE = {"/jax/compilation_cache/cache_hits": "hits",
             "/jax/compilation_cache/cache_misses": "misses"}

    def __enter__(self):
        self.seconds, self.compiles = 0.0, 0
        self.cache = {"hits": 0, "misses": 0}
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def _on_duration(self, event, duration, **_):
        if event == self.EVENT:
            self.seconds += duration
            self.compiles += 1

    def _on_event(self, event, **_):
        if event in self.CACHE:
            self.cache[self.CACHE[event]] += 1

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)
        return False


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module."""
    path = os.path.join(BENCH_DIR, kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cli_flags(config: dict, traffic: dict, seed: int) -> list[str]:
    """The train-CLI flags of the cell: the traffic's sizes, optimizer and
    compression settings, then any further flags it lists."""
    inner = traffic["inner"]
    comp = traffic.get("compression")
    wire = (["--compression", "quant", "--quant-mode", "linear", "--bits", str(comp["bits"])]
            + ["--rowwise"] * comp["rowwise"] + ["--error-feedback"] * comp["error_feedback"]
            if comp else [])
    return ["--arch", config["registry"], "--seed", str(seed),
            "--inner", traffic["inner_optimizer"],
            "--workers", str(traffic["workers"]),
            "--sync-interval", str(traffic["sync_interval"]),
            "--batch-per-worker", str(traffic["batch_per_worker"]),
            "--seq-len", str(traffic["seq_len"]),
            "--rounds", str(traffic["rounds"]),
            "--lr", repr(inner["lr"]), "--weight-decay", repr(inner["weight_decay"]),
            "--outer-lr", repr(traffic["outer_lr"]),
            "--outer-momentum", repr(traffic["outer_momentum"]),
            *wire, *traffic.get("flags", [])]


def path_str(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", getattr(k, "name", k))))
                    for k in path)


def host_leaves(tree) -> dict:
    """{path: float32 numpy array} of a device pytree."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {path_str(p): np.asarray(jax.device_get(x), np.float32) for p, x in flat}


def leaf_norms(leaves: dict) -> dict:
    return {k: float(np.linalg.norm(v.ravel())) for k, v in leaves.items()}


class Program:
    """The system under test, built as the train CLI builds it."""

    def __init__(self, config: dict, traffic: dict, seed: int):
        from repro.configs import get_config
        from repro.core.diloco import DiLoCoConfig  # noqa: F401  (import order)
        from repro.data import DataConfig, MarkovStream, batches_for_span
        from repro.engine import TrainEngine
        from repro.kernels.autotune import configure, tuned_model_config
        from repro.launch.train import build_parser, make_diloco_cfg, parse_mesh
        from repro.models import build_model
        from repro.optim import OptimizerConfig

        args = build_parser().parse_args(cli_flags(config, traffic, seed))
        cfg = get_config(args.arch).replace(**config["model"])
        seq_len = args.seq_len
        cfg = cfg.replace(
            max_seq_len=seq_len,
            sliding_window=min(cfg.sliding_window, seq_len) if cfg.sliding_window else 0,
            attn_impl=args.attn_impl)
        configure(enabled=args.autotune == "on", table_path=args.autotune_table)
        if args.autotune == "on":
            cfg = tuned_model_config(cfg, seq_len)
        model = build_model(cfg)
        dcfg = make_diloco_cfg(args)
        total_steps = args.rounds * args.sync_interval
        icfg = OptimizerConfig(
            lr=args.lr, weight_decay=args.weight_decay, schedule=args.schedule,
            warmup_steps=max(total_steps // 100, 5), total_steps=total_steps,
            ns_period=args.ns_period)
        inner = traffic["inner"]
        stated = {"b1": icfg.b1, "b2": icfg.b2, "eps": icfg.eps, "schedule": icfg.schedule}
        for key, got in stated.items():
            if inner[key] != got:
                raise ValueError(f"the program's {key} is {got}, the traffic states "
                                 f"{inner[key]}")
        ekw: dict = {}
        if args.mesh:
            from repro.launch.mesh import mesh_axis_sizes
            from repro.launch.steps import activation_rules

            mesh = parse_mesh(args.mesh)
            ekw = {"mesh": mesh,
                   "rules": activation_rules(mesh, args.batch_per_worker, cfg, train=True),
                   "spmd_axis": "pod" if mesh_axis_sizes(mesh).get("pod", 0) > 1 else None}
        self.args, self.cfg, self.dcfg = args, cfg, dcfg
        self.engine = TrainEngine(model, dcfg, icfg, **ekw)
        self.state = self.engine.init(jax.random.PRNGKey(args.seed))
        if args.mesh:
            from repro.launch.steps import tp_friendly

            self.state = jax.device_put(self.state, self.engine.state_shardings(
                tensor_parallel=tp_friendly(cfg, ekw["mesh"])))
        data = MarkovStream(DataConfig(
            vocab=cfg.vocab, seq_len=cfg.max_seq_len,
            batch_per_worker=args.batch_per_worker, n_workers=dcfg.n_workers,
            seed=args.seed))
        eval_data = MarkovStream(DataConfig(
            vocab=cfg.vocab, seq_len=cfg.max_seq_len,
            batch_per_worker=args.batch_per_worker, n_workers=1,
            seed=args.seed + 10_000))
        H = dcfg.sync_interval

        def span_batches_for(r0, n):
            with jax.profiler.TraceAnnotation("bench.datagen"):
                return batches_for_span(data, r0, H, n)

        def eval_batches_for(r0, n):
            with jax.profiler.TraceAnnotation("bench.datagen"):
                return jax.tree.map(lambda x: x[:, 0], eval_data.batch_stack(r0, n))

        self.span_batches_for, self.eval_batches_for = span_batches_for, eval_batches_for
        self.tokens_per_round = (H * dcfg.n_workers * args.batch_per_worker
                                 * cfg.max_seq_len)

    def round0(self) -> tuple[dict, float, float]:
        """Round 0 through the window's call and feed. Returns the readings,
        the round's device seconds (from the call's return to the state
        being ready) and the host seconds spent reading the state."""
        t = time.perf_counter()
        p0 = host_leaves(self.state["outer_params"])
        read_s = time.perf_counter() - t
        with jax.profiler.TraceAnnotation("bench.dispatch"):
            self.state, out = self.engine.superstep(
                self.state, self.span_batches_for(0, 1), self.eval_batches_for(0, 1))
        t_call = time.perf_counter()
        jax.block_until_ready(self.state)
        round_s = time.perf_counter() - t_call
        t = time.perf_counter()
        prog = {"loss": [float(x) for x in np.asarray(out["loss"])[0]],
                "eval_loss": float(np.asarray(out["eval_loss"])[0]),
                "comm_bytes": float(np.asarray(out["comm_bytes"])[0])}
        del out
        prog["u"] = leaf_norms(host_leaves(self.state["outer_opt"]["u"]))
        p1 = host_leaves(self.state["outer_params"])
        prog["change"] = {k: float(np.linalg.norm((p1[k] - p0[k]).ravel())) for k in p0}
        del p0, p1
        read_s += time.perf_counter() - t
        return prog, round_s, read_s

    def window(self, seconds: float, round_s: float) -> dict:
        """Whole rounds from round 1 while they fit ``seconds`` at round 0's
        pace (at least one)."""
        from repro.engine import run_rounds

        n_rounds = max(1, int(seconds // max(round_s, 1e-9)))
        telemetry: dict = {}
        records: list = []

        def should_stop():
            return telemetry.get("dispatches", 0) >= n_rounds

        def on_round(rec):
            with jax.profiler.TraceAnnotation("bench.drain"):
                records.append(rec)

        engine = _Annotated(self.engine)
        with CompileClock() as clock, jax.profiler.TraceAnnotation("bench.window"):
            t0 = time.perf_counter()
            self.state, _ = run_rounds(
                engine, self.state, None, self.args.rounds, start=1,
                rounds_per_dispatch=1,
                span_batches_for=self.span_batches_for,
                eval_batches_for=self.eval_batches_for,
                on_round=on_round, telemetry=telemetry, should_stop=should_stop)
            jax.block_until_ready(self.state)
            window_s = time.perf_counter() - t0
        if clock.compiles or clock.cache["hits"] or clock.cache["misses"]:
            raise RuntimeError(f"{clock.compiles} compiles and cache lookups "
                               f"{clock.cache} inside the measured window")
        return {"window_s": window_s, "rounds": len(records),
                "tokens": len(records) * self.tokens_per_round,
                "failed": sum(not np.isfinite(r["train_loss"]) for r in records),
                "comm_bytes": [r["comm_bytes"] for r in records]}


class _Annotated:
    """The engine, with each dispatch inside a ``bench.dispatch`` span."""

    def __init__(self, engine):
        self._engine = engine

    def __getattr__(self, name):
        return getattr(self._engine, name)

    def superstep(self, *a, **kw):
        with jax.profiler.TraceAnnotation("bench.dispatch"):
            return self._engine.superstep(*a, **kw)


def peak_memory_bytes(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run(cell: dict, config: dict, traffic: dict, seed: int, seconds: float,
        trace: bool, per_layer: list, limits: dict, t_start: float,
        peaks: dict | None = None) -> dict:
    """One run of ``cell``; returns the result's fields and the check."""
    from bench import check
    from bench.reference.common import follow_round0

    devices = jax.devices()[: cell["chips"]]
    with CompileClock() as setup_clock:
        program = Program(config, traffic, seed)
        prog, round_s, read_s = program.round0()
    setup_s = time.perf_counter() - t_start - read_s

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        jax.profiler.start_trace(trace_dir)
    try:
        win = program.window(seconds, round_s)
    finally:
        if trace:
            jax.profiler.stop_trace()
    memory_peak = peak_memory_bytes(devices)
    del program
    gc.collect()

    result = {"setup_s": setup_s, "setup_compiles": setup_clock.compiles,
              "setup_compile_s": setup_clock.seconds, "setup_cache": setup_clock.cache,
              "round0_s": round_s, **win, "memory_peak_bytes": memory_peak}
    metrics = {"train_tokens_per_s": win["tokens"] / win["window_s"]}
    if trace:
        metrics, extra = per_layer_metrics(cell, config, traffic, win, trace_dir,
                                           per_layer, peaks)
        result.update(extra)

    t = time.perf_counter()
    family = load_module("reference", config["family"])
    ref = follow_round0(family, config["model"], reference_traffic(traffic), seed)
    result["reference_s"] = time.perf_counter() - t
    read = check.readings(prog, ref)
    correct, table = check.judge(read, limits)
    result.update(metrics=metrics, correct=correct and win["failed"] == 0,
                  check=table, readings=read, program=prog, reference=ref)
    return result


def reference_traffic(traffic: dict) -> dict:
    keys = ("inner_optimizer", "workers", "sync_interval", "batch_per_worker", "seq_len",
            "rounds", "inner", "outer_lr", "outer_momentum", "compression",
            "reference_seq_block")
    return {k: traffic[k] for k in keys if k in traffic}


def per_layer_metrics(cell, config, traffic, win, trace_dir, per_layer, peaks):
    """The cell's per-layer metrics from the trace, and the traced run's
    ``busy_s``/``window_s`` and ``breakdown``."""
    import shutil

    from bench import trace as tr

    try:
        t = tr.load(tr.find_xplane(trace_dir))
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    lo, hi = t.window()
    trace_window_s = (hi - lo) / 1e9
    busy = [tr.busy_ns(d, lo, hi) / 1e9 for d in t.devices.values()]
    busy_s = sum(busy) / len(busy) if busy else 0.0
    flops = load_module("flops", config["family"])
    device_kind = jax.devices()[0].device_kind
    ctx = SimpleNamespace(
        trace=t, trace_window_ns=(lo, hi), trace_window_s=trace_window_s, busy_s=busy_s,
        window_s=win["window_s"], tokens=win["tokens"], chips=cell["chips"],
        flops_per_token=flops.train_flops_per_token(config["model"], traffic["seq_len"]),
        peak=(peaks or {}).get(device_kind), comm_bytes=win["comm_bytes"])
    metrics = {}
    for m in per_layer:
        value = load_module("metrics", m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    extra = {"busy_s": busy_s, "trace_window_s": trace_window_s,
             "breakdown": {"device_ops": tr.top_ops(t, lo, hi),
                           "idle_gaps": tr.idle_gaps(t, lo, hi)}}
    return metrics, extra
