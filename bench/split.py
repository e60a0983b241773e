"""Device time of one cell's round by the program's layers, from a trace.

    python3 bench/split.py --workload <cell> --seed <n> --seconds <s> [--keep <path>]

Sets the cell up as ``bench/run.py`` does (``bench/harness.py``: the
program, round 0), then profiles the measured window and reads the trace
with :mod:`bench.scopes`. Prints one JSON line: the cell's per-layer metrics
of ``BENCHMARK.json``, the shares of the program's scopes
(``bench/metrics/{fwd_bwd,inner_opt,outer_sync,eval}_frac.py``), the
window's device self seconds by scope with the unscoped rest (they add up to
``busy_s``), the sync's stages, the longest unscoped operations, the idle
gaps named by the ``bench.`` and ``repro.`` host spans, and the traced
window's rate (``traced_tokens_per_s``; set it beside ``bench/run.py
--trace 0`` on the same seed for what tracing costs). It checks nothing
against the reference and refuses without a TPU, as ``bench/run.py`` does.
``--keep`` writes the trace there, without the programs' HLO.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
from types import SimpleNamespace

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
SCOPE_METRICS = ("fwd_bwd_frac", "inner_opt_frac", "outer_sync_frac", "eval_frac")
LAYERS = ("repro.fwd_bwd", "repro.inner_opt", "repro.outer_sync", "repro.eval",
          "repro.datagen")
STAGES = ("repro.newton_schulz", "repro.pseudograd", "repro.reduce", "repro.outer_update")


def load_json(*parts: str):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", help="write the trace here")
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"split: JAX found no TPU (platform {device.platform})", file=sys.stderr)
        return 2
    from bench import harness, scopes

    bench = load_json(ROOT, "BENCHMARK.json")
    cell = {w["name"]: w for w in bench["workloads"]}[args.workload]
    config = load_json(BENCH_DIR, "configs", f"{cell['config']}.json")
    traffic = load_json(BENCH_DIR, "traffic", f"{cell['traffic']}.json")
    per_layer = [m for m in bench["per_layer"]
                 if cell["name"] in m.get("workloads", [cell["name"]])]

    program = harness.Program(config, traffic, args.seed)
    _, round_s, _ = program.round0()
    trace_dir = tempfile.mkdtemp(prefix="bench-split-")
    jax.profiler.start_trace(trace_dir)
    try:
        win = program.window(args.seconds, round_s)
    finally:
        jax.profiler.stop_trace()
    t = time.perf_counter()
    path = scopes.tr.find_xplane(trace_dir)
    if args.keep:
        scopes.save_without_hlo(path, args.keep)
    trace = scopes.load(path)
    lo, hi = trace.window()
    window_s = (hi - lo) / 1e9
    ctx = SimpleNamespace(trace=trace, trace_window_ns=(lo, hi), trace_window_s=window_s)
    shares = {m: harness.load_module("metrics", m).read(ctx) for m in SCOPE_METRICS}
    split = scopes.scope_split(trace, lo, hi, LAYERS)
    line = {
        "workload": cell["name"], "seed": args.seed,
        "device": {"kind": device.device_kind, "count": cell["chips"]},
        "traced_tokens_per_s": win["tokens"] / win["window_s"],
        "rounds": win["rounds"], "tf_ops": len(trace.tf_ops),
        "scope_shares": shares,
        "split_s": {k: v / 1e9 for k, v in split.items()},
        "split_s_strict": {k: v / 1e9 for k, v in
                           scopes.scope_split(trace, lo, hi, LAYERS, infer=False).items()},
        "stages_s": {s: scopes.scope_ns(trace, lo, hi, s) / 1e9 for s in STAGES},
        "top_unscoped": scopes.top_unscoped(trace, lo, hi, LAYERS),
        "idle_gaps": scopes.idle_gaps(trace, lo, hi),
    }
    del trace, ctx
    # the benchmark's own readings of the same trace (this removes trace_dir)
    metrics, extra = harness.per_layer_metrics(
        cell, config, traffic, win, trace_dir, per_layer, load_json(BENCH_DIR, "peaks.json"))
    line.update(per_layer={k: v["value"] for k, v in metrics.items()},
                busy_s=extra["busy_s"], trace_window_s=extra["trace_window_s"],
                bench_idle_gaps=extra["breakdown"]["idle_gaps"],
                read_s=time.perf_counter() - t)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
