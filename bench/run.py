"""Run one cell of the chip benchmark once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``workloads`` in ``BENCHMARK.json``) names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``); ``bench/limits/<cell>.json`` holds the
limits of the check. With ``--trace 0`` the last line of standard output is
one JSON object with the cell's end-to-end metrics; with ``--trace 1`` the
window is profiled and the line holds the cell's per-layer metrics, the
device's busy and window seconds, and a breakdown of device time and idle
gaps. Every run checks round 0 of the timed program against the plain
reference (``bench/check.py``) and prints each compared number beside its
limit, last on standard error and last in the result line.

The run refuses (non-zero exit, no result) when JAX finds no TPU, fewer
chips than the cell asks for, a device kind missing from
``bench/peaks.json``, or no program beside the benchmark.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(*parts: str):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def fail(msg: str, code: int = 2) -> int:
    print(f"bench: {msg}", file=sys.stderr)
    return code


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        return fail(f"unknown workload {args.workload!r}; known: {sorted(cells)}")
    cell = cells[args.workload]
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        return fail(f"no program (src/repro) beside {BENCH_DIR}")
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        return fail(f"JAX found no TPU (platform {devices[0].platform})")
    if len(devices) < cell["chips"]:
        return fail(f"the cell needs {cell['chips']} chips, JAX found {len(devices)}")
    peaks = load_json(BENCH_DIR, "peaks.json")
    if devices[0].device_kind not in peaks:
        return fail(f"device kind {devices[0].device_kind!r} is not in bench/peaks.json")

    from bench import check, harness

    config = load_json(BENCH_DIR, "configs", f"{cell['config']}.json")
    traffic = load_json(BENCH_DIR, "traffic", f"{cell['traffic']}.json")
    limits = check.load_limits(BENCH_DIR, cell["name"])
    wanted = "per_layer" if args.trace else "end_to_end"
    metric_defs = [m for m in bench[wanted]
                   if cell["name"] in m.get("workloads", [cell["name"]])]
    res = harness.run(cell, config, traffic, args.seed, args.seconds, bool(args.trace),
                      metric_defs, limits, T_START, peaks=peaks)

    units = {m["name"]: m["unit"] for m in metric_defs}
    metrics = {k: {"value": v["value"] if isinstance(v, dict) else v, "unit": units[k]}
               for k, v in res["metrics"].items() if k in units}
    if not args.trace:
        metrics["setup_s"] = {"value": res["setup_s"], "unit": units["setup_s"]}
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": cell["chips"], "memory_peak_bytes": res["memory_peak_bytes"]}
    if args.trace:
        device.update(busy_s=res["busy_s"], window_s=res["trace_window_s"])
    detail = {k: res[k] for k in ("setup_s", "setup_compiles", "setup_compile_s",
                                  "setup_cache", "round0_s", "window_s", "rounds",
                                  "tokens", "reference_s")}
    detail["readings"] = res["readings"]
    detail["program"] = {k: res["program"][k] for k in ("loss", "eval_loss", "comm_bytes")}
    detail["reference"] = {k: res["reference"][k] for k in ("loss", "eval_loss")}
    print("bench detail: " + json.dumps(detail), file=sys.stderr)
    line = {"correct": res["correct"], "attempted": res["rounds"], "failed": res["failed"],
            "metrics": metrics, "device": device}
    if args.trace:
        line["breakdown"] = res["breakdown"]
    line["check"] = {k: {"value": v, "limit": lim} for k, (v, lim) in res["check"].items()}
    for k, (v, lim) in res["check"].items():
        print(f"check {k} {v!r} limit {lim!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
