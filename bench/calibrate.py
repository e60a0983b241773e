"""Readings that the check's limits are set from, on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds 11,12,13 \
        [--variants control,half_batch]

For each seed: the program's set-up and round 0 (as in a run, with no
window) against the float32 reference, then each variant against the same
reference: ``control`` is the reference with fp8-rounded matrix operands,
``half_batch`` the reference with half of every worker's sequences left out
of each step. One JSON line per (seed, side) on standard output.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def worst_leaves(prog: dict, ref: dict, n: int = 3) -> dict:
    """The ``n`` leaves with the largest gaps of each leaf number."""
    from bench import check

    keep = check.moved_leaves(ref)
    out = {}
    for key in ("u", "change"):
        gaps = check.leaf_gaps(prog[key], ref[key], keep)
        out[key] = sorted(gaps.items(), key=lambda kv: -kv[1])[:n]
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--variants", default="control,half_batch")
    ap.add_argument("--variant-seeds", type=int, default=3,
                    help="run the variants on the first N seeds only")
    args = ap.parse_args()
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if jax.devices()[0].platform != "tpu":
        print("calibrate: JAX found no TPU", file=sys.stderr)
        return 2
    from bench import check, harness
    from bench.reference.common import follow_round0

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cell = {w["name"]: w for w in json.load(f)["workloads"]}[args.workload]
    with open(os.path.join(BENCH_DIR, "configs", f"{cell['config']}.json")) as f:
        config = json.load(f)
    with open(os.path.join(BENCH_DIR, "traffic", f"{cell['traffic']}.json")) as f:
        traffic = json.load(f)
    family = harness.load_module("reference", config["family"])
    rt = harness.reference_traffic(traffic)
    variants = [v for v in args.variants.split(",") if v]
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        program = harness.Program(config, traffic, seed)
        prog, round_s, _ = program.round0()
        del program
        gc.collect()
        t_ref = time.perf_counter()
        ref = follow_round0(family, config["model"], rt, seed)
        ref_s = time.perf_counter() - t_ref
        print(json.dumps({"seed": seed, "side": "program", "round0_s": round_s,
                          "reference_s": ref_s, "setup_and_round0_s": t_ref - t,
                          "readings": check.readings(prog, ref),
                          "worst": worst_leaves(prog, ref),
                          "loss": prog["loss"], "ref_loss": ref["loss"],
                          "eval": prog["eval_loss"], "ref_eval": ref["eval_loss"]}),
              flush=True)
        if i >= args.variant_seeds:
            continue
        for v in variants:
            t_v = time.perf_counter()
            kw = {"numerics": "fp8"} if v == "control" else {"half_batch": True}
            other = follow_round0(family, config["model"], rt, seed, **kw)
            print(json.dumps({"seed": seed, "side": v,
                              "seconds": time.perf_counter() - t_v,
                              "readings": check.readings(other, ref),
                              "worst": worst_leaves(other, ref),
                              "loss": other["loss"], "eval": other["eval_loss"]}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
