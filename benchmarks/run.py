"""Benchmark harness — one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run            # everything
    PYTHONPATH=src python -m benchmarks.run --only fig2,tab5
    PYTHONPATH=src python -m benchmarks.run --only kernels --json results/

Prints ``name,value,derived`` CSV rows (and writes results/benchmarks.csv).
``--json DIR`` additionally writes one ``BENCH_<target>.json`` per target —
``{"target", "rows": [{"name", "value", "derived"}, ...], "elapsed_s"}`` —
the machine-readable artifact the CI benchmark-regression tier diffs
against the committed ``benchmarks/baseline.json``
(:mod:`benchmarks.check_regression`).
"""
from __future__ import annotations

import argparse
import csv
import os
import sys
import time


def all_benchmarks():
    from benchmarks import paper_figures as pf
    from benchmarks import systems as sy

    return {
        "fig6a": pf.bench_fig6a_worker_scaling,
        "fig6b": pf.bench_fig6b_sync_interval,
        "tab5": pf.bench_tab5_quantization,
        "tab4": pf.bench_tab4_topk,
        "fig8b": pf.bench_fig8b_streaming,
        "fig2": pf.bench_fig2_alignment,
        "fig3": pf.bench_fig3_interference,
        "fig5": pf.bench_fig5_frobenius,
        "prop42": pf.bench_prop42_identity,
        "train_throughput": sy.bench_train_throughput,
        "serve_bench": sy.bench_serve_throughput,
        "optimizer_bench": sy.bench_optimizer_sweep,
        "compression_bench": sy.bench_compression_sweep,
        "fault_bench": sy.bench_fault_bench,
        "tab10": sy.bench_tab10_wallclock,
        "fig16": sy.bench_fig16_utilization,
        "tab2": sy.bench_tab2_scaling_forms,
        "kernels": sy.bench_kernel_micro,
        "attention_bench": sy.bench_attention_sweep,
        "mesh_kernel_bench": sy.bench_mesh_kernels,
        "roofline": sy.bench_roofline_table,
    }


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="comma-separated subset")
    ap.add_argument("--out", default="results/benchmarks.csv")
    ap.add_argument("--json", default=None, metavar="DIR",
                    help="also write one BENCH_<target>.json per target into "
                         "DIR (the regression tier's comparison artifact)")
    return ap


def main() -> None:
    args = build_parser().parse_args()
    from repro.launch.compile_cache import use_compilation_cache

    use_compilation_cache()

    benches = all_benchmarks()
    names = args.only.split(",") if args.only else list(benches)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    if args.json:
        os.makedirs(args.json, exist_ok=True)
    rows = []
    failed = []
    print("name,value,derived")
    for name in names:
        t0 = time.time()
        try:
            out = benches[name]()
        except Exception as e:  # noqa: BLE001 — run the rest, then fail
            print(f"{name}/ERROR,{type(e).__name__},{e}", flush=True)
            failed.append(name)
            continue
        finally:
            # the suite compiles hundreds of distinct programs; without this
            # the XLA CPU JIT eventually fails to materialize new dylibs
            import jax

            jax.clear_caches()
        for row in out:
            print(f"{row['name']},{row['value']},{row['derived']}", flush=True)
            rows.append(row)
        elapsed = time.time() - t0
        print(f"# {name} done in {elapsed:.1f}s", file=sys.stderr, flush=True)
        if args.json:
            import json

            with open(os.path.join(args.json, f"BENCH_{name}.json"), "w") as f:
                json.dump({"target": name, "rows": out,
                           "elapsed_s": round(elapsed, 2)}, f, indent=1)
                f.write("\n")
    with open(args.out, "w", newline="") as f:
        w = csv.DictWriter(f, fieldnames=["name", "value", "derived"])
        w.writeheader()
        w.writerows(rows)
    if failed:
        sys.exit(f"benchmark targets failed: {','.join(failed)}")


if __name__ == "__main__":
    main()
