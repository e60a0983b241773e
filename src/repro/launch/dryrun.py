import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 " + os.environ.get("XLA_FLAGS", "")
)

# ruff: noqa: E402  (the two lines above MUST precede any jax-touching import)
"""Multi-pod dry-run: lower + compile every (arch x input-shape x mesh)
combination on 512 placeholder host devices and record memory / cost /
collective evidence for the roofline analysis.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch smollm-135m --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all --out results/dryrun
    PYTHONPATH=src python -m repro.launch.dryrun --arch ... --multi-pod

Outputs one JSON per (arch, shape, mesh) under --out.
"""
import argparse
import json
import time
import traceback

import jax

from repro.configs import (
    ASSIGNED_ARCHS,
    INPUT_SHAPES,
    config_for_shape,
    get_config,
    shape_supported,
)
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import build_plans
from repro.models.api import build_model
from repro.roofline.analysis import (
    RooflineTerms,
    active_params,
    model_flops,
    parse_collective_bytes,
)
from repro.roofline.flops import (
    forward_flops,
    hbm_bytes,
    train_step_flops,
)
from repro.roofline.hlo import collective_bytes_corrected
from repro.utils.tree import tree_bytes, tree_count_params


def run_one(arch: str, shape: str, multi_pod: bool, sync_interval: int = 30,
            verbose: bool = True, plan_filter: str | None = None,
            inner_name: str = "muon", rounds_per_dispatch: int = 4,
            compression: str = "none", bits: int = 4,
            topk_frac: float = 0.01, attn_impl: str = "xla",
            ns_impl: str = "jnp", outer_kernel: bool = False,
            wire_impl: str = "jnp", straggler_sigma: float = 0.25,
            straggler_drop: float = 0.0) -> list[dict]:
    """Lower + compile all step plans for one (arch, shape, mesh) combo."""
    from repro.core.compression import CompressionConfig

    # Pallas calls carry no GSPMD partitioning rules of their own, but the
    # StepPlan machinery routes every call site through shard_map on the
    # plan's mesh (launch/sharding.kernel_specs), so 'pallas' backends lower
    # on the 512-device world too — a plan that still fails is recorded as
    # status=error with an error_path classifying which route broke
    cfg0 = get_config(arch).replace(attn_impl=attn_impl)
    if not shape_supported(cfg0, shape):
        return [{
            "arch": arch, "shape": shape, "mesh": "2x16x16" if multi_pod else "16x16",
            "status": "skipped", "reason": f"{shape} not applicable (DESIGN.md §4)",
        }]
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    records = []
    kw = {}
    # wire_impl='pallas' shard_maps the quantize/dequantize rows over
    # ('pod','data') — the same K-folded layout the wire buffers carry
    ccfg = CompressionConfig(
        kind=compression, bits=bits, topk_frac=topk_frac, wire_impl=wire_impl,
        collective="gather" if compression == "topk" else "a2a_rs_ag")
    dcfg = None
    if INPUT_SHAPES[shape].kind == "train":
        from repro.core.diloco import DiLoCoConfig

        n_pods = 2 if multi_pod else 1
        dcfg = DiLoCoConfig(n_workers=n_pods, sync_interval=sync_interval,
                            inner_name=inner_name, compression=ccfg,
                            ns_impl=ns_impl, outer_kernel=outer_kernel)
        kw["dcfg"] = dcfg
        kw["rounds_per_dispatch"] = rounds_per_dispatch
    plans = build_plans(cfg0, shape, mesh, **kw)
    # kernel-routing evidence shared by every record of this combo: which
    # backends were requested and which mesh axes each kernel shards over
    from repro.launch.sharding import kernel_specs

    kparts = kernel_specs(mesh, cfg0)
    from repro.models.attention import attention_path

    uses_pallas = (attention_path(cfg0, INPUT_SHAPES[shape].seq_len) == "pallas"
                   or ns_impl == "pallas" or outer_kernel or wire_impl == "pallas")
    from repro.kernels.autotune import autotune_evidence

    kernels_evidence = {
        "attn_impl": attn_impl, "ns_impl": ns_impl,
        "outer_kernel": outer_kernel, "wire_impl": wire_impl,
        # which block-size knobs the committed autotune table resolved for
        # this shape's sequence length (empty 'tuned' = all constants)
        "autotune": autotune_evidence(config_for_shape(cfg0, shape),
                                      INPUT_SHAPES[shape].seq_len),
        "shard_map": kparts is not None,
        "partitioning": None if kparts is None else {
            "flash_axes": list(kparts.flash_axes),
            "quantize_axes": list(kparts.quantize_axes),
            "ns_axes": list(kparts.ns_axes),
            "paged_axes": list(kparts.paged_axes),
            "outer_tp": kparts.outer_tp,
        },
    }
    for plan in plans:
        if plan_filter and plan.name != plan_filter:
            continue
        rec = {
            "arch": arch, "shape": shape, "plan": plan.name,
            "mesh": "2x16x16" if multi_pod else "16x16", "chips": chips,
            "inner": inner_name if plan.meta["kind"] in
            ("train", "sync", "round", "superstep") else None,
            "kernels": kernels_evidence,
        }
        t0 = time.time()
        try:
            with jax.set_mesh(mesh):
                jitted = jax.jit(
                    plan.fn,
                    in_shardings=plan.in_shardings,
                    donate_argnums=plan.donate,
                )
                lowered = jitted.lower(*plan.args)
                compiled = lowered.compile()
            mem = compiled.memory_analysis()
            cost = compiled.cost_analysis()
            if isinstance(cost, list):  # some jaxlibs return [dict]
                cost = cost[0] if cost else {}
            hlo_text = compiled.as_text()
            coll_flat = parse_collective_bytes(hlo_text)
            coll = collective_bytes_corrected(hlo_text)
            cfg = plan.meta["cfg"]
            params_abs = jax.eval_shape(lambda: build_model(cfg).init(jax.random.PRNGKey(0)))
            n_params = tree_count_params(params_abs)
            n_active = active_params(cfg, n_params)
            mf = model_flops(plan.meta["kind"], n_active, plan.meta["tokens_per_step"])
            flops_chip, bytes_chip = _analytic_terms(plan, cfg, params_abs, chips, shape)
            # measured cross-worker wire traffic of the program's outer
            # sync(s): actual wire-buffer sizes, not the ratio model
            comm = None
            wire_total = 0.0
            if dcfg is not None and plan.meta["kind"] in ("sync", "round", "superstep"):
                from repro.core.collectives import (
                    collective_bytes_tree,
                    measured_sync_bytes,
                )

                per_sync = measured_sync_bytes(params_abs, ccfg, dcfg.n_workers)
                syncs = (plan.meta.get("rounds_per_dispatch", 1)
                         if plan.meta["kind"] == "superstep" else 1)
                wire_total = float(per_sync) * syncs
                comm = {
                    "compression": {"kind": ccfg.kind, "bits": ccfg.bits,
                                    "topk_frac": ccfg.topk_frac},
                    "measured_bytes_per_sync_per_worker": int(per_sync),
                    "modeled_bytes_per_sync_per_worker": collective_bytes_tree(
                        params_abs, ccfg, dcfg.n_workers)["bytes_per_sync_per_worker"],
                    "syncs_in_program": int(syncs),
                    "measured_bytes_in_program": int(wire_total),
                }
            terms = RooflineTerms(
                flops=flops_chip,
                hlo_bytes=bytes_chip,
                collective_bytes=float(coll["total"]),
                chips=chips,
                model_flops=mf,
                amortize=float(plan.meta["amortize"]),
                wire_bytes=wire_total,
            )
            if plan.meta["kind"] in ("train", "round", "superstep", "prefill"):
                from repro.kernels.flash_attention import (
                    clamp_block,
                    visited_fraction,
                )

                S = INPUT_SHAPES[shape].seq_len
                path = attention_path(cfg, S)
                rec["attention"] = {
                    "impl": cfg.attn_impl,
                    "path": path,
                    "block_q": clamp_block(cfg.attn_block_q, S),
                    "block_kv": clamp_block(cfg.attn_block_kv, S),
                    # block-granular execution: the flash kernel and the
                    # blockwise XLA path
                    "blockwise": path != "dense",
                    # fraction of the block grid the visit schedule executes
                    # (causal diagonal + sliding window skipping)
                    "visited_fraction": round(visited_fraction(
                        S, cfg.attn_block_q, cfg.attn_block_kv,
                        causal=True, window=cfg.sliding_window), 4),
                }
            if plan.meta["kind"] in ("train", "round", "superstep"):
                # straggler evidence at the paper's K=16 scale: per-round
                # wall-clock p50/p99 when every worker draws a lognormal
                # latency multiplier and an i.i.d. drop coin, vs the
                # deterministic lockstep estimate — "what does p99 worker
                # latency cost at K=16?" (uses the plan's measured per-sync
                # wire bytes when the comm block carries them)
                from repro.core.wallclock import (
                    RunSpec,
                    StragglerModel,
                    straggler_stats,
                )

                ishape = INPUT_SHAPES[shape]
                wspec = RunSpec(
                    n_params=float(n_params), n_active_params=float(n_active),
                    batch_tokens=float(ishape.global_batch * ishape.seq_len),
                    seq_len=ishape.seq_len, n_steps=sync_interval,
                    sync_interval=sync_interval, n_workers=16,
                    wire_bytes_per_sync=float(
                        comm["measured_bytes_per_sync_per_worker"])
                    if comm is not None else 0.0)
                smodel = StragglerModel(sigma=straggler_sigma,
                                        drop_prob=straggler_drop)
                rec["straggler_wallclock"] = {
                    "n_workers": 16, "sigma": straggler_sigma,
                    "drop_prob": straggler_drop,
                    "bandwidth_gbit_s": 1.0,
                    **straggler_stats(wspec, 1e9, smodel),
                }
            donation = None
            if plan.name in ("round_step", "superstep"):
                donation = round_step_donation_report(plan.args[0], hlo_text,
                                                      mem, chips)
                # record first, then fail: on a lost alias the record keeps
                # status=error AND the donation diagnostics (rec.update in
                # the except handler preserves existing keys)
                rec["donation"] = donation
                if not donation["outer_state_aliased"]:
                    raise RuntimeError(
                        f"{plan.name} donation lost the outer-transform state: "
                        f"params {donation['outer_opt_param_indices']} not all "
                        f"in the input_output_alias map "
                        f"(alias {donation['alias_bytes_per_chip']} B/chip)")
            if comm is not None:
                rec["comm"] = comm
            rec.update({
                "status": "ok",
                "compile_s": round(time.time() - t0, 1),
                "n_params": n_params,
                "n_active_params": n_active,
                "memory": {
                    "argument_bytes": int(mem.argument_size_in_bytes),
                    "output_bytes": int(mem.output_size_in_bytes),
                    "temp_bytes": int(mem.temp_size_in_bytes),
                    "alias_bytes": int(mem.alias_size_in_bytes),
                    "peak_per_chip_gib": round(
                        (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                         - mem.alias_size_in_bytes) / 2**30, 3),
                },
                "collectives": {k: int(v) for k, v in coll.items()},
                "collectives_uncorrected": {k: int(v) for k, v in coll_flat.items()},
                "hlo_cost_analysis": {
                    "flops_per_chip_loop_body_once": float(cost.get("flops", 0.0)),
                    "bytes_accessed_loop_body_once": float(cost.get("bytes accessed", 0.0)),
                },
                "roofline": terms.as_dict(),
            })
        except Exception as e:  # noqa: BLE001 — record the failure verbatim
            # classify where the lowering broke: a pallas backend under
            # shard_map routing, a pallas backend with NO routing installed
            # (single-device-only legacy path), or plain GSPMD
            if uses_pallas:
                error_path = ("pallas-shard-map" if kparts is not None
                              else "pallas-unpartitioned")
            else:
                error_path = "gspmd"
            rec.update({"status": "error", "error": f"{type(e).__name__}: {e}",
                        "error_path": error_path,
                        "traceback": traceback.format_exc()[-2000:]})
        if verbose:
            _print_record(rec)
        records.append(rec)
    return records


def round_step_donation_report(state_abs, hlo_text: str, mem, chips: int) -> dict:
    """GSPMD-aliasing evidence for the donated round/superstep plans
    (ROADMAP open item).

    Both plans donate the TrainState, so the sync-state buffers — outer
    params AND the outer-transform (pseudogradient chain) state — must come
    back via input/output aliasing, not fresh allocations. Two checks:

    * per-chip accounting: ``memory_analysis().alias_size_in_bytes`` (a
      per-device number) covers at least the outer params+opt shard;
    * the HLO ``input_output_alias`` map contains the ``outer_opt`` entry
      parameters (jit flattens the donated TrainState field-by-field, so the
      outer-transform state occupies a contiguous leaf-index range right
      after ``outer_params``). The check is byte-weighted: through the
      superstep's scan-over-R while loop XLA legitimately declines to alias
      O(kB) vector buffers (norm scales), so up to 1% of the outer-state
      bytes may escape aliasing — the parameter-sized buffers donation
      exists for must all alias.

    The report is **per-buffer**: every outer-params / outer-opt leaf is
    listed by its tree path with its bytes and aliasing verdict, so the
    escaped bytes are attributed to named buffers (``unaliased_buffers``)
    rather than a byte total.
    """
    import re

    def named_leaves(tree, start: int) -> list[dict]:
        flat, _ = jax.tree_util.tree_flatten_with_path(tree)
        return [{
            "param_index": start + i,
            "path": jax.tree_util.keystr(path),
            "bytes": int(leaf.size * leaf.dtype.itemsize),
        } for i, (path, leaf) in enumerate(flat)]

    param_entries = named_leaves(state_abs["outer_params"], 0)
    opt_entries = named_leaves(state_abs["outer_opt"], len(param_entries))
    aliased = {int(g) for g in re.findall(
        r"\((\d+), \{[^}]*\}, \w+-alias\)", hlo_text)}
    for e in param_entries + opt_entries:
        e["aliased"] = e["param_index"] in aliased
    outer_opt_bytes = tree_bytes(state_abs["outer_opt"])
    outer_param_bytes = tree_bytes(state_abs["outer_params"])
    unaliased_opt_bytes = sum(e["bytes"] for e in opt_entries if not e["aliased"])
    alias = int(mem.alias_size_in_bytes)
    return {
        "alias_bytes_per_chip": alias,
        "outer_opt_bytes_global": int(outer_opt_bytes),
        "outer_params_bytes_global": int(outer_param_bytes),
        "outer_opt_unaliased_bytes": int(unaliased_opt_bytes),
        "aliased_param_count": len(aliased),
        "outer_opt_param_indices": [e["param_index"] for e in opt_entries],
        "buffers": param_entries + opt_entries,
        "unaliased_buffers": [
            {"path": e["path"], "bytes": e["bytes"]}
            for e in param_entries + opt_entries if not e["aliased"]],
        "outer_state_aliased": bool(
            unaliased_opt_bytes <= 0.01 * max(outer_opt_bytes, 1)
            and alias * chips >= (outer_opt_bytes + outer_param_bytes
                                  - 2 * unaliased_opt_bytes)),
    }


def _analytic_terms(plan, cfg, params_abs, chips: int, shape: str) -> tuple[float, float]:
    """Per-chip (flops, hbm_bytes) from the closed-form models (flops.py)."""
    from repro.configs import INPUT_SHAPES

    spec = INPUT_SHAPES[shape]
    kind = plan.meta["kind"]
    pbytes = tree_bytes(params_abs)
    act_elt = 2.0  # bf16 activations
    d_ff_active = cfg.d_ff * (cfg.experts_per_token + cfg.n_shared_experts) if cfg.n_experts else cfg.d_ff
    per_tok_layer = (8.0 * cfg.d_model + 2.0 * d_ff_active) * act_elt

    if kind in ("train", "round", "superstep"):
        dcfg = plan.meta["dcfg"]
        sf = train_step_flops(cfg, spec.seq_len, spec.global_batch, params_abs, dcfg.inner_name)
        # optimizer state per chip: m (+v for adamw / embeds)
        state_abs = plan.args[0]
        opt_bytes = tree_bytes(state_abs["inner_state"])
        act_bytes = spec.global_batch * spec.seq_len * cfg.n_layers * per_tok_layer
        # each worker's params are fully sharded within its pod (chips/K chips)
        chips_per_worker = chips / max(dcfg.n_workers, 1)
        total_bytes = hbm_bytes("train", param_bytes_chip=pbytes / chips_per_worker,
                                opt_state_bytes_chip=opt_bytes / chips,
                                act_bytes_chip=act_bytes / chips)
        if kind in ("round", "superstep"):
            # the fused round = H inner steps + one sync (elementwise terms);
            # a superstep is R such rounds in one dispatch
            H = dcfg.sync_interval
            R = plan.meta.get("rounds_per_dispatch", 1)
            n = tree_count_params(params_abs)
            sync_flops = 10.0 * n * 3.0
            sync_bytes = hbm_bytes("sync", param_bytes_chip=pbytes / chips * 4.0,
                                   opt_state_bytes_chip=tree_bytes(state_abs["outer_opt"]) / chips,
                                   act_bytes_chip=0.0)
            return (R * (sf.total * H + sync_flops) / chips,
                    R * (total_bytes * H + sync_bytes))
        return sf.total / chips, total_bytes
    if kind == "sync":
        state_abs = plan.args[0]
        n = tree_count_params(params_abs)
        flops = 10.0 * n * 3.0  # EF/compress + nesterov + reset, elementwise
        total_bytes = hbm_bytes("sync", param_bytes_chip=pbytes / chips * 4.0,
                                opt_state_bytes_chip=tree_bytes(state_abs["outer_opt"]) / chips,
                                act_bytes_chip=0.0)
        return flops / chips, total_bytes
    if kind == "prefill":
        f = forward_flops(cfg, spec.seq_len, spec.global_batch)
        act_bytes = spec.global_batch * spec.seq_len * cfg.n_layers * per_tok_layer
        total_bytes = hbm_bytes("prefill", param_bytes_chip=pbytes / chips,
                                opt_state_bytes_chip=0.0, act_bytes_chip=act_bytes / chips)
        return f / chips, total_bytes
    # decode
    f = forward_flops(cfg, spec.seq_len, spec.global_batch, T=1, kv_len=spec.seq_len)
    cache_bytes = tree_bytes(plan.args[1])
    act_bytes = spec.global_batch * cfg.n_layers * per_tok_layer
    total_bytes = hbm_bytes("decode", param_bytes_chip=pbytes / chips,
                            opt_state_bytes_chip=0.0, act_bytes_chip=act_bytes / chips,
                            cache_bytes_chip=cache_bytes / chips)
    return f / chips, total_bytes


def _print_record(rec: dict) -> None:
    if rec["status"] == "skipped":
        print(f"[SKIP] {rec['arch']} x {rec['shape']} ({rec['mesh']}): {rec['reason']}")
        return
    if rec["status"] == "error":
        print(f"[FAIL] {rec['arch']} x {rec['shape']} {rec['plan']} ({rec['mesh']}): {rec['error']}")
        return
    r = rec["roofline"]
    print(
        f"[ OK ] {rec['arch']:22s} {rec['shape']:12s} {rec['plan']:12s} {rec['mesh']:8s} "
        f"compile={rec['compile_s']:6.1f}s peak/chip={rec['memory']['peak_per_chip_gib']:8.3f}GiB "
        f"C={r['compute_s']:.3e}s M={r['memory_s']:.3e}s X={r['collective_s']:.3e}s "
        f"dom={r['dominant']:10s} useful={r['useful_flops_ratio']:.2f}"
    )


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=list(ASSIGNED_ARCHS) + ["paper-416m", "paper-15.23b"])
    ap.add_argument("--shape", default=None, choices=list(INPUT_SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true", help="every arch x shape")
    ap.add_argument("--plan", default=None, help="only this plan (train_step/sync_step/...)")
    from repro.optim import INNER_OPTIMIZERS

    ap.add_argument("--inner", default="muon", choices=list(INNER_OPTIMIZERS))
    ap.add_argument("--rounds-per-dispatch", type=int, default=4,
                    help="R of the superstep plan (rounds per dispatch)")
    ap.add_argument("--compression", default="none",
                    choices=["none", "topk", "quant"],
                    help="pseudogradient wire format for the train plans "
                         "(lowered via the jnp wire path; the comm block "
                         "records measured vs modeled bytes)")
    ap.add_argument("--bits", type=int, default=4)
    ap.add_argument("--topk-frac", type=float, default=0.01)
    ap.add_argument("--attn-impl", default="xla",
                    choices=["auto", "xla", "pallas"],
                    help="attention backend for the lowered plans ('auto' "
                         "resolves as the model's attention_path does); "
                         "'pallas' shard_maps the fused kernel over the mesh "
                         "(batch x kv-heads -> 'data' x 'model'), so it "
                         "lowers on the 512-device world too")
    ap.add_argument("--ns-impl", default="jnp", choices=["jnp", "pallas"],
                    help="Newton-Schulz backend for the Muon inner steps; "
                         "'pallas' shard_maps the matrix stack over 'data'")
    ap.add_argument("--outer-kernel", action="store_true",
                    help="route the outer Nesterov descent through the fused "
                         "Pallas update kernel, shard_mapped over the flat "
                         "('pod','data','model') element axis")
    ap.add_argument("--wire-impl", default="jnp", choices=["jnp", "pallas"],
                    help="quantize/dequantize backend for the wire stages; "
                         "'pallas' shard_maps the row axis over "
                         "('pod','data')")
    ap.add_argument("--straggler-sigma", type=float, default=0.25,
                    help="lognormal sigma of the per-worker latency "
                         "multiplier in the straggler_wallclock evidence "
                         "block (p50/p99 round wall-clock at K=16)")
    ap.add_argument("--straggler-drop", type=float, default=0.0,
                    help="per-(round, worker) drop probability in the "
                         "straggler_wallclock evidence block (dropped "
                         "workers leave the round's slowest-worker max)")
    ap.add_argument("--autotune", default="on", choices=["on", "off"],
                    help="consult the committed kernel autotune table when "
                         "resolving block sizes ('off' restores the raw "
                         "constants); the resolution lands in every record's "
                         "kernels.autotune evidence block")
    ap.add_argument("--autotune-table", default=None,
                    help="path of the autotune JSON table (default: the "
                         "committed src/repro/kernels/autotune_table.json)")
    ap.add_argument("--out", default="results/dryrun")
    return ap


def main() -> None:
    args = build_parser().parse_args()
    from repro.kernels.autotune import configure

    configure(enabled=args.autotune == "on", table_path=args.autotune_table)

    archs = list(ASSIGNED_ARCHS) if (args.all or not args.arch) else [args.arch]
    shapes = list(INPUT_SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = [False, True] if (args.both_meshes or args.all) else [args.multi_pod]

    os.makedirs(args.out, exist_ok=True)
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                tag = f"{arch}__{shape}__{'2x16x16' if mp else '16x16'}__{args.inner}"
                if args.compression == "quant":
                    tag += f"__quant{args.bits}"
                elif args.compression == "topk":
                    tag += f"__topk{args.topk_frac}"
                kern_bits = []
                if args.attn_impl != "xla":
                    kern_bits.append(f"attn-{args.attn_impl}")
                if args.ns_impl != "jnp":
                    kern_bits.append(f"ns-{args.ns_impl}")
                if args.outer_kernel:
                    kern_bits.append("outerk")
                if args.wire_impl != "jnp":
                    kern_bits.append(f"wire-{args.wire_impl}")
                if kern_bits:
                    tag += "__" + "-".join(kern_bits)
                path = os.path.join(args.out, tag + ".json")
                if os.path.exists(path):
                    print(f"[CACHED] {tag}")
                    continue
                recs = run_one(arch, shape, mp, plan_filter=args.plan,
                               inner_name=args.inner,
                               rounds_per_dispatch=args.rounds_per_dispatch,
                               compression=args.compression, bits=args.bits,
                               topk_frac=args.topk_frac,
                               attn_impl=args.attn_impl, ns_impl=args.ns_impl,
                               outer_kernel=args.outer_kernel,
                               wire_impl=args.wire_impl,
                               straggler_sigma=args.straggler_sigma,
                               straggler_drop=args.straggler_drop)
                with open(path, "w") as f:
                    json.dump(recs, f, indent=2)


if __name__ == "__main__":
    main()
