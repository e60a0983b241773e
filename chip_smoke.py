#!/usr/bin/env python3
"""Smoke run of MuLoCo training and paged serving on a TPU.

    python chip_smoke.py                # phases (a)-(d) on one chip
    python chip_smoke.py --four-chips   # K=4 workers one per chip vs vmapped

Every phase goes through the normal entry points in this one process
(``repro.launch.train.train`` built from its ``build_parser``, and
``repro.launch.serve.serve``) at the full published width of smollm-135m:
30 layers, d_model 576, vocab 49152, random weights from a seed.

  (a) Muon inner, K=4 workers vmapped on the chip, H=4, 2 rounds, 4
      sequences of 2048 per worker (the default ``--attn-impl auto``, which
      is the flash kernel at 2048 on a TPU; jnp Newton-Schulz);
  (b) the same run with the AdamW inner optimizer;
  (c) every training kernel compiled by Mosaic: 4-bit wire quantization
      with error feedback, Pallas flash attention, Pallas Newton-Schulz and
      the fused outer update; each kernel, and Mamba2's fused SSD, is also
      checked against its jnp oracle (``repro.kernels.ref``, and
      ``ssm.ssd_intra_chunk_xla`` for the SSD) on a small input;
  (d) paged serving with the Pallas paged-decode kernel, a handful of
      requests, and the kernel's own oracle check.

Each phase prints one JSON line: its name, the XLA backend-compile seconds
inside it, the host seconds per round (phase wall clock less compile, over
the rounds: it includes initialization and data generation), and the
losses, which must be finite. ``--four-chips`` runs only the four-chip
comparison: phase (a)'s configuration with the workers placed one per chip
(``--mesh 4x1x1``) against the same seed vmapped on one chip.

The last line of stdout is ``{"ok": true, "device": {...}}``. The script
exits non-zero and prints no such line when JAX finds no TPU, when it is
not run from a checkout of the repository, or when any phase fails.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")

TRAIN = ["--arch", "smollm-135m", "--workers", "4", "--sync-interval", "4",
         "--rounds", "2", "--seq-len", "2048", "--batch-per-worker", "4"]
PHASES = {
    "a_muon": [],
    "b_adamw": ["--inner", "adamw"],
    "c_all_kernels": ["--compression", "quant", "--bits", "4",
                      "--error-feedback", "--attn-impl", "pallas",
                      "--ns-impl", "pallas", "--outer-kernel"],
}
SERVE = ["--arch", "smollm-135m", "--engine", "paged", "--attn-impl", "pallas",
         "--batch", "4", "--prompt-len", "16", "--max-new", "32"]

# Kernel vs oracle, as max|got - want| / max|want|. The kernels run on bf16
# operands (the model's compute dtype) and the MXU may take f32 products in
# bf16 passes: each rounding is worth at most 2^-8 of the largest magnitude,
# and 2^-5 leaves room for several of them to add up. A wrong mask, layout
# or block index moves the result by O(1).
KERNEL_TOL = 2.0 ** -5
# The four-chip run and the one-chip run execute the same arithmetic but
# partition it differently, so XLA rounds their bf16 activations and orders
# their f32 reductions differently. One bf16 rounding is worth 2^-8 of a
# value; the losses of both runs agree when they are within 2^-7 relative,
# two such roundings, of each other.
FOUR_CHIP_TOL = 2.0 ** -7


class CompileClock:
    """Sums XLA backend-compile seconds reported by JAX while open, and
    counts persistent-cache hits and misses (a warm cache skips the
    backend compile)."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    CACHE = {"/jax/compilation_cache/cache_hits": "hits",
             "/jax/compilation_cache/cache_misses": "misses"}

    def __enter__(self):
        import jax

        self.seconds = 0.0
        self.cache = {"hits": 0, "misses": 0}
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def _on_duration(self, event, duration, **_):
        if event == self.EVENT:
            self.seconds += duration

    def _on_event(self, event, **_):
        if event in self.CACHE:
            self.cache[self.CACHE[event]] += 1

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)
        return False


def _rel_err(got, want) -> float:
    import numpy as np

    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-30))


def _finite(xs) -> bool:
    return bool(xs) and all(math.isfinite(x) for x in xs)


def train_kernel_errors() -> dict:
    """Every training kernel against its jnp oracle on a small input, with
    smollm-135m's head layout (9 query heads over 3 KV heads of 64)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops, ref
    from repro.kernels.flash_attention import gqa_flash_attention
    from repro.optim.muon import NS_COEFFS

    k0, k1, k2 = jax.random.split(jax.random.PRNGKey(0), 3)
    errs = {}
    q = jax.random.normal(k0, (2, 256, 9, 64), jnp.bfloat16)
    k = jax.random.normal(k1, (2, 256, 3, 64), jnp.bfloat16)
    v = jax.random.normal(k2, (2, 256, 3, 64), jnp.bfloat16)

    def loss(fn):
        return jax.jit(jax.value_and_grad(
            lambda q, k, v: jnp.sum(jnp.sin(fn(q, k, v).astype(jnp.float32))),
            argnums=(0, 1, 2)))

    def flash(q, k, v):
        return gqa_flash_attention(q, k, v, block_q=64, block_kv=128)

    def oracle(q, k, v):
        return ref.gqa_attention_ref(q.astype(jnp.float32), k.astype(jnp.float32),
                                     v.astype(jnp.float32))

    with jax.default_matmul_precision("highest"):
        want_o = jax.jit(oracle)(q, k, v)
        _, want_g = loss(oracle)(q, k, v)
    errs["flash_fwd"] = _rel_err(jax.jit(flash)(q, k, v), want_o)
    _, got_g = loss(flash)(q, k, v)
    for name, got, want in zip(("dq", "dk", "dv"), got_g, want_g):
        errs[f"flash_{name}"] = _rel_err(got, want)

    x = jax.random.normal(k0, (64, 1536), jnp.float32) * 3
    deq, codes, lo, scale = ops.quantize_rowwise(x, bits=4)
    deq_r, codes_r, _, _ = ref.rowwise_quantize_ref(x, 4)
    # a value on a rounding tie may land one level apart from the oracle;
    # more than 0.2% of such codes counts as a failure
    mismatch = float(np.mean(np.asarray(codes) != np.asarray(codes_r)))
    errs["quantize"] = _rel_err(deq, deq_r) if mismatch < 2e-3 else 1.0
    errs["dequantize"] = _rel_err(ops.dequantize_rowwise(codes, lo, scale),
                                  ref.rowwise_dequantize_ref(codes, lo, scale))

    # one Newton-Schulz product with its fused epilogue (B @ X + a * X) on
    # the MLP matrix; the five chained iterations amplify bf16-pass rounding
    # past KERNEL_TOL, so the oracle check is per product
    b_, x_ = (jax.random.normal(kk, shape, jnp.float32) / 24
              for kk, shape in ((k1, (576, 576)), (k2, (576, 1536))))
    a_coef = NS_COEFFS[0]
    with jax.default_matmul_precision("highest"):
        want_mm = jax.jit(lambda b, x: ref.matmul_epilogue_ref(
            b, x, x, alpha=1.0, beta=a_coef))(b_, x_)
    errs["ns_matmul"] = _rel_err(ops.matmul(b_, x_, x_, alpha=1.0, beta=a_coef),
                                 want_mm)

    t, p, u = (jax.random.normal(kk, (576, 1536), jnp.float32) for kk in (k0, k1, k2))
    got = ops.nesterov_update(t, p, u, lr=0.7, momentum=0.9)
    want = ref.nesterov_update_ref(t, p, u, lr=0.7, momentum=0.9)
    errs["outer_update"] = max(_rel_err(a, b) for a, b in zip(got, want))
    errs.update(ssd_errors())
    return errs


def ssd_errors() -> dict:
    """The fused intra-chunk SSD kernel, forward and VJP, against its jnp
    expression (``ssm.ssd_intra_chunk_xla``) in float32 at mamba2-370m's
    chunk 256, head dim 64 and state 128, with 16 heads (two grid steps of
    heads per chunk, so the backward's sums over steps are checked)."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.ssd import ssd_intra_chunk
    from repro.models.ssm import ssd_intra_chunk_xla

    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    B, C, Q, H, P, N = 1, 2, 256, 16, 64, 128
    xc, Bc, Cc = (jax.random.normal(kk, shape, jnp.bfloat16).astype(jnp.float32)
                  for kk, shape in ((ks[0], (B, C, Q, H, P)), (ks[1], (B, C, Q, N)),
                                    (ks[2], (B, C, Q, N))))
    dt = jax.nn.softplus(jax.random.normal(ks[3], (B, C, Q, H)) - 3.0)
    dA = dt * -jnp.linspace(1.0, 16.0, H)
    dy = jax.random.normal(ks[4], (B, C, Q, H, P))

    def kernel(xc, dt, Bc, Cc, dA):
        return ssd_intra_chunk(xc, dt, Bc, Cc, jnp.cumsum(dA, axis=2))

    def fwd_bwd(fn):
        def run(*args):
            y, vjp = jax.vjp(fn, *args)
            return y, vjp(dy)
        return jax.jit(run)

    args = (xc, dt, Bc, Cc, dA)
    with jax.default_matmul_precision("highest"):
        want_y, want_g = fwd_bwd(ssd_intra_chunk_xla)(*args)
    got_y, got_g = fwd_bwd(kernel)(*args)
    errs = {"ssd_fwd": _rel_err(got_y, want_y)}
    for name, got, want in zip(("dx", "ddt", "dB", "dC", "ddA"), got_g, want_g):
        errs[f"ssd_{name}"] = _rel_err(got, want)
    return errs


def paged_decode_error() -> float:
    """The paged-decode kernel against its dense oracle on a ragged table."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ref
    from repro.kernels.flash_attention import paged_decode_attention

    k0, k1, k2 = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(k0, (4, 9, 64), jnp.bfloat16)
    kp = jax.random.normal(k1, (16, 16, 3, 64), jnp.bfloat16)
    vp = jax.random.normal(k2, (16, 16, 3, 64), jnp.bfloat16)
    tbl = jnp.array([[1, 2, 0], [3, 0, 0], [4, 5, 6], [7, 0, 0]], jnp.int32)
    lens = jnp.array([20, 5, 41, 16], jnp.int32)
    got = jax.jit(lambda *a: paged_decode_attention(*a, impl="pallas"))(
        q, kp, vp, tbl, lens)
    with jax.default_matmul_precision("highest"):
        want = ref.paged_attention_ref(q.astype(jnp.float32), kp, vp, tbl, lens)
    return _rel_err(got, want)


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: {what}")


def run_train(name: str, argv: list[str]) -> dict:
    """One training run through the CLI entry point; metrics only (the
    returned state is dropped so the next run has the device memory)."""
    from repro.launch.train import build_parser, train

    args = build_parser().parse_args(argv)
    with CompileClock() as clock:
        res = train(args)
    out = {
        "phase": name,
        "compile_s": clock.seconds,
        "compile_cache": clock.cache,
        "train_loss": [h["train_loss"] for h in res["history"]],
        "eval_loss": list(res["losses"]),
        "_state": res["state"],
    }
    del res
    return out


def train_phase(name: str, extra: list[str], out_root: str,
                size: tuple[str, ...] = ()) -> dict:
    argv = TRAIN + extra + list(size) + ["--out", os.path.join(out_root, name)]
    rec = run_train(name, argv)
    del rec["_state"]
    gc.collect()
    rounds = int(argv[argv.index("--rounds") + 1])
    if name == "c_all_kernels":
        rec["kernel_err"] = train_kernel_errors()
    print(json.dumps(rec), flush=True)
    for key in ("train_loss", "eval_loss"):
        _check(len(rec[key]) == rounds and _finite(rec[key]),
               f"{name}: {key} not {rounds} finite values: {rec[key]}")
    for kernel, err in rec.get("kernel_err", {}).items():
        _check(err <= KERNEL_TOL, f"{name}: {kernel} off its oracle by {err}")
    return rec


def serve_phase(size: tuple[str, ...] = ()) -> dict:
    from repro.configs import get_config
    from repro.launch.serve import build_parser, serve

    args = build_parser().parse_args(SERVE + list(size))
    with CompileClock() as clock:
        t0 = time.perf_counter()
        res = serve(args)
        wall = time.perf_counter() - t0
    vocab = get_config(args.arch).vocab
    toks = res["tokens"]
    rec = {
        "phase": "d_serve_paged",
        "compile_s": clock.seconds,
        "compile_cache": clock.cache,
        "s_per_token": (wall - clock.seconds) / res["n_new"],
        "requests": len(toks),
        "tokens": res["n_new"],
        "kernel_err": {"paged_decode": paged_decode_error()},
    }
    print(json.dumps(rec), flush=True)
    _check(len(toks) == args.batch, f"served {len(toks)} of {args.batch} requests")
    for rid, t in toks.items():
        _check(t.shape == (args.max_new,) and bool(((t >= 0) & (t < vocab)).all()),
               f"{rid}: tokens {t} not {args.max_new} ids in [0, {vocab})")
    _check(rec["kernel_err"]["paged_decode"] <= KERNEL_TOL,
           f"paged decode off its oracle by {rec['kernel_err']['paged_decode']}")
    return rec


def four_chip_compare(out_root: str, size: tuple[str, ...] = ()) -> dict:
    """Phase (a) with K=4 workers one per chip against the same seed and
    config with the four workers vmapped on one chip."""
    import jax

    argv = TRAIN + list(size)
    spread = run_train("four_chips", argv + [
        "--mesh", "4x1x1", "--out", os.path.join(out_root, "four_chips")])
    # every worker's parameters live on their own chip: the worker axis of
    # each leaf is split four ways, one slice per device
    placed = []
    for leaf in jax.tree.leaves(spread.pop("_state")["worker_params"]):
        shards = leaf.addressable_shards
        placed.append(len({s.device for s in shards}) == 4
                      and all(s.data.shape[0] == 1 for s in shards))
    gc.collect()
    one = run_train("one_chip", argv + [
        "--out", os.path.join(out_root, "one_chip")])
    on_one = {d for leaf in jax.tree.leaves(one.pop("_state")["worker_params"])
              for d in leaf.sharding.device_set}
    gc.collect()
    diffs = {
        key: [abs(a - b) / abs(b) for a, b in zip(spread[key], one[key])]
        for key in ("train_loss", "eval_loss")
    }
    rec = {"phase": "four_chips_vs_one_chip", "tol": FOUR_CHIP_TOL,
           "rel_diff": diffs, "four_chips": spread, "one_chip": one}
    print(json.dumps(rec), flush=True)
    _check(all(placed), "worker parameters are not one worker per device")
    _check(len(on_one) == 1, f"the one-chip run spans devices {on_one}")
    for key in ("train_loss", "eval_loss"):
        _check(_finite(spread[key]) and _finite(one[key])
               and len(spread[key]) == len(one[key]),
               f"{key}: non-finite or missing values")
        _check(max(diffs[key]) <= FOUR_CHIP_TOL,
               f"{key}: four-chip and one-chip runs differ by {diffs[key]}")
    return rec


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the K=4 one-worker-per-chip comparison "
                         "(needs four devices)")
    ap.add_argument("--out", default=os.path.join(ROOT, "results", "chip_smoke"),
                    help="directory for the runs' metrics.csv files")
    return ap


def main() -> int:
    args = build_parser().parse_args()
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke: no src/repro beside {__file__}; run it from a "
              f"checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)
    import jax

    from repro.launch.compile_cache import use_compilation_cache

    if jax.default_backend() != "tpu":
        print(f"chip_smoke: JAX found no TPU (backend: {jax.default_backend()})",
              file=sys.stderr)
        return 1
    use_compilation_cache()
    devices = jax.devices()
    if args.four_chips:
        _check(len(devices) >= 4, f"--four-chips needs 4 devices, found {len(devices)}")
        four_chip_compare(args.out)
    else:
        for name, extra in PHASES.items():
            train_phase(name, extra, args.out)
        serve_phase()
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
