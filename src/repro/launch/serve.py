"""Serving driver: paged-KV continuous batching or the dense-cache baseline.

Two engines (``--engine``):

* ``paged`` — ``repro.serving.PagedEngine``: fixed pool of KV pages
  (``--max-pages`` x ``--page-size``), continuous batching over
  ``--slots`` batch slots, single-dispatch batched prefill, and decode
  spans of ``--decode-steps-per-dispatch`` tokens per donated jitted
  call. Dense/MoE attention families only.
* ``naive`` — the seed's lockstep dense-cache loop (kept as the
  benchmark baseline), upgraded with batched prefill and with request
  ``context`` threaded into the cache. Serves every family, including
  recurrent-state (ssm/hybrid) and cross-attention (audio/vlm) models.

On CPU this serves reduced configs (examples/serve_batched.py); the same
driver lowers to the production mesh for the real deployment.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, reduce_config
from repro.models import build_model
from repro.serving import PagedEngine, Request, naive_generate


def generate(model, params, prompts: jax.Array, max_new: int, temperature: float = 0.0,
             context: jax.Array | None = None, rng: jax.Array | None = None,
             batched_prefill: bool = True):
    """prompts: [B, P] int32 -> tokens [B, P + max_new] (dense-cache path).

    Kept as the stable entry point; now delegates to
    :func:`repro.serving.naive_generate`, which threads ``context`` into
    the cache (the previous version dropped it — audio/VLM decode ran
    unconditioned) and prefills attention families in one dispatch.
    """
    return naive_generate(model, params, prompts, max_new,
                          temperature=temperature, context=context, rng=rng,
                          batched_prefill=batched_prefill)


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4,
                    help="number of requests (paged: admitted across --slots)")
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--engine", choices=["naive", "paged"], default="paged",
                    help="paged: continuous batching over the KV page pool; "
                         "naive: lockstep dense-cache baseline")
    ap.add_argument("--page-size", type=int, default=16,
                    help="KV slots per page (paged engine)")
    ap.add_argument("--max-pages", type=int, default=128,
                    help="total pages in the pool, incl. reserved null page 0")
    ap.add_argument("--decode-steps-per-dispatch", type=int, default=8,
                    help="tokens decoded per jitted dispatch (lax.scan span)")
    ap.add_argument("--slots", type=int, default=4,
                    help="concurrent batch slots of the paged engine")
    ap.add_argument("--attn-impl", default="xla", choices=["xla", "pallas"],
                    help="decode attention backend: 'xla' or 'pallas' (fused "
                         "paged-decode kernel; shard_mapped over the mesh "
                         "when the engine is built with one)")
    return ap


def serve(args) -> dict:
    """Serve ``args.batch`` seeded random prompts to completion.

    Returns ``{"tokens": {rid: np.ndarray[max_new]}, "n_new": int}``; the
    dense-cache ``naive`` engine reports its batch under rids ``req0..``."""
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduce_config(cfg)
    cfg = cfg.replace(attn_impl=args.attn_impl)
    model = build_model(cfg)
    rng = jax.random.PRNGKey(0)
    params = model.init(rng)
    prompts = jax.random.randint(rng, (args.batch, args.prompt_len), 0, cfg.vocab)
    ctx = None
    if cfg.arch_type == "audio":
        ctx = jnp.zeros((args.batch, cfg.n_audio_frames, cfg.d_model))
    if cfg.arch_type == "vlm":
        ctx = jnp.zeros((args.batch, cfg.n_image_tokens, cfg.d_model))

    t0 = time.time()
    if args.engine == "paged":
        engine = PagedEngine(
            model, params, slots=args.slots, page_size=args.page_size,
            max_pages=args.max_pages,
            decode_steps_per_dispatch=args.decode_steps_per_dispatch,
            temperature=args.temperature, attn_impl=args.attn_impl, rng=rng)
        reqs = [Request(f"req{i}", tuple(int(t) for t in row), args.max_new)
                for i, row in enumerate(jax.device_get(prompts))]
        results = engine.run(reqs)
    else:
        toks = generate(model, params, prompts, args.max_new,
                        temperature=args.temperature, context=ctx, rng=rng)
        new = np.asarray(toks[:, args.prompt_len:])
        results = {f"req{i}": row for i, row in enumerate(new)}
    dt = time.time() - t0
    n_new = args.batch * args.max_new
    print(f"[{args.engine}] generated {n_new} tokens in {dt:.2f}s "
          f"({n_new/dt:.1f} tok/s)")
    print("sample:", results["req0"][:8].tolist())
    return {"tokens": results, "n_new": n_new}


def main() -> None:
    from repro.launch.compile_cache import use_compilation_cache

    use_compilation_cache()
    serve(build_parser().parse_args())


if __name__ == "__main__":
    main()
