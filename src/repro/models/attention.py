"""Grouped-query attention with RoPE, QK-norm, sliding-window and KV caches.

Three entry points:
  * ``attend``            — full-sequence (training / prefill)
  * ``attend_decode``     — one new token against a [B, S, KV, hd] cache
  * ``cross_attend``      — encoder-decoder / VLM cross attention

Caches are plain dicts so they shard like any other pytree:
  full cache:   {"k": [B, S, KV, hd], "v": ..., "pos": i32[]}
  ring cache:   same but S == sliding window; slot = pos % window (used for
                long-context decode so dense archs stay sub-quadratic).
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro import tracing
from repro.kernels.backend import pallas_interpret
from repro.models.common import ModelConfig, apply_rope, dense_init, rms_norm, shard_hint

PyTree = Any
NEG_INF = -2.0e38


def init_attention(key, cfg: ModelConfig, n_layers: int | None = None, cross: bool = False) -> PyTree:
    """Attention params; stacked over n_layers when given (leading L axis)."""
    d, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    L = (n_layers,) if n_layers else ()
    ks = jax.random.split(key, 4)
    pd = cfg.pdtype
    params = {
        "wq": dense_init(ks[0], (*L, d, H * hd), fan_in=d, dtype=pd),
        "wk": dense_init(ks[1], (*L, d, KV * hd), fan_in=d, dtype=pd),
        "wv": dense_init(ks[2], (*L, d, KV * hd), fan_in=d, dtype=pd),
        "wo": dense_init(ks[3], (*L, H * hd, d), fan_in=H * hd, dtype=pd),
    }
    if cfg.qk_norm:
        params["q_norm_scale"] = jnp.zeros((*L, hd), pd)
        params["k_norm_scale"] = jnp.zeros((*L, hd), pd)
    if cross:
        params["gate"] = jnp.zeros((*L,), pd)  # llama-3.2-vision tanh gate
    return params


def _project_qkv(p: PyTree, cfg: ModelConfig, x: jax.Array, kv_x: jax.Array):
    """Project to q [B,S,H,hd], k/v [B,Skv,KV,hd] with optional QK-norm."""
    B, S, _ = x.shape
    Skv = kv_x.shape[1]
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    dt = cfg.compute_dtype
    q = (x @ p["wq"].astype(dt)).reshape(B, S, H, hd)
    k = (kv_x @ p["wk"].astype(dt)).reshape(B, Skv, KV, hd)
    v = (kv_x @ p["wv"].astype(dt)).reshape(B, Skv, KV, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm_scale"])
        k = rms_norm(k, p["k_norm_scale"])
    return q, k, v


def _gqa_scores(q: jax.Array, k: jax.Array) -> jax.Array:
    """q [B,Sq,H,hd] x k [B,Sk,KV,hd] -> scores [B,KV,G,Sq,Sk] with G=H/KV."""
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    qg = q.reshape(B, Sq, KV, G, hd)
    s = jnp.einsum("bqkgh,bskh->bkgqs", qg, k) / jnp.sqrt(jnp.asarray(hd, jnp.float32)).astype(q.dtype)
    return s


def _gqa_out(probs: jax.Array, v: jax.Array) -> jax.Array:
    """probs [B,KV,G,Sq,Sk] x v [B,Sk,KV,hd] -> [B,Sq,H*hd]."""
    B, KV, G, Sq, Sk = probs.shape
    hd = v.shape[-1]
    o = jnp.einsum("bkgqs,bskh->bqkgh", probs, v)
    return o.reshape(B, Sq, KV * G * hd)


# default for ModelConfig.blockwise_threshold (kept as a module constant for
# external callers; the config field is what `attend` consults)
BLOCKWISE_THRESHOLD = 4096
# Shortest sequence at which attn_impl='auto' takes the flash kernel on a
# TPU. Below it XLA's dense softmax was as fast or faster (fwd+bwd of one
# smollm-135m layer's attention, bf16, on a v5e; docs/architecture.md).
FLASH_MIN_SEQ = 1024


def attention_path(cfg: ModelConfig, S: int) -> str:
    """The path ``attend`` runs for a full sequence of length S:
    ``'pallas'`` (the flash kernel), ``'blockwise'`` or ``'dense'`` (XLA).

    ``attn_impl='auto'`` takes the flash kernel where it runs compiled (a
    TPU, where ``pallas_interpret()`` is False) at S >= FLASH_MIN_SEQ with
    S a multiple of 128 (so its blocks, clamped to divide S, stay whole
    128-row tiles), and the XLA paths otherwise; ``'pallas'`` and ``'xla'``
    force their path.
    """
    impl = cfg.attn_impl
    if impl == "auto":
        flash = not pallas_interpret() and S >= FLASH_MIN_SEQ and S % 128 == 0
        impl = "pallas" if flash else "xla"
    if impl == "pallas":
        return "pallas"
    return "blockwise" if S >= cfg.blockwise_threshold else "dense"


def attend(p: PyTree, cfg: ModelConfig, x: jax.Array, positions: jax.Array,
           causal: bool = True, return_kv: bool = False):
    """Full-sequence self-attention (training / prefill).

    Backend dispatch (``cfg.attn_impl``, resolved by :func:`attention_path`;
    ``'auto'`` picks one of the two below by backend and sequence length):

    * ``'pallas'`` — the fused flash-attention kernel
      (:func:`repro.kernels.flash_attention.gqa_flash_attention`): GQA-native
      blocked online softmax with full-block skipping and a flash-style
      custom VJP. Interpret mode off-TPU. On a mesh the StepPlan machinery
      routes the call through shard_map (batch x kv-heads over
      'data' x 'model', see :func:`repro.launch.sharding.kernel_specs`), so
      'pallas' lowers on multi-device worlds too.
    * ``'xla'`` (default) — dense O(S^2) softmax below
      ``cfg.blockwise_threshold``; above it, a blockwise online-softmax
      recurrence (lax.scan over kv blocks) that never materializes the
      score matrix and skips out-of-schedule blocks
      (:func:`repro.kernels.flash_attention.visited_kv_range`). Exact,
      differentiable, O(S * block) memory.

    Both non-dense paths assume rows attend by absolute position
    (``positions == arange(S)``, the training/prefill layout). The core,
    between the projections, runs under the device scope
    ``repro.attention``.

    ``return_kv=True`` additionally returns the post-RoPE ``(k, v)``
    projections ([B, S, KV, hd] each) — exactly what ``attend_decode``
    writes into its cache per token, so a single batched prefill forward
    can populate a KV cache at every prompt position at once (the serving
    prefill path).
    """
    q, k, v = _project_qkv(p, cfg, x, x)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    q = shard_hint(q, "attn_kv")
    k = shard_hint(k, "attn_kv")
    v = shard_hint(v, "attn_kv")
    with jax.named_scope(tracing.ATTENTION):
        o = _attention_core(cfg, q, k, v, positions, causal, x.dtype)
    out = o @ p["wo"].astype(cfg.compute_dtype)
    if return_kv:
        return out, (k, v)
    return out


def _attention_core(cfg: ModelConfig, q: jax.Array, k: jax.Array, v: jax.Array,
                    positions: jax.Array, causal: bool, probs_dtype) -> jax.Array:
    """q [B,S,H,hd], k/v [B,S,KV,hd] -> o [B,S,H*hd] on :func:`attention_path`
    (the dense path rounds its probabilities to ``probs_dtype``)."""
    B, S = q.shape[:2]
    path = attention_path(cfg, S)
    if path == "pallas":
        from repro.kernels.flash_attention import gqa_flash_attention

        o = gqa_flash_attention(
            q, k, v, causal=causal,
            window=cfg.sliding_window if causal else 0,
            block_q=cfg.attn_block_q, block_kv=cfg.attn_block_kv)
        return o.reshape(B, S, -1)
    if path == "blockwise":
        o = _blockwise_attention(cfg, q, k, v, causal=causal,
                                 block_q=cfg.attn_block_q,
                                 block_kv=cfg.attn_block_kv)
        return o.reshape(B, S, -1)
    scores = _gqa_scores(q, k).astype(jnp.float32)  # [B,KV,G,S,S]
    if causal:
        i = positions if positions.ndim == 1 else positions[0]
        mask = i[:, None] >= i[None, :]
        if cfg.sliding_window:
            mask &= i[:, None] - i[None, :] < cfg.sliding_window
        scores = jnp.where(mask[None, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(probs_dtype)
    probs = shard_hint(probs, "attn_probs")
    return _gqa_out(probs, v)


def _blockwise_attention(cfg: ModelConfig, q: jax.Array, k: jax.Array, v: jax.Array,
                         causal: bool, block_q: int = 512, block_kv: int = 1024,
                         skip_blocks: bool = True) -> jax.Array:
    """Exact attention via the online-softmax recurrence over KV blocks.

    q [B,S,H,hd], k/v [B,S,KV,hd] -> o [B,S,H,hd]. Memory per step is
    O(block_q * block_kv) instead of O(S^2). Each q block scans only its
    *visit schedule* — the contiguous kv-block range below the causal
    diagonal and inside the sliding window
    (:func:`repro.kernels.flash_attention.visited_kv_range`, the same
    schedule the Pallas kernel grids over) — so out-of-window and
    above-diagonal blocks are never computed. Skipping is bitwise-exact:
    a fully-masked block contributes exactly zero to (m, l, acc)
    (``skip_blocks=False`` forces the full sweep; pinned by
    tests/test_attention.py).
    """
    from repro.kernels.flash_attention import visited_kv_range

    B, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    bq = min(block_q, S)
    bkv = min(block_kv, S)
    nq, nkv = S // bq, S // bkv
    assert S % bq == 0 and S % bkv == 0
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    window = cfg.sliding_window if causal else 0

    qb = q.reshape(B, nq, bq, KV, G, hd)
    kb = k.reshape(B, nkv, bkv, KV, hd)
    vb = v.reshape(B, nkv, bkv, KV, hd)

    def make_q_block(qi: int, kj_lo: int, kj_hi: int):
        # qi and the kv range are static per q block (the schedule), so the
        # scan trip count is exactly the visited-block count.
        @jax.checkpoint  # backward recomputes the kv scan: O(block) residuals,
        def q_block(q_i):  # not O(S * block) saved probs per q block
            # q_i: [B, bq, KV, G, hd]
            q32 = q_i.astype(jnp.float32)

            def kv_step(carry, kj):
                m, l, acc = carry
                k_j = jax.lax.dynamic_index_in_dim(kb, kj, axis=1, keepdims=False)
                v_j = jax.lax.dynamic_index_in_dim(vb, kj, axis=1, keepdims=False)
                s = jnp.einsum("bqkgh,bskh->bkgqs", q32, k_j.astype(jnp.float32)) * scale
                rows = qi * bq + jnp.arange(bq)
                cols = kj * bkv + jnp.arange(bkv)
                mask = jnp.ones((bq, bkv), bool)
                if causal:
                    mask &= rows[:, None] >= cols[None, :]
                if window:  # sliding window only applies under causal,
                    mask &= rows[:, None] - cols[None, :] < window
                    # matching the dense and pallas paths (and the schedule)
                s = jnp.where(mask[None, None, None], s, NEG_INF)
                m_new = jnp.maximum(m, jnp.max(s, axis=-1))
                p = jnp.exp(s - m_new[..., None])
                corr = jnp.exp(m - m_new)
                l_new = l * corr + jnp.sum(p, axis=-1)
                acc_new = acc * corr[..., None] + jnp.einsum(
                    "bkgqs,bskh->bkgqh", p, v_j.astype(jnp.float32))
                return (m_new, l_new, acc_new), None

            m0 = jnp.full((B, KV, G, bq), NEG_INF, jnp.float32)
            l0 = jnp.zeros((B, KV, G, bq), jnp.float32)
            a0 = jnp.zeros((B, KV, G, bq, hd), jnp.float32)
            (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0),
                                          jnp.arange(kj_lo, kj_hi))
            out = acc / jnp.maximum(l, 1e-30)[..., None]  # [B,KV,G,bq,hd]
            return jnp.moveaxis(out, 3, 1)  # [B,bq,KV,G,hd]

        return q_block

    outs = []
    for qi in range(nq):
        lo, hi = ((0, nkv) if not skip_blocks else
                  visited_kv_range(qi, nkv, bq, bkv, causal, window))
        outs.append(make_q_block(qi, lo, hi)(qb[:, qi]))
    # outs: [nq, B, bq, KV, G, hd] -> [B, S, H, hd]
    o = jnp.moveaxis(jnp.stack(outs), 0, 1).reshape(B, S, KV, G, hd).astype(q.dtype)
    return o.reshape(B, S, H, hd)


def init_cache(cfg: ModelConfig, batch: int, cache_len: int, n_layers: int, dtype=None) -> PyTree:
    dt = dtype or cfg.compute_dtype
    KV, hd = cfg.n_kv_heads, cfg.hd
    return {
        "k": jnp.zeros((n_layers, batch, cache_len, KV, hd), dt),
        "v": jnp.zeros((n_layers, batch, cache_len, KV, hd), dt),
    }


def fill_cache_from_prefill(k: jax.Array, v: jax.Array, cache_layer: PyTree) -> PyTree:
    """Write full-seq prefill K/V into the (larger) cache buffers."""
    ck = jax.lax.dynamic_update_slice(cache_layer["k"], k, (0, 0, 0, 0))
    cv = jax.lax.dynamic_update_slice(cache_layer["v"], v, (0, 0, 0, 0))
    return {"k": ck, "v": cv}


def attend_decode(p: PyTree, cfg: ModelConfig, x: jax.Array, cache_layer: PyTree,
                  pos: jax.Array) -> tuple[jax.Array, PyTree]:
    """Decode one token. x: [B, 1, d]; cache k/v: [B, W, KV, hd]; pos: i32[].

    With ``cfg.sliding_window`` the cache is a ring buffer of size W=window
    (slot = pos % W) so long-context decode memory is O(window), the
    sub-quadratic variant used for the 500k-token shape. Without it, the
    cache holds absolute positions (W >= seq_len).
    """
    B = x.shape[0]
    q, k_new, v_new = _project_qkv(p, cfg, x, x)
    posb = jnp.full((B, 1), pos, jnp.int32)
    q = apply_rope(q, posb, cfg.rope_theta)
    k_new = apply_rope(k_new, posb, cfg.rope_theta)

    W = cache_layer["k"].shape[1]
    slot = pos % W if cfg.sliding_window else pos
    ck = jax.lax.dynamic_update_slice(cache_layer["k"], k_new, (0, slot, 0, 0))
    cv = jax.lax.dynamic_update_slice(cache_layer["v"], v_new, (0, slot, 0, 0))

    scores = _gqa_scores(q, ck).astype(jnp.float32)  # [B,KV,G,1,W]
    idx = jnp.arange(W)
    if cfg.sliding_window:
        # slot s currently holds absolute position p(s): the largest p <= pos
        # with p % W == s.
        slot_pos = pos - ((pos - idx) % W)
        valid = (slot_pos >= 0) & (slot_pos > pos - W)
    else:
        valid = idx <= pos
    scores = jnp.where(valid[None, None, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    o = _gqa_out(probs, cv)
    out = o @ p["wo"].astype(cfg.compute_dtype)
    return out, {"k": ck, "v": cv}


def init_paged_cache(cfg: ModelConfig, n_pages: int, page_size: int,
                     n_layers: int, dtype=None) -> PyTree:
    """Paged KV pool: ``n_pages`` fixed-size pages shared by all sequences.

    Layout ``[L, n_pages, page_size, KV, hd]`` — the layer axis leads so the
    decode scan threads one ``[n_pages, page_size, KV, hd]`` pool per layer,
    mirroring :func:`init_cache`'s ``[L, B, S, KV, hd]``. Page 0 is reserved
    as the null/garbage page (see ``repro.serving.paging``).
    """
    dt = dtype or cfg.compute_dtype
    KV, hd = cfg.n_kv_heads, cfg.hd
    return {
        "k": jnp.zeros((n_layers, n_pages, page_size, KV, hd), dt),
        "v": jnp.zeros((n_layers, n_pages, page_size, KV, hd), dt),
    }


def paged_attend_decode(p: PyTree, cfg: ModelConfig, x: jax.Array,
                        cache_layer: PyTree, page_table: jax.Array,
                        lengths: jax.Array, impl: str = "xla") -> tuple[jax.Array, PyTree]:
    """Decode one token per slot against a paged KV cache (one layer).

    x ``[B, 1, d]``; cache k/v ``[n_pages, page_size, KV, hd]``;
    ``page_table`` ``[B, max_pages]`` int32 (0-padded; page 0 is the null
    page); ``lengths`` ``[B]`` int32 — slot b's new token sits at position
    ``lengths[b]`` (so, unlike :func:`attend_decode`, every slot has its own
    position: continuous batching never runs in lockstep). Writes the new
    K/V into each slot's current page, then attends over the slot's own
    pages via :func:`repro.kernels.flash_attention.paged_decode_attention`.
    """
    B = x.shape[0]
    ps = cache_layer["k"].shape[1]
    max_pages = page_table.shape[1]
    q, k_new, v_new = _project_qkv(p, cfg, x, x)
    posb = lengths[:, None].astype(jnp.int32)  # [B, 1] per-slot positions
    q = apply_rope(q, posb, cfg.rope_theta)
    k_new = apply_rope(k_new, posb, cfg.rope_theta)

    # page/slot of the new token; the min() clamp keeps slots that decode
    # past their allocation (finished requests padding out a span) writing
    # into the null page instead of reading out of bounds
    page_of = jnp.minimum(lengths // ps, max_pages - 1)
    page_ids = jnp.take_along_axis(page_table, page_of[:, None], axis=1)[:, 0]
    slot = lengths % ps
    ck = cache_layer["k"].at[page_ids, slot].set(k_new[:, 0])
    cv = cache_layer["v"].at[page_ids, slot].set(v_new[:, 0])

    from repro.kernels.flash_attention import paged_decode_attention

    o = paged_decode_attention(q[:, 0], ck, cv, page_table, lengths + 1,
                               window=cfg.sliding_window, impl=impl)
    out = o.reshape(B, 1, -1) @ p["wo"].astype(cfg.compute_dtype)
    return out, {"k": ck, "v": cv}


def fill_paged_cache(cache_layer: PyTree, k: jax.Array, v: jax.Array,
                     page_table: jax.Array, lengths: jax.Array) -> PyTree:
    """Scatter batched-prefill K/V ([B, P, KV, hd]) into pages.

    Position t of slot b lands in page ``page_table[b, t // ps]`` at slot
    ``t % ps``; positions at or past ``lengths[b]`` (prompt padding) are
    redirected to the null page 0.
    """
    B, P = k.shape[:2]
    ps = cache_layer["k"].shape[1]
    max_pages = page_table.shape[1]
    pos = jnp.arange(P)[None, :]  # [1, P]
    page_of = jnp.minimum(pos // ps, max_pages - 1)
    page_ids = jnp.take_along_axis(page_table, page_of.repeat(B, 0), axis=1)
    page_ids = jnp.where(pos < lengths[:, None], page_ids, 0)  # [B, P]
    slot = (pos % ps).repeat(B, 0)
    ck = cache_layer["k"].at[page_ids.reshape(-1), slot.reshape(-1)].set(
        k.reshape(B * P, *k.shape[2:]))
    cv = cache_layer["v"].at[page_ids.reshape(-1), slot.reshape(-1)].set(
        v.reshape(B * P, *v.shape[2:]))
    return {"k": ck, "v": cv}


def cross_attend(p: PyTree, cfg: ModelConfig, x: jax.Array, kv: jax.Array | tuple,
                 gated: bool = False) -> jax.Array:
    """Cross attention to a context. kv: context states [B, Sk, d] or a
    precomputed (k, v) pair ([B, Sk, KV, hd] each) for cached decoding."""
    dt = cfg.compute_dtype
    B, Sq, _ = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = (x @ p["wq"].astype(dt)).reshape(B, Sq, H, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm_scale"])
    if isinstance(kv, tuple):
        k, v = kv
    else:
        Sk = kv.shape[1]
        k = (kv @ p["wk"].astype(dt)).reshape(B, Sk, KV, hd)
        v = (kv @ p["wv"].astype(dt)).reshape(B, Sk, KV, hd)
        if cfg.qk_norm:
            k = rms_norm(k, p["k_norm_scale"])
    scores = _gqa_scores(q, k).astype(jnp.float32)
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    o = _gqa_out(probs, v)
    out = o @ p["wo"].astype(dt)
    if gated:
        out = jnp.tanh(p["gate"].astype(jnp.float32)).astype(dt) * out
    return out


def cross_kv(p: PyTree, cfg: ModelConfig, context: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Precompute cross-attention K/V once per request (decode path)."""
    dt = cfg.compute_dtype
    B, Sk, _ = context.shape
    KV, hd = cfg.n_kv_heads, cfg.hd
    k = (context @ p["wk"].astype(dt)).reshape(B, Sk, KV, hd)
    v = (context @ p["wv"].astype(dt)).reshape(B, Sk, KV, hd)
    if cfg.qk_norm:
        k = rms_norm(k, p["k_norm_scale"])
    return k, v
