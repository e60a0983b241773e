"""Serving engine tests: page-allocator invariants, paged-vs-dense decode
equality (incl. GQA + sliding window), continuous-batching lifecycle, and
the context-threading regression for cross-attention families.

The decode-equality tests are the serving analogue of
test_models.test_arch_decode_matches_forward: the paged path must
reproduce the dense-cache path bitwise (same dtype, same reduction
order in the XLA gather fallback), so greedy token streams are pinned
identical, not just allclose.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduce_config
from repro.models import build_model
from repro.serving import (OutOfPages, PageAllocator, PagedEngine, Request,
                           naive_generate, pages_needed)


# ---------------------------------------------------------------- allocator

def test_allocator_no_double_allocation():
    a = PageAllocator(n_pages=8, page_size=4)
    seen = set(a.alloc("a", 3))
    more = a.alloc("b", 4)
    assert not seen & set(more)
    assert 0 not in seen | set(more)  # null page never handed out
    assert a.n_free == 0


def test_allocator_release_returns_pages():
    a = PageAllocator(n_pages=8, page_size=4)
    a.alloc("a", 3)
    a.alloc("b", 2)
    assert a.n_free == 2
    assert a.release("a") == 3
    assert a.n_free == 5
    assert a.pages_for("a") == []
    # released pages are reusable
    assert len(a.alloc("c", 5)) == 5


def test_allocator_out_of_pages_raises():
    a = PageAllocator(n_pages=4, page_size=4)
    a.alloc("a", 2)
    with pytest.raises(OutOfPages):
        a.alloc("b", 2)
    # failed alloc must not leak pages
    assert a.n_free == 1
    assert a.can_admit(4) and not a.can_admit(5)


def test_allocator_ensure_grows_on_demand():
    a = PageAllocator(n_pages=8, page_size=4)
    a.alloc("a", 1)
    assert a.capacity("a") == 4
    assert a.ensure("a", 4) == []          # already covered
    assert len(a.ensure("a", 9)) == 2      # grow to 3 pages
    assert a.capacity("a") == 12
    assert pages_needed(9, 4) == 3


def test_allocator_page_table_layout():
    a = PageAllocator(n_pages=8, page_size=4)
    pages = a.alloc("a", 2)
    tbl = a.page_table(["a", None], max_pages=4)
    assert tbl.shape == (2, 4) and tbl.dtype == np.int32
    assert tbl[0, :2].tolist() == pages and tbl[0, 2:].tolist() == [0, 0]
    assert tbl[1].tolist() == [0, 0, 0, 0]  # empty slot -> all-null row


# ------------------------------------------------------- paged == dense

def _model(arch="smollm-135m", **overrides):
    cfg = reduce_config(get_config(arch))
    if overrides:
        cfg = cfg.replace(**overrides)
    model = build_model(cfg)
    return model, model.init(jax.random.PRNGKey(0))


def _paged_decode_tokens(model, params, prompts, max_new, impl="xla"):
    """Greedy-decode via paged prefill + per-token paged decode steps."""
    B, P = prompts.shape
    ps = 4
    alloc = PageAllocator(n_pages=1 + B * pages_needed(P + max_new, ps),
                          page_size=ps)
    for b in range(B):
        alloc.alloc(b, pages_needed(P + max_new, ps))
    tbl = jnp.asarray(alloc.page_table(range(B), pages_needed(P + max_new, ps)))
    cache = model.init_paged_cache(alloc.n_pages, ps)
    lens = jnp.full((B,), P, jnp.int32)
    logits, cache = jax.jit(model.paged_prefill)(params, cache, prompts, tbl,
                                                 lens)
    tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
    out = [tok]
    step = jax.jit(lambda p, c, t, l: model.paged_decode_step(p, c, t, tbl, l,
                                                              impl=impl))
    for t in range(max_new - 1):
        logits, cache = step(params, cache, tok, lens + t)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        out.append(tok)
    return np.stack([np.asarray(t) for t in out], axis=1), logits


@pytest.mark.parametrize("window", [0, 6])
def test_paged_decode_matches_dense(window):
    """Paged prefill+decode pins the dense-cache greedy stream exactly —
    GQA (reduced smollm is 4 q-heads : 1 kv-head) with and without a
    sliding window."""
    model, params = _model(sliding_window=window)
    prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 7), 0,
                                 model.cfg.vocab)
    dense = np.asarray(naive_generate(model, params, prompts, 6))[:, 7:]
    paged, logits = _paged_decode_tokens(model, params, prompts, 6)
    np.testing.assert_array_equal(paged, dense)
    assert np.all(np.isfinite(np.asarray(logits)))


def test_paged_decode_logits_match_dense_exactly():
    """Per-step logits (not just argmax) are bitwise equal to the dense
    decode path for positions inside the window."""
    model, params = _model()
    B, P, N = 2, 5, 4
    prompts = jax.random.randint(jax.random.PRNGKey(2), (B, P), 0,
                                 model.cfg.vocab)
    # dense reference
    cache = model.init_cache(params, B, P + N)
    logits, cache = jax.jit(model.prefill_with_cache)(params, cache, prompts)
    tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
    dense_steps = []
    for t in range(N - 1):
        lg, cache = jax.jit(model.decode_step)(params, cache, tok,
                                               jnp.int32(P + t))
        dense_steps.append(np.asarray(lg))
        tok = jnp.argmax(lg, -1).astype(jnp.int32)
    # paged path
    _, _ = _paged_decode_tokens(model, params, prompts, N)  # smoke
    ps = 4
    alloc = PageAllocator(n_pages=1 + B * pages_needed(P + N, ps), page_size=ps)
    for b in range(B):
        alloc.alloc(b, pages_needed(P + N, ps))
    tbl = jnp.asarray(alloc.page_table(range(B), pages_needed(P + N, ps)))
    pcache = model.init_paged_cache(alloc.n_pages, ps)
    lens = jnp.full((B,), P, jnp.int32)
    lg, pcache = jax.jit(model.paged_prefill)(params, pcache, prompts, tbl, lens)
    tok = jnp.argmax(lg[:, -1], -1).astype(jnp.int32)
    for t in range(N - 1):
        lg, pcache = jax.jit(model.paged_decode_step)(params, pcache, tok, tbl,
                                                      lens + t)
        np.testing.assert_array_equal(np.asarray(lg), dense_steps[t])
        tok = jnp.argmax(lg, -1).astype(jnp.int32)


# ------------------------------------------ kernel vs oracle (both impls)

@pytest.mark.parametrize("window", [0, 5])
def test_paged_decode_attention_matches_oracle(window):
    """`paged_decode_attention` (xla gather fallback AND the Pallas
    scalar-prefetch kernel in interpret mode) against the dense jnp
    oracle, over ragged lengths, null-padded table rows, and GQA."""
    from repro.kernels.flash_attention import paged_decode_attention
    from repro.kernels.ref import paged_attention_ref

    B, H, KV, hd, ps, max_pages = 3, 4, 2, 8, 4, 4
    n_pool = 1 + B * max_pages
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (B, H, hd), jnp.float32)
    k_pages = jax.random.normal(ks[1], (n_pool, ps, KV, hd), jnp.float32)
    v_pages = jax.random.normal(ks[2], (n_pool, ps, KV, hd), jnp.float32)
    # ragged allocations: slot 0 owns 1 page, slot 1 owns 3, slot 2 all 4;
    # unowned tail entries point at the reserved null page 0
    alloc = PageAllocator(n_pages=n_pool, page_size=ps)
    for b, n in enumerate([1, 3, 4]):
        alloc.alloc(b, n)
    table = jnp.asarray(alloc.page_table(range(B), max_pages))
    lengths = jnp.asarray([2, 11, 16], jnp.int32)  # include current token

    ref = paged_attention_ref(q, k_pages, v_pages, table, lengths,
                              window=window)
    xla = paged_decode_attention(q, k_pages, v_pages, table, lengths,
                                 window=window, impl="xla")
    pal = paged_decode_attention(q, k_pages, v_pages, table, lengths,
                                 window=window, impl="pallas")
    assert np.all(np.isfinite(np.asarray(ref)))
    np.testing.assert_allclose(np.asarray(xla), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(pal), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


# ------------------------------------------------- continuous batching

def test_engine_matches_naive_batch():
    model, params = _model()
    prompts = jax.random.randint(jax.random.PRNGKey(3), (3, 6), 0,
                                 model.cfg.vocab)
    ref = np.asarray(naive_generate(model, params, prompts, 8))[:, 6:]
    eng = PagedEngine(model, params, slots=2, page_size=4, max_pages=32,
                      decode_steps_per_dispatch=3)
    reqs = [Request(f"r{i}", tuple(int(t) for t in row), 8)
            for i, row in enumerate(np.asarray(prompts))]
    out = eng.run(reqs)
    for i in range(3):
        np.testing.assert_array_equal(out[f"r{i}"], ref[i])


def test_engine_late_join_matches_solo():
    """A request admitted mid-flight (staggered arrivals, varying prompt
    lengths and max_new) produces exactly the tokens of a solo decode."""
    model, params = _model()
    prompts = [tuple(int(t) for t in np.asarray(jax.random.randint(
        jax.random.PRNGKey(10 + i), (L,), 0, model.cfg.vocab)))
        for i, L in enumerate([3, 9, 5])]
    eng = PagedEngine(model, params, slots=2, page_size=4, max_pages=32,
                      decode_steps_per_dispatch=2)
    reqs = [Request(f"s{i}", p, [7, 4, 9][i], arrival=[0, 1, 4][i])
            for i, p in enumerate(prompts)]
    out = eng.run(reqs)
    for i, p in enumerate(prompts):
        solo = np.asarray(naive_generate(
            model, params, jnp.asarray([p], jnp.int32), reqs[i].max_new))
        np.testing.assert_array_equal(out[f"s{i}"], solo[0, len(p):])


def test_engine_releases_pages_and_rejects_oversized():
    model, params = _model()
    eng = PagedEngine(model, params, slots=1, page_size=4, max_pages=8,
                      decode_steps_per_dispatch=2)
    # sequential requests through one slot: pool must be fully recycled
    reqs = [Request(f"q{i}", (1, 2, 3), 4) for i in range(3)]
    out = eng.run(reqs)
    assert sorted(out) == ["q0", "q1", "q2"]
    ref = out["q0"]
    for rid in ("q1", "q2"):
        np.testing.assert_array_equal(out[rid], ref)  # identical prompts
    # a request that can never fit raises instead of deadlocking
    big = Request("big", tuple(range(1, 40)), 8)
    with pytest.raises(OutOfPages):
        eng.run([big])


def test_engine_requires_paged_support():
    model, params = _model("mamba2-370m")
    with pytest.raises(ValueError, match="naive"):
        PagedEngine(model, params)


# --------------------------------------------- context threading regression

@pytest.mark.parametrize("arch", ["whisper-large-v3", "llama-3.2-vision-90b"])
def test_generate_threads_context(arch):
    """Regression: serve-path generate() must condition decode on the
    request context (the seed dropped it — audio/VLM decode ran
    unconditioned, so changing the context changed nothing)."""
    from repro.launch.serve import generate

    model, params = _model(arch)
    cfg = model.cfg
    if cfg.arch_type == "vlm":
        # open the Flamingo-style tanh gates (zero-init => cross path is
        # exactly zero at init and context could not influence logits)
        params["cross_layers"]["attn"]["gate"] = jnp.ones_like(
            params["cross_layers"]["attn"]["gate"])
        params["cross_layers"]["mlp_gate"] = jnp.ones_like(
            params["cross_layers"]["mlp_gate"])
        nctx = cfg.n_image_tokens
    else:
        nctx = cfg.n_audio_frames
    prompts = jax.random.randint(jax.random.PRNGKey(4), (2, 5), 0, cfg.vocab)
    ctx_a = jax.random.normal(jax.random.PRNGKey(1), (2, nctx, cfg.d_model))
    ctx_b = jax.random.normal(jax.random.PRNGKey(2), (2, nctx, cfg.d_model))
    out_a = np.asarray(generate(model, params, prompts, 6, context=ctx_a))
    out_a2 = np.asarray(generate(model, params, prompts, 6, context=ctx_a))
    out_b = np.asarray(generate(model, params, prompts, 6, context=ctx_b))
    np.testing.assert_array_equal(out_a, out_a2)      # deterministic
    assert not np.array_equal(out_a[:, 5:], out_b[:, 5:])


def test_naive_generate_batched_prefill_matches_stepped():
    """The single-dispatch batched prefill is a pure execution change:
    greedy streams match the token-stepped prefill exactly."""
    model, params = _model()
    prompts = jax.random.randint(jax.random.PRNGKey(6), (2, 9), 0,
                                 model.cfg.vocab)
    a = np.asarray(naive_generate(model, params, prompts, 5,
                                  batched_prefill=True))
    b = np.asarray(naive_generate(model, params, prompts, 5,
                                  batched_prefill=False))
    np.testing.assert_array_equal(a, b)
