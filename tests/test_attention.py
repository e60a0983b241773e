"""Attention backend equivalences: dense XLA <-> blockwise XLA <-> Pallas
flash kernel (interpret mode), forward AND gradients, plus grid-level proofs
that block skipping visits the schedule bound and changes nothing."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_attention import (
    attention_schedule,
    clamp_block,
    gqa_flash_attention,
    visited_fraction,
    visited_kv_range,
)
from repro.models import ModelConfig, attention as A

CASES = [
    # (causal, window, H, KV)  — GQA G>1, MQA-ish, MHA, sliding-window
    (True, 0, 4, 2),
    (True, 12, 4, 2),
    (True, 0, 4, 4),
    (True, 8, 4, 1),
    (False, 0, 4, 2),
]


def _qkv(S, H, KV, hd, seed=0):
    key = jax.random.PRNGKey(seed)
    q = jax.random.normal(key, (2, S, H, hd), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(key, 1), (2, S, KV, hd), jnp.float32)
    v = jax.random.normal(jax.random.fold_in(key, 2), (2, S, KV, hd), jnp.float32)
    return q, k, v


@pytest.mark.parametrize("causal,window,H,KV", CASES)
def test_flash_matches_dense_ref_forward_and_grad(causal, window, H, KV):
    """Pallas kernel == jitted jnp oracle to fp32 tolerance, fwd + grads
    (value_and_grad drives the custom VJP's dq/dk/dv kernels)."""
    S, hd = 48, 16
    q, k, v = _qkv(S, H, KV, hd)
    flash = functools.partial(gqa_flash_attention, causal=causal,
                              window=window, block_q=16, block_kv=8)
    oracle = functools.partial(ref.gqa_attention_ref, causal=causal,
                               window=window)
    out = jax.jit(flash)(q, k, v)
    exp = jax.jit(oracle)(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=2e-5, atol=2e-5)

    def loss(fn):
        # sin() makes the cotangent vary per element (catches transposed
        # or mis-scaled backward terms a sum() cotangent would hide)
        return jax.jit(jax.value_and_grad(
            lambda q, k, v: jnp.sum(jnp.sin(fn(q, k, v))), argnums=(0, 1, 2)))

    lv, g = loss(flash)(q, k, v)
    le, ge = loss(oracle)(q, k, v)
    assert abs(float(lv) - float(le)) < 1e-4
    for got, exp_g in zip(g, ge):
        np.testing.assert_allclose(np.asarray(got), np.asarray(exp_g),
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("causal,window", [(True, 0), (True, 12), (False, 0),
                                           (False, 12)])
def test_flash_matches_xla_blockwise(causal, window):
    """dense <-> XLA blockwise <-> Pallas: all three agree on one input —
    including causal=False with a sliding-window config, where all paths
    must agree the window only applies under causal masking."""
    S, H, KV, hd = 64, 4, 2, 16
    q, k, v = _qkv(S, H, KV, hd)
    cfg = ModelConfig(n_heads=H, n_kv_heads=KV, d_model=H * hd, head_dim=hd,
                      dtype="float32", qk_norm=False, sliding_window=window)
    blocked = jax.jit(lambda q, k, v: A._blockwise_attention(
        cfg, q, k, v, causal=causal, block_q=16, block_kv=16))(q, k, v)
    flash = jax.jit(lambda q, k, v: gqa_flash_attention(
        q, k, v, causal=causal, window=window if causal else 0,
        block_q=16, block_kv=16))(q, k, v)
    dense = jax.jit(lambda q, k, v: ref.gqa_attention_ref(
        q, k, v, causal=causal, window=window if causal else 0))(q, k, v)
    np.testing.assert_allclose(np.asarray(blocked), np.asarray(dense),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(flash), np.asarray(dense),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("window", [0, 16])
def test_attend_pallas_equals_xla(window):
    """The full attend() path (projections, RoPE, QK-norm) dispatched through
    attn_impl='pallas' matches the XLA paths, fwd + param/input grads."""
    S = 32
    base = ModelConfig(n_heads=4, n_kv_heads=2, d_model=64, head_dim=16,
                      d_ff=64, vocab=64, dtype="float32", qk_norm=True,
                      sliding_window=window, attn_block_q=8, attn_block_kv=8)
    p = A.init_attention(jax.random.PRNGKey(0), base)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, S, 64), jnp.float32)
    pos = jnp.arange(S)

    def run(cfg):
        fwd = jax.jit(lambda p, x: A.attend(p, cfg, x, pos))
        val, grads = jax.jit(jax.value_and_grad(
            lambda p, x: jnp.sum(jnp.sin(A.attend(p, cfg, x, pos))),
            argnums=(0, 1)))(p, x)
        return fwd(p, x), val, grads

    o_x, l_x, g_x = run(base)  # dense (S < threshold)
    o_b, l_b, g_b = run(base.replace(blockwise_threshold=S))  # blockwise
    o_p, l_p, g_p = run(base.replace(attn_impl="pallas"))  # flash kernel
    for o, lv, g in [(o_b, l_b, g_b), (o_p, l_p, g_p)]:
        np.testing.assert_allclose(np.asarray(o), np.asarray(o_x),
                                   rtol=3e-5, atol=3e-5)
        assert abs(float(lv) - float(l_x)) < 1e-4
        for got, exp in zip(jax.tree.leaves(g), jax.tree.leaves(g_x)):
            np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                                       rtol=5e-4, atol=5e-4)


def test_stacked_layer_lm_loss_and_grads_match():
    """Whole-model equivalence: a 2-layer scan-over-layers LM trained through
    attn_impl='pallas' (value_and_grad through the custom VJP inside vmap +
    scan + remat) matches attn_impl='xla' loss and gradients."""
    from repro.models import build_model

    cfg = ModelConfig(arch_type="dense", n_layers=2, d_model=32, n_heads=4,
                      n_kv_heads=2, d_ff=64, vocab=64, dtype="float32",
                      qk_norm=True, remat=True, attn_block_q=8,
                      attn_block_kv=8)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 17), 0, 64)
    batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}

    outs = {}
    for impl in ("xla", "pallas"):
        model = build_model(cfg.replace(attn_impl=impl))
        params = model.init(jax.random.PRNGKey(0))
        (loss, _), grads = jax.jit(jax.value_and_grad(
            model.loss, has_aux=True))(params, batch)
        outs[impl] = (float(loss), grads)
    assert abs(outs["xla"][0] - outs["pallas"][0]) < 1e-5
    for a, b in zip(jax.tree.leaves(outs["xla"][1]),
                    jax.tree.leaves(outs["pallas"][1])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-4)


@pytest.mark.parametrize("S", [16, 256, 1024, 2048, 4096, 8192])
def test_auto_takes_xla_off_tpu(S):
    """Off the TPU 'auto' is 'xla' at every length (dense below the
    blockwise threshold), so CPU numerics do not move."""
    cfg = ModelConfig(attn_impl="auto")
    want = "blockwise" if S >= cfg.blockwise_threshold else "dense"
    assert A.attention_path(cfg, S) == want
    assert A.attention_path(cfg.replace(attn_impl="xla"), S) == want


def test_auto_takes_flash_on_tpu_from_the_crossover(monkeypatch):
    """Where kernels compile (pallas_interpret() False) 'auto' takes the
    flash kernel from FLASH_MIN_SEQ (the smollm cell's 2048 among them) and
    the dense path below; 'xla' and 'pallas' still force their path."""
    monkeypatch.setattr(A, "pallas_interpret", lambda: False)
    cfg = ModelConfig(attn_impl="auto")
    assert A.FLASH_MIN_SEQ <= 2048
    for S in (2048, A.FLASH_MIN_SEQ, 8192):
        assert A.attention_path(cfg, S) == "pallas"
    assert A.attention_path(cfg, A.FLASH_MIN_SEQ // 2) == "dense"
    assert A.attention_path(cfg, 1500) == "dense"  # blocks would clamp to 4
    assert A.attention_path(cfg.replace(attn_impl="xla"), 2048) == "dense"
    assert A.attention_path(cfg.replace(attn_impl="pallas"), 64) == "pallas"


def test_bf16_flash_is_no_further_from_f32_than_dense():
    """bf16 operands on the MXU with f32 scores and accumulators: the
    kernel's output and grads lie no further from the float32 oracle (norm
    of the error) than the dense XLA path's, which rounds its scores to
    bf16."""
    S, H, KV, hd = 128, 6, 2, 64
    q, k, v = (x.astype(jnp.bfloat16) for x in _qkv(S, H, KV, hd))
    base = ModelConfig(n_heads=H, n_kv_heads=KV, d_model=H * hd, head_dim=hd,
                       dtype="bfloat16", attn_block_q=32, attn_block_kv=64)
    pos = jnp.arange(S)

    def core(impl):
        cfg = base.replace(attn_impl=impl)
        return lambda q, k, v: A._attention_core(cfg, q, k, v, pos, True,
                                                 jnp.bfloat16)

    def oracle(q, k, v):
        return ref.gqa_attention_ref(*(x.astype(jnp.float32) for x in (q, k, v))
                                     ).reshape(2, S, -1)

    def out_and_grads(fn):
        o = jax.jit(fn)(q, k, v)
        g = jax.jit(jax.grad(lambda q, k, v: jnp.sum(jnp.sin(
            fn(q, k, v).astype(jnp.float32))), argnums=(0, 1, 2)))(q, k, v)
        return [np.asarray(x, np.float32) for x in (o, *g)]

    with jax.default_matmul_precision("highest"):
        want = out_and_grads(oracle)
    flash, dense = out_and_grads(core("pallas")), out_and_grads(core("xla"))
    for name, w, f, d in zip(("o", "dq", "dk", "dv"), want, flash, dense):
        err_f, err_d = np.linalg.norm(f - w), np.linalg.norm(d - w)
        assert err_f <= err_d, (name, err_f, err_d)


# ---------------------------------------------------------------------------
# Block skipping: proofs on the grid itself, and skipped == unskipped
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("S,bq,bkv", [(64, 8, 8), (64, 8, 16), (128, 16, 16),
                                      (128, 32, 16), (256, 64, 64)])
def test_causal_schedule_visits_at_most_half_plus_diagonal(S, bq, bkv):
    """The causal grid provably runs <= nq*nkv/2 + nq kv-blocks — asserted
    on the schedule the kernel grids over, not on timing."""
    nq, nkv = S // bq, S // bkv
    sched = attention_schedule(nq, nkv, bq, bkv, causal=True, window=0)
    assert len(sched) <= nq * nkv // 2 + nq
    # and it is exactly the brute-force visited set
    def visited(qi, kj):
        rows = np.arange(qi * bq, (qi + 1) * bq)
        cols = np.arange(kj * bkv, (kj + 1) * bkv)
        return bool((rows[:, None] >= cols[None, :]).any())
    brute = [(qi, kj) for qi in range(nq) for kj in range(nkv)
             if visited(qi, kj)]
    assert sched == brute


@pytest.mark.parametrize("S,window", [(128, 16), (128, 32), (256, 32)])
def test_window_schedule_is_o_window_over_s(S, window):
    """Sliding-window schedules visit O(window/S) of the grid: each q block
    scans a contiguous range of at most window/bkv + 2 kv blocks."""
    bq = bkv = 16
    nq, nkv = S // bq, S // bkv
    per_q = [visited_kv_range(qi, nkv, bq, bkv, True, window)
             for qi in range(nq)]
    assert all(hi - lo <= window // bkv + 2 for lo, hi in per_q)
    assert visited_fraction(S, bq, bkv, True, window) <= (window / S) + 3 * bkv / S
    # the q-major schedule is exactly the concatenation of the ranges
    sched = attention_schedule(nq, nkv, bq, bkv, True, window)
    flat = [(qi, kj) for qi, (lo, hi) in enumerate(per_q)
            for kj in range(lo, hi)]
    assert sched == flat


@pytest.mark.parametrize("causal,window", [(True, 0), (True, 12)])
def test_block_skipping_is_exact(causal, window):
    """Skipped blocks change nothing: skip vs full-sweep grids are bitwise
    identical, for the Pallas kernel AND the XLA blockwise fallback."""
    S, H, KV, hd = 64, 4, 2, 8
    q, k, v = _qkv(S, H, KV, hd)
    f_skip = jax.jit(lambda q, k, v: gqa_flash_attention(
        q, k, v, causal=causal, window=window, block_q=8, block_kv=8,
        skip_blocks=True))(q, k, v)
    f_full = jax.jit(lambda q, k, v: gqa_flash_attention(
        q, k, v, causal=causal, window=window, block_q=8, block_kv=8,
        skip_blocks=False))(q, k, v)
    np.testing.assert_array_equal(np.asarray(f_skip), np.asarray(f_full))

    cfg = ModelConfig(n_heads=H, n_kv_heads=KV, d_model=H * hd, head_dim=hd,
                      dtype="float32", qk_norm=False, sliding_window=window)
    b_skip = jax.jit(lambda q, k, v: A._blockwise_attention(
        cfg, q, k, v, causal=causal, block_q=8, block_kv=8,
        skip_blocks=True))(q, k, v)
    b_full = jax.jit(lambda q, k, v: A._blockwise_attention(
        cfg, q, k, v, causal=causal, block_q=8, block_kv=8,
        skip_blocks=False))(q, k, v)
    np.testing.assert_array_equal(np.asarray(b_skip), np.asarray(b_full))


def test_block_clamping_divides_any_sequence():
    for S in (16, 48, 96, 4096):
        for b in (512, 1024, 7):
            assert S % clamp_block(b, S) == 0
            assert clamp_block(b, S) <= max(b, 1)


def test_visited_fraction_causal_is_about_half():
    f = visited_fraction(4096, 512, 1024, causal=True, window=0)
    assert 0.5 < f <= 0.5 + 1024 / 4096 + 1e-9
    assert visited_fraction(4096, 512, 1024, causal=False, window=0) == 1.0
