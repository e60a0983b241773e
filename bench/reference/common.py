"""Plain float32 reference of the first DiLoCo/MuLoCo round of a cell.

Nothing here imports the program under test. From the seed alone it builds
the weights and the token stream the configuration states, then follows
round 0 step by step in float32 with every matrix product at
``Precision.HIGHEST``: K workers each take H inner steps (Muon on the hidden
matrices, AdamW on the rest, warm-up + cosine learning rate, decoupled weight
decay), the pseudogradient is the worker mean of outer - worker, the outer
Nesterov step updates the outer parameters, and the eval loss is read on the
synced parameters. With ``inner_optimizer`` "adamw" every leaf takes AdamW.
Where the traffic states a ``compression``, the pseudogradient goes through
the two quantization points of a quantized all-to-all reduce-scatter and
all-gather: each worker's delta (plus the decayed error-feedback residual,
zero in round 0) is quantized to ``bits`` linear levels between each row's
min and max and reconstructed, the reconstructions are averaged over the
workers, and the average is quantized and reconstructed once more. Each worker step runs as one jitted call, one sequence
block at a time where memory asks for it, so the whole round fits one chip
after the program's state is freed.

``Numerics("fp8")`` is the control: the same reference with every matrix
product's operands rounded to float8 e4m3 (per-tensor scale), the precision
step below the bfloat16 that the configurations compute in.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any
F32 = jnp.float32
NS_COEFFS = (3.4445, -4.7750, 2.0315)
FP8_MAX = 448.0  # largest finite float8_e4m3fn


class Numerics:
    """Matrix products of the reference: float32 at HIGHEST, or the fp8
    control (operands rounded to e4m3 with a per-tensor scale)."""

    def __init__(self, mode: str = "f32"):
        if mode not in ("f32", "fp8"):
            raise ValueError(f"unknown numerics {mode!r}")
        self.mode = mode

    def _round(self, x: jax.Array) -> jax.Array:
        if self.mode == "f32":
            return x
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
        scale = jax.lax.stop_gradient(scale)
        q = (x / scale).astype(jnp.float8_e4m3fn).astype(F32)
        # straight-through: the backward pass sees the rounded forward value
        return x + jax.lax.stop_gradient(q * scale - x)

    def dot(self, spec: str, a: jax.Array, b: jax.Array) -> jax.Array:
        return jnp.einsum(spec, self._round(a.astype(F32)), self._round(b.astype(F32)),
                          precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=F32)


# ---------------------------------------------------------------------------
# Inputs from the seed
# ---------------------------------------------------------------------------


def truncated(key, shape, fan_in: int) -> jax.Array:
    """Truncated normal on [-3, 3], scaled by 1/sqrt(fan_in)."""
    return jax.random.truncated_normal(key, -3.0, 3.0, shape, F32) * (1.0 / math.sqrt(fan_in))


class TokenStream:
    """First-order Markov token stream: a successor table of ``branching``
    ids per token (drawn once from a fixed numpy seed), Zipf(1.2) start
    tokens, and per-step, per-worker threefry keys folded from ``seed``."""

    BRANCHING = 8

    def __init__(self, vocab: int, seq_len: int, batch: int, workers: int, seed: int):
        rng = np.random.default_rng(1337)
        self.table = jnp.asarray(rng.integers(0, vocab, size=(vocab, self.BRANCHING),
                                              dtype=np.int32))
        zipf = 1.0 / (np.arange(1, vocab + 1) ** 1.2)
        self.start_logits = jnp.asarray(np.log(zipf / zipf.sum()), F32)
        self.seq_len, self.batch, self.workers, self.seed = seq_len, batch, workers, seed
        self._fn = jax.jit(self._tokens)

    def _sequences(self, key):
        k0, k1 = jax.random.split(key)
        first = jax.random.categorical(k0, self.start_logits, shape=(self.batch,))

        def walk(tok, k):
            nxt = self.table[tok, jax.random.randint(k, (self.batch,), 0, self.BRANCHING)]
            return nxt, tok

        _, toks = jax.lax.scan(walk, first, jax.random.split(k1, self.seq_len + 1))
        return toks.T.astype(jnp.int32)

    def _tokens(self, step):
        key = jax.random.fold_in(jax.random.PRNGKey(self.seed), step)
        return jax.vmap(self._sequences)(jax.random.split(key, self.workers))

    def step(self, step: int) -> tuple[jax.Array, jax.Array]:
        """(tokens, labels), each [workers, batch, seq_len]."""
        toks = self._fn(jnp.asarray(step, jnp.int32))
        return toks[..., :-1], toks[..., 1:]


# ---------------------------------------------------------------------------
# Optimizers
# ---------------------------------------------------------------------------


def cosine_lr(step: int, lr: float, total: int, warmup: int, min_ratio: float = 0.1) -> float:
    """Linear warm-up to ``lr``, then cosine decay to ``min_ratio * lr``."""
    if step < warmup:
        return lr * min(step / max(warmup, 1), 1.0)
    frac = min(max((step - max(warmup, 1)) / max(total - max(warmup, 1), 1), 0.0), 1.0)
    return lr * (min_ratio + (1.0 - min_ratio) * 0.5 * (1.0 + math.cos(math.pi * frac)))


def newton_schulz(nx: Numerics, g: jax.Array, iters: int = 5) -> jax.Array:
    """Quintic Newton-Schulz orthogonalization of each trailing [m, n]."""
    a, b, c = NS_COEFFS
    x = g.astype(F32)
    tall = x.shape[-2] > x.shape[-1]
    if tall:
        x = jnp.swapaxes(x, -1, -2)
    x = x / (jnp.sqrt(jnp.sum(x * x, axis=(-2, -1), keepdims=True)) + 1e-7)
    for _ in range(iters):
        A = nx.dot("...ij,...kj->...ik", x, x)
        B = b * A + c * nx.dot("...ij,...jk->...ik", A, A)
        x = a * x + nx.dot("...ij,...jk->...ik", B, x)
    return jnp.swapaxes(x, -1, -2) if tall else x


def inner_init(params: PyTree, is_muon: Callable[[str], bool]) -> dict:
    """Muon momentum for hidden matrices, Adam moments for the rest."""
    state = {}
    for path, p in flat(params).items():
        state[path] = {"m": jnp.zeros(p.shape, F32)}
        if not is_muon(path):
            state[path]["v"] = jnp.zeros(p.shape, F32)
    return state


def inner_update(nx: Numerics, params: dict, grads: dict, state: dict, step: int,
                 lr: float, opt: dict, is_muon: Callable[[str], bool]):
    """One inner optimizer step on flat {path: array} trees (``step`` from 1)."""
    b1, b2, eps, wd = opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"]
    new_p, new_s = {}, {}
    for path, p in params.items():
        g = grads[path]
        s = state[path]
        if is_muon(path):
            m = b1 * s["m"] + g
            m_rows, n_cols = p.shape[-2], p.shape[-1]
            u = newton_schulz(nx, m)
            new_p[path] = p - (lr * math.sqrt(n_cols / m_rows)) * u - (lr * wd) * p
            new_s[path] = {"m": m}
        else:
            m = b1 * s["m"] + (1.0 - b1) * g
            v = b2 * s["v"] + (1.0 - b2) * g * g
            u = (m / (1.0 - b1 ** step)) / (jnp.sqrt(v / (1.0 - b2 ** step)) + eps)
            new_p[path] = p - lr * u - (lr * wd) * p
            new_s[path] = {"m": m, "v": v}
    return new_p, new_s


def quantize_rows(x: jax.Array, bits: int, rowwise: bool) -> jax.Array:
    """Linear quantization and reconstruction: ``2**bits`` levels from each
    row's min to its max (rows: the last axis of a matrix when ``rowwise``,
    else the whole tensor), codes rounded half to even."""
    rows = x.reshape(-1, x.shape[-1]) if rowwise and x.ndim >= 2 else x.reshape(1, -1)
    lo = jnp.min(rows, axis=1, keepdims=True)
    scale = (jnp.max(rows, axis=1, keepdims=True) - lo) / ((1 << bits) - 1)
    scale = jnp.where(scale > 0, scale, 1.0)
    return (lo + jnp.round((rows - lo) / scale) * scale).reshape(x.shape)


def pseudogradient(outer: jax.Array, workers: list, comp: dict | None) -> jax.Array:
    """Mean over workers of outer - worker, through the wire if compressed."""
    deltas = [outer - w for w in workers]
    if not comp:
        return sum(deltas) / len(deltas)
    q = partial(quantize_rows, bits=comp["bits"], rowwise=comp["rowwise"])
    return q(sum(q(d) for d in deltas) / len(deltas))


# ---------------------------------------------------------------------------
# Trees
# ---------------------------------------------------------------------------


def flat(tree: PyTree, prefix: str = "") -> dict:
    """Nested dict -> {'a/b/c': leaf}."""
    out = {}
    for k, v in tree.items():
        path = f"{prefix}/{k}" if prefix else str(k)
        if isinstance(v, dict):
            out.update(flat(v, path))
        else:
            out[path] = v
    return out


def unflat(leaves: dict) -> dict:
    out: dict = {}
    for path, v in leaves.items():
        node = out
        *head, last = path.split("/")
        for k in head:
            node = node.setdefault(k, {})
        node[last] = v
    return out


def norms(leaves: dict) -> dict:
    return {k: float(jnp.sqrt(jnp.sum(jnp.square(v.astype(F32))))) for k, v in leaves.items()}


def cross_entropy(nx: Numerics, hidden: jax.Array, head: jax.Array, labels: jax.Array,
                  chunk: int = 512) -> jax.Array:
    """Summed next-token cross-entropy of ``hidden @ head`` over [S] rows,
    in blocks of ``chunk`` positions (the logits of one block at a time)."""
    S = hidden.shape[0]
    chunk = min(chunk, S)

    @jax.checkpoint
    def block(h, y):
        logits = nx.dot("sd,dv->sv", h, head)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
        return jnp.sum(lse - gold)

    total = jnp.float32(0.0)
    for i in range(0, S, chunk):
        total = total + block(hidden[i:i + chunk], labels[i:i + chunk])
    return total


def rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + scale)


# ---------------------------------------------------------------------------
# Round 0
# ---------------------------------------------------------------------------


def follow_round0(family, model: dict, traffic: dict, seed: int,
                  numerics: str = "f32", half_batch: bool = False) -> dict:
    """Round 0 of the cell from ``seed``: per-step mean train loss [H], the
    eval loss on the synced parameters, and per-leaf norms of the outer
    momentum (the pseudogradient as the outer optimizer holds it), of the
    outer parameters' change, and of worker 0's first gradient.

    ``half_batch`` plants a fault: every worker step sees only the first
    half of its sequences.
    """
    nx = Numerics(numerics)
    K, H = traffic["workers"], traffic["sync_interval"]
    B, S = traffic["batch_per_worker"], traffic["seq_len"]
    opt = traffic["inner"]
    total = traffic["rounds"] * H
    warmup = max(total // 100, 5)
    rows = B // 2 if half_batch else B
    seq_block = traffic.get("reference_seq_block", rows)

    with jax.default_matmul_precision("highest"):
        params = family.init(jax.random.PRNGKey(seed), model)
        outer = flat(params)
        is_muon = family.is_muon if traffic["inner_optimizer"] == "muon" else (
            lambda _path: False)
        stream = TokenStream(model["vocab"], S, B, K, seed)
        eval_stream = TokenStream(model["vocab"], S, B, 1, seed + 10_000)

        def loss_fn(p, tokens, labels):
            tree = unflat(p)
            total_nll = jnp.float32(0.0)
            for i in range(0, tokens.shape[0], seq_block):
                total_nll = total_nll + family.nll(nx, tree, tokens[i:i + seq_block],
                                                   labels[i:i + seq_block], model)
            return total_nll / (tokens.shape[0] * tokens.shape[1])

        @partial(jax.jit, donate_argnums=(0, 1))
        def worker_step(p, s, tokens, labels, lr, step):
            loss, g = jax.value_and_grad(loss_fn)(p, tokens, labels)
            new_p, new_s = inner_update(nx, p, g, s, step, lr, opt, is_muon)
            return new_p, new_s, loss, g

        def run_worker_step(p, s, tokens, labels, step):
            lr = cosine_lr(step, opt["lr"], total, warmup)
            return worker_step(p, s, tokens, labels, jnp.float32(lr), step)

        # worker steps donate their parameters: each worker holds a copy
        workers = [{k: jnp.copy(v) for k, v in outer.items()} for _ in range(K)]
        states = [inner_init(outer, is_muon) for _ in range(K)]
        losses = []
        grad1 = None
        for h in range(H):
            tokens, labels = stream.step(h)
            step_losses = []
            for k in range(K):
                workers[k], states[k], loss, g = run_worker_step(
                    workers[k], states[k], tokens[k, :rows], labels[k, :rows], h + 1)
                step_losses.append(float(loss))
                if h == 0 and k == 0:
                    grad1 = norms(g)
                del g
            losses.append(sum(step_losses) / K)

        mu, eta = traffic["outer_momentum"], traffic["outer_lr"]
        u, new_outer = {}, {}
        comp = traffic.get("compression")
        for path, p in outer.items():
            psi = pseudogradient(p, [w[path] for w in workers], comp)
            u[path] = eta * psi
            new_outer[path] = p - mu * u[path] - eta * psi
        change = {k: new_outer[k] - outer[k] for k in outer}
        etok, elab = eval_stream.step(0)
        eval_loss = float(jax.jit(loss_fn)(new_outer, etok[0], elab[0]))
    return {"loss": losses, "eval_loss": eval_loss, "u": norms(u),
            "change": norms(change), "grad1": grad1}
