"""Deterministic synthetic LM data with per-worker shards.

A fixed random first-order Markov chain over the vocabulary with Zipfian
stationary structure: the data has real sequential signal (entropy well below
log V), so optimizer differences (AdamW vs Muon, K, H, compression) move the
loss the way they do on text. Each DiLoCo worker k draws from an independent
stream seeded by (seed, worker) — the paper's i.i.d. shard setting D_k.

Everything is derived from counters, so batches are reproducible, resumable
from a step index, and identical across hosts without any files.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.tracing import DATAGEN


@dataclasses.dataclass(frozen=True)
class DataConfig:
    vocab: int = 256
    seq_len: int = 128
    batch_per_worker: int = 8
    n_workers: int = 1
    seed: int = 0       # sampling stream (train vs held-out eval use different seeds)
    table_seed: int = 0  # the "language" (transition table) — shared across streams
    branching: int = 8  # successors per state: entropy ~= log2(branching) bits


def _transition_table(cfg: DataConfig) -> np.ndarray:
    """[vocab, branching] successor table + Zipf-weighted start distribution.

    Keyed by ``table_seed`` (not ``seed``) so train and eval streams sample
    the SAME chain with disjoint randomness — held-out eval, same language."""
    rng = np.random.default_rng(cfg.table_seed + 1337)
    return rng.integers(0, cfg.vocab, size=(cfg.vocab, cfg.branching), dtype=np.int32)


@dataclasses.dataclass
class MarkovStream:
    cfg: DataConfig

    def __post_init__(self):
        self.table = jnp.asarray(_transition_table(self.cfg))
        zipf = 1.0 / (np.arange(1, self.cfg.vocab + 1) ** 1.2)
        self.start_logits = jnp.asarray(np.log(zipf / zipf.sum()), jnp.float32)
        # one compiled sampler per n_steps (jitted: a whole round's batches
        # are generated in a single dispatch instead of H python-level calls)
        self._stacked_fns: dict[int, callable] = {}

    def _batch_toks(self, step) -> jax.Array:
        """[K, B, S+1] token sample for one global step (traced-step safe)."""
        cfg = self.cfg
        key = jax.random.fold_in(jax.random.PRNGKey(cfg.seed), step)
        keys = jax.random.split(key, cfg.n_workers)
        return jax.vmap(lambda k: self._sample(k, cfg.batch_per_worker, cfg.seq_len + 1))(keys)

    def batch(self, step: int) -> dict:
        """Batch for one global step: leaves [K, B, S] (+labels)."""
        toks = self._batch_toks(step)
        return {"tokens": toks[..., :-1], "labels": toks[..., 1:]}

    def batch_stack(self, start_step: int, n_steps: int) -> dict:
        """``n_steps`` consecutive batches in ONE compiled call: [n, K, B, S].

        Bitwise-identical to stacking ``batch(start_step + h)`` for h in
        range(n_steps) — the per-step threefry fold-in and per-worker sampling
        are the same ops under an extra vmap — but built device-side in a
        single dispatch, so the engine's scan input no longer costs H
        host-level trace/dispatch round-trips per round.
        """
        fn = self._stacked_fns.get(n_steps)
        if fn is None:
            @jax.named_scope(DATAGEN)
            def stacked(start):  # compiled as jit_stacked: trace readers find it by that name
                steps = start + jnp.arange(n_steps)
                return jax.vmap(self._batch_toks)(steps)

            fn = self._stacked_fns[n_steps] = jax.jit(stacked)
        toks = fn(jnp.asarray(start_step, jnp.int32))
        return {"tokens": toks[..., :-1], "labels": toks[..., 1:]}

    def _sample(self, key: jax.Array, batch: int, length: int) -> jax.Array:
        cfg = self.cfg
        k0, k1 = jax.random.split(key)
        state = jax.random.categorical(k0, self.start_logits, shape=(batch,))

        def step_fn(state, k):
            choice = jax.random.randint(k, (batch,), 0, cfg.branching)
            nxt = self.table[state, choice]
            return nxt, state

        ks = jax.random.split(k1, length)
        _, toks = jax.lax.scan(step_fn, state, ks)
        return toks.T.astype(jnp.int32)  # [batch, length]

    def entropy_floor_nats(self) -> float:
        """Per-token entropy of the chain (the achievable loss floor)."""
        return float(np.log(self.cfg.branching))


def batches_for_round(stream: MarkovStream, round_idx: int, sync_interval: int) -> dict:
    """Stacked batches for one DiLoCo round: leaves [H, K, B, S].

    Generated in one compiled call (:meth:`MarkovStream.batch_stack`) rather
    than H sequential ``stream.batch`` host dispatches."""
    return stream.batch_stack(round_idx * sync_interval, sync_interval)


def batches_for_span(stream: MarkovStream, round_idx: int, sync_interval: int,
                     n_rounds: int) -> dict:
    """Round-stacked batches for ``n_rounds`` consecutive rounds:
    leaves [R, H, K, B, S] — the superstep executor's input.

    One compiled ``batch_stack`` call for all R*H steps, then a reshape of
    the leading axis; bitwise-identical to stacking
    ``batches_for_round(stream, round_idx + i, sync_interval)`` for i in
    range(n_rounds)."""
    flat = stream.batch_stack(round_idx * sync_interval, n_rounds * sync_interval)
    return jax.tree.map(
        lambda x: x.reshape(n_rounds, sync_interval, *x.shape[1:]), flat)
