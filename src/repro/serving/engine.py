"""Continuous-batching serving engine over the paged KV cache.

The seed served requests in lockstep: one batch, token-by-token prefill,
every sequence padded to the longest, the whole batch held until the last
request finished. This engine replaces that with the standard
paged-attention design:

* :class:`PageAllocator` (``serving.paging``) owns a fixed pool of KV
  pages on the host; the device holds the page *contents*
  (``model.init_paged_cache``).
* :class:`Scheduler` admits pending requests into freed batch slots as
  soon as pages are available, and its admission check accounts for the
  worst-case remaining growth of every in-flight request, so
  allocate-on-demand (``PageAllocator.ensure``) can never fail mid-span.
* Admitted requests are prefilled in ONE batched dispatch
  (``model.paged_prefill``) instead of stepping the decode path through
  the prompt.
* Decode runs ``decode_steps_per_dispatch`` tokens for ALL active slots
  in one donated jitted ``lax.scan`` (``decode.build_span_fn``) — the
  host syncs once per span, not once per token.

Per-slot lengths are independent (never lockstep): a request admitted at
dispatch 40 decodes in the same device program as one admitted at
dispatch 0, each attending to exactly its own pages.

:func:`naive_generate` is the ``--engine naive`` baseline: the seed's
dense-cache serving loop, but with the batched single-dispatch prefill
and with request ``context`` actually threaded into the cache (the seed
dropped it, so audio/VLM decode ran unconditioned).
"""
from __future__ import annotations

import collections
import dataclasses
import functools
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.serving import decode as _decode
from repro.serving.paging import OutOfPages, PageAllocator, pages_needed

PyTree = Any


@dataclasses.dataclass(frozen=True)
class Request:
    """One generation request. ``arrival`` is the dispatch step at which
    the request becomes visible to the scheduler (0 = present at start),
    which is how tests inject late-joining requests deterministically."""

    rid: str
    tokens: tuple[int, ...]
    max_new: int
    arrival: int = 0

    def __post_init__(self):
        if len(self.tokens) < 1 or self.max_new < 1:
            raise ValueError("request needs >=1 prompt token and max_new >= 1")


@dataclasses.dataclass
class DecodeState:
    """Engine state between dispatches. ``cache`` lives on device (and is
    donated to every dispatch); everything else is host-side bookkeeping."""

    cache: PyTree
    tok: np.ndarray        # [B] int32 — each slot's pending (last sampled) token
    lengths: np.ndarray    # [B] int64 — tokens already written to each slot's pages
    owners: list[Request | None]

    @property
    def active(self) -> list[int]:
        return [i for i, o in enumerate(self.owners) if o is not None]


class Scheduler:
    """FIFO admission of pending requests into free batch slots.

    A request is admitted only when the pool can cover its *entire*
    worst-case footprint (prompt + max_new + one decode span, rounded up
    to pages) on top of the outstanding growth of already-admitted
    requests. Only the prompt pages are allocated up front; decode pages
    are allocated on demand — the accounting just guarantees that demand
    is always satisfiable.
    """

    def __init__(self, allocator: PageAllocator, requests: Sequence[Request],
                 span: int):
        self.alloc = allocator
        self.span = span
        self.pending = collections.deque(
            sorted(requests, key=lambda r: r.arrival))

    def _budget_pages(self, req: Request) -> int:
        return pages_needed(len(req.tokens) + req.max_new + self.span,
                            self.alloc.page_size)

    def _outstanding(self, owners: Sequence[Request | None]) -> int:
        """Pages in-flight requests may still allocate on demand."""
        tot = 0
        for r in owners:
            if r is not None:
                tot += max(0, self._budget_pages(r) - len(self.alloc.pages_for(r.rid)))
        return tot

    def admit(self, state: DecodeState, step: int) -> list[tuple[int, Request]]:
        """Fill free slots from the pending queue; allocates prompt pages."""
        admitted: list[tuple[int, Request]] = []
        for slot, owner in enumerate(state.owners):
            if owner is not None or not self.pending:
                continue
            req = self.pending[0]
            if req.arrival > step:
                break  # FIFO: don't let later arrivals jump the queue
            if self._budget_pages(req) > self.alloc.n_free - self._outstanding(state.owners):
                break
            self.pending.popleft()
            self.alloc.alloc(req.rid, pages_needed(len(req.tokens), self.alloc.page_size))
            state.owners[slot] = req
            admitted.append((slot, req))
        return admitted

    def finish(self, state: DecodeState, slot: int) -> int:
        """Release a finished request's pages and free its slot."""
        req = state.owners[slot]
        state.owners[slot] = None
        return self.alloc.release(req.rid)


class PagedEngine:
    """Paged-KV continuous-batching engine (``--engine paged``).

    ``run(requests)`` drives every request to completion and returns
    ``{rid: np.ndarray[max_new] generated tokens}``. Works for any model
    with ``supports_paged_decode`` (dense/moe attention families).
    """

    def __init__(self, model, params, *, slots: int = 4, page_size: int = 16,
                 max_pages: int = 64, decode_steps_per_dispatch: int = 8,
                 temperature: float = 0.0, attn_impl: str = "xla",
                 mesh=None, rng: jax.Array | None = None):
        if not model.supports_paged_decode:
            raise ValueError(
                f"arch_type {model.cfg.arch_type!r} has no paged decode path; "
                "serve it with --engine naive")
        self.model, self.params = model, params
        self.slots = slots
        self.page_size = page_size
        self.max_pages = max_pages
        self.span = decode_steps_per_dispatch
        self.rng = rng if rng is not None else jax.random.PRNGKey(0)
        # mesh routing: the paged-decode Pallas kernel shard_maps its batch
        # slots over 'data' (KV pool replicated — page ids stay valid on
        # every device); None on single-device worlds
        self.mesh = mesh
        from repro.launch.sharding import kernel_specs

        kparts = kernel_specs(mesh, model.cfg) if mesh is not None else None
        self._prefill = _decode.build_prefill_fn(model, temperature,
                                                 kernel_parts=kparts)
        self._span_fn = _decode.build_span_fn(model, self.span, temperature,
                                              impl=attn_impl,
                                              kernel_parts=kparts)

    def _mesh_ctx(self):
        from contextlib import nullcontext

        return jax.set_mesh(self.mesh) if self.mesh is not None else nullcontext()

    def _init_state(self) -> DecodeState:
        return DecodeState(
            cache=self.model.init_paged_cache(self.max_pages, self.page_size),
            tok=np.zeros((self.slots,), np.int32),
            lengths=np.zeros((self.slots,), np.int64),
            owners=[None] * self.slots,
        )

    def run(self, requests: Sequence[Request]) -> dict[str, np.ndarray]:
        rids = [r.rid for r in requests]
        if len(set(rids)) != len(rids):
            raise ValueError("request ids must be unique")
        sched = Scheduler(PageAllocator(self.max_pages, self.page_size),
                          requests, self.span)
        # static page-table width for this run: the largest footprint any
        # single request can reach (compiled once per width)
        table_w = max(sched._budget_pages(r) for r in requests)
        state = self._init_state()
        emitted: dict[str, list[int]] = {r.rid: [] for r in requests}
        results: dict[str, np.ndarray] = {}
        step = 0

        def _maybe_finish(slot: int) -> None:
            req = state.owners[slot]
            if len(emitted[req.rid]) >= req.max_new:
                results[req.rid] = np.asarray(emitted[req.rid][: req.max_new],
                                              np.int32)
                sched.finish(state, slot)

        while sched.pending or state.active:
            admitted = sched.admit(state, step)
            if admitted:
                n = len(admitted)
                pmax = max(len(r.tokens) for _, r in admitted)
                toks = np.zeros((n, pmax), np.int32)
                lens = np.zeros((n,), np.int32)
                for i, (_, r) in enumerate(admitted):
                    toks[i, : len(r.tokens)] = r.tokens
                    lens[i] = len(r.tokens)
                rows = np.stack([sched.alloc.page_table_row(r.rid, table_w)
                                 for _, r in admitted])
                with self._mesh_ctx():
                    state.cache, first = self._prefill(
                        self.params, state.cache, toks, rows, lens,
                        jax.random.fold_in(self.rng, 2 * step))
                first = np.asarray(first)
                for i, (slot, r) in enumerate(admitted):
                    state.tok[slot] = first[i]
                    state.lengths[slot] = len(r.tokens)
                    emitted[r.rid].append(int(first[i]))
                    _maybe_finish(slot)

            active = state.active
            if active:
                for i in active:
                    sched.alloc.ensure(state.owners[i].rid,
                                       int(state.lengths[i]) + self.span)
                table = sched.alloc.page_table(
                    [o.rid if o is not None else None for o in state.owners],
                    table_w)
                with self._mesh_ctx():
                    state.cache, toks = self._span_fn(
                        self.params, state.cache, state.tok,
                        state.lengths.astype(np.int32), table,
                        jax.random.fold_in(self.rng, 2 * step + 1))
                toks = np.asarray(toks)  # [span, B]
                for i in active:
                    emitted[state.owners[i].rid].extend(toks[:, i].tolist())
                    state.lengths[i] += self.span
                    state.tok[i] = toks[-1, i]
                    _maybe_finish(i)
            elif sched.pending and not admitted:
                if sched.pending[0].arrival <= step:
                    raise OutOfPages(
                        f"request {sched.pending[0].rid!r} needs "
                        f"{sched._budget_pages(sched.pending[0])} pages but the "
                        f"pool has {sched.alloc.n_free} free even when idle — "
                        "raise --max-pages or lower --page-size waste")
            step += 1
        return results


# Model is a frozen dataclass over a hashable config, so jitted closures can
# be cached per model — repeated naive_generate calls (benchmarks, tests)
# reuse the compiled step instead of re-tracing under a fresh jax.jit wrapper.
@functools.lru_cache(maxsize=None)
def _jitted_decode_step(model):
    return jax.jit(model.decode_step)


@functools.lru_cache(maxsize=None)
def _jitted_prefill_with_cache(model):
    return jax.jit(model.prefill_with_cache)


def naive_generate(model, params, prompts: jax.Array, max_new: int,
                   temperature: float = 0.0, context: jax.Array | None = None,
                   rng: jax.Array | None = None,
                   batched_prefill: bool = True) -> jax.Array:
    """Dense-cache lockstep serving (``--engine naive``): the seed loop with
    two fixes — ``context`` is threaded into the cache via
    ``model.fill_context`` (the seed dropped it, leaving audio/VLM decode
    unconditioned), and attention-cache families prefill the whole prompt
    in one dispatch instead of stepping token by token.

    prompts [B, P] int32 -> tokens [B, P + max_new].
    """
    B, P = prompts.shape
    cache = model.init_cache(params, B, P + max_new)
    if context is not None:
        cache = model.fill_context(params, cache, context)
    step = _jitted_decode_step(model)

    def sample(logits, rng):
        if temperature > 0:
            rng, k = jax.random.split(rng)
            tok = jax.random.categorical(k, logits / temperature, axis=-1)
            return tok.astype(jnp.int32), rng
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), rng

    out = [prompts[:, t] for t in range(P)]
    if batched_prefill and model.supports_batched_prefill:
        logits, cache = _jitted_prefill_with_cache(model)(params, cache, prompts)
        logits = logits[:, -1]
    else:
        # recurrent-state families: prefill by stepping the decode path
        for t in range(P):
            logits, cache = step(params, cache, prompts[:, t], jnp.int32(t))
    tok, rng = sample(logits, rng)
    out.append(tok)
    for t in range(P, P + max_new - 1):
        logits, cache = step(params, cache, tok, jnp.int32(t))
        tok, rng = sample(logits, rng)
        out.append(tok)
    return jnp.stack(out, axis=1)
