"""The round program's device scopes and the driver's host spans
(:mod:`repro.tracing`): every scope reaches the compiled program's
``op_name`` metadata, and ``run_rounds`` writes its spans into a profiler
trace with bare names, nested in ``repro.run_rounds``."""
import glob
import os
import re

import jax
import numpy as np
import pytest

from repro import tracing
from repro.core import DiLoCoConfig
from repro.data import DataConfig, MarkovStream, batches_for_span
from repro.engine import TrainEngine, run_rounds
from repro.models import ModelConfig, build_model
from repro.optim import OptimizerConfig

CFG = ModelConfig(arch_type="dense", n_layers=2, d_model=32, n_heads=2, n_kv_heads=2,
                  d_ff=64, vocab=64, remat=False, dtype="float32")
ICFG = OptimizerConfig(lr=1e-2, weight_decay=0.0)
K, H = 2, 2


def _setup(inner):
    engine = TrainEngine(build_model(CFG), DiLoCoConfig(n_workers=K, sync_interval=H,
                                                        inner_name=inner), ICFG)
    data = MarkovStream(DataConfig(vocab=CFG.vocab, seq_len=16, batch_per_worker=2,
                                   n_workers=K, seed=3))
    eval_data = MarkovStream(DataConfig(vocab=CFG.vocab, seq_len=16, batch_per_worker=2,
                                        n_workers=1, seed=4))

    def span_batches_for(r0, n):
        return batches_for_span(data, r0, H, n)

    def eval_batches_for(r0, n):
        return jax.tree.map(lambda x: x[:, 0], eval_data.batch_stack(r0, n))

    return engine, data, span_batches_for, eval_batches_for


def _op_names(compiled_text: str) -> set:
    return set(re.findall(r'op_name="([^"]*)"', compiled_text))


@pytest.mark.parametrize("inner", ["muon", "adamw"])
def test_round_program_carries_every_scope(inner):
    engine, data, span_batches_for, eval_batches_for = _setup(inner)
    state = engine.abstract_state()
    text = engine.jitted_round.lower(state, span_batches_for(0, 1), eval_batches_for(0, 1),
                                     None, None).compile().as_text()
    names = _op_names(text)
    in_program = set(tracing.DEVICE_SCOPES) - {tracing.DATAGEN}
    if inner != "muon":
        in_program.discard(tracing.NEWTON_SCHULZ)
    for scope in in_program:
        assert any(scope in n for n in names), f"{scope} missing from the round program"
    assert (inner == "muon") == any(tracing.NEWTON_SCHULZ in n for n in names)
    # attention runs in every forward: the inner steps' and the eval's
    attention = [n for n in names if tracing.ATTENTION in n]
    assert any(tracing.FWD_BWD in n for n in attention)
    assert any(tracing.EVAL in n for n in attention)
    # Newton-Schulz is part of the inner optimizer, the sync's stages of the sync
    for inner_scope, outer_scope in [(tracing.NEWTON_SCHULZ, tracing.INNER_OPT),
                                     (tracing.PSEUDOGRAD, tracing.OUTER_SYNC),
                                     (tracing.REDUCE, tracing.OUTER_SYNC),
                                     (tracing.OUTER_UPDATE, tracing.OUTER_SYNC)]:
        assert all(outer_scope in n for n in names if inner_scope in n)
    # the sampler is its own program, still compiled as jit_stacked
    data.batch_stack(0, H)
    sampler = data._stacked_fns[H].lower(np.int32(0))
    assert sampler.compile().as_text().count(tracing.DATAGEN) > 0
    assert "jit_stacked" in sampler.as_text()


def test_run_rounds_writes_host_spans(tmp_path):
    engine, _, span_batches_for, eval_batches_for = _setup("adamw")
    state = engine.init(jax.random.PRNGKey(0))
    saved = []
    telemetry: dict = {}
    # compile outside the trace, then three single-round dispatches
    state, _ = run_rounds(engine, state, None, 1, span_batches_for=span_batches_for,
                          eval_batches_for=eval_batches_for)
    with jax.profiler.trace(str(tmp_path)):
        state, history = run_rounds(
            engine, state, None, 4, start=1, rounds_per_dispatch=1,
            span_batches_for=span_batches_for, eval_batches_for=eval_batches_for,
            on_state=lambda r, s: saved.append(r), on_state_every=2, telemetry=telemetry)
        jax.block_until_ready(state)
    assert [h["round"] for h in history] == [1, 2, 3] and saved == [1, 3]
    path = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"), recursive=True)[0]
    data = jax.profiler.ProfileData.from_file(path)
    spans = [(e.start_ns, e.start_ns + e.duration_ns, e.name, dict(e.stats))
             for plane in data.planes if plane.name == "/host:CPU"
             for line in plane.lines for e in line.events if e.name.startswith("repro.")]
    names = [n for _, _, n, _ in spans]
    # every span but the rollback's, which a run without a health flag never takes
    assert set(names) == set(tracing.HOST_SPANS) - {tracing.RECOVERY}
    assert names.count(tracing.RUN_ROUNDS) == 1
    assert names.count(tracing.DISPATCH) == telemetry["dispatches"] == 3
    assert names.count(tracing.DRAIN) == 3 and names.count(tracing.CHECKPOINT) == 2
    dispatches = [st for _, _, n, st in sorted(spans, key=lambda sp: sp[0])
                  if n == tracing.DISPATCH]
    assert [(d["round"], d["rounds"]) for d in dispatches] == [(1, 1), (2, 1), (3, 1)]
    (lo, hi), = [(s, e) for s, e, n, _ in spans if n == tracing.RUN_ROUNDS]
    assert all(lo <= s <= e <= hi for s, e, _, _ in spans)
