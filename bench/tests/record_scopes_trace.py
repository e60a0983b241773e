"""Record the chip trace of the round program's scopes that
``bench/tests/test_scopes.py`` reads.

    python3 bench/tests/record_scopes_trace.py <out_dir>

On one TPU: a reduced smollm-135m (2 layers, d_model 256) MuLoCo engine,
K=2 workers, H=2, 2 x 128 tokens per worker step, eval in the program.
Round 0 compiles outside the trace; inside a ``bench.window`` span,
``repro.engine.run_rounds`` drives rounds 1 and 2, one per dispatch. The
profiler leaves out Python calls and the runtime's own host events, and the
copy leaves out the programs' HLO (``bench.scopes.save_without_hlo``). Writes
``<out_dir>/scopes.xplane.pb`` and prints the device time of each scope,
the host spans and the idle gaps.
"""
from __future__ import annotations

import glob
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import jax  # noqa: E402

K, H, BATCH, SEQ = 2, 2, 2, 128


def main(out_dir: str) -> int:
    if jax.devices()[0].platform != "tpu":
        print("record_scopes_trace: JAX found no TPU", file=sys.stderr)
        return 2
    from bench import scopes
    from repro import tracing
    from repro.configs import get_config, reduce_config
    from repro.core import DiLoCoConfig
    from repro.data import DataConfig, MarkovStream, batches_for_span
    from repro.engine import TrainEngine, run_rounds
    from repro.models import build_model
    from repro.optim import OptimizerConfig

    cfg = reduce_config(get_config("smollm-135m")).replace(max_seq_len=SEQ)
    engine = TrainEngine(build_model(cfg), DiLoCoConfig(n_workers=K, sync_interval=H),
                         OptimizerConfig(lr=1e-2))
    state = engine.init(jax.random.PRNGKey(0))
    data = MarkovStream(DataConfig(vocab=cfg.vocab, seq_len=SEQ, batch_per_worker=BATCH,
                                   n_workers=K, seed=1))
    eval_data = MarkovStream(DataConfig(vocab=cfg.vocab, seq_len=SEQ,
                                        batch_per_worker=BATCH, n_workers=1, seed=2))
    feed = {"span_batches_for": lambda r0, n: batches_for_span(data, r0, H, n),
            "eval_batches_for": lambda r0, n: jax.tree.map(
                lambda x: x[:, 0], eval_data.batch_stack(r0, n))}
    state, _ = run_rounds(engine, state, None, 1, **feed)
    jax.block_until_ready(state)
    tmp = tempfile.mkdtemp(prefix="record-scopes-")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level, options.host_tracer_level = 0, 1
    with jax.profiler.trace(tmp, profiler_options=options):
        with jax.profiler.TraceAnnotation("bench.window"):
            state, _ = run_rounds(engine, state, None, 3, start=1, **feed)
            jax.block_until_ready(state)
    path = sorted(glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True))[-1]
    os.makedirs(out_dir, exist_ok=True)
    out = os.path.join(out_dir, "scopes.xplane.pb")
    scopes.save_without_hlo(path, out)
    shutil.rmtree(tmp, ignore_errors=True)
    trace = scopes.load(out)
    lo, hi = trace.window()
    busy = sum(scopes.tr.busy_ns(d, lo, hi) for d in trace.devices.values())
    print("window_ns", hi - lo, "busy_ns", busy, "tf_ops", len(trace.tf_ops))
    top = [tracing.FWD_BWD, tracing.INNER_OPT, tracing.OUTER_SYNC, tracing.EVAL,
           tracing.DATAGEN]
    print("split_ns", scopes.scope_split(trace, lo, hi, top))
    print("nested_ns", {s: scopes.scope_ns(trace, lo, hi, s) for s in tracing.DEVICE_SCOPES})
    print("unscoped", scopes.top_unscoped(trace, lo, hi, top))
    print("program_spans", sorted(trace.program_spans))
    print("idle_gaps", scopes.idle_gaps(trace, lo, hi))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
