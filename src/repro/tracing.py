"""Names of the round program's device scopes and the driver's host spans.

Any ``jax.profiler`` capture of a training run (``jax.profiler.trace``)
shows both:

* a device scope (``jax.named_scope``) becomes part of the ``op_name``
  metadata of every operation compiled inside it, and a TPU trace carries
  that path as each device operation's ``tf_op``; the device time of a layer
  is the time of the operations whose path contains its scope. A scope
  changes metadata only: the arithmetic and the fusions stay as they are;
* a host span (``jax.profiler.TraceAnnotation``) is an event on the trace's
  host plane, on the device's clock, so an idle gap on the device is named
  by what the host was doing in it. With the profiler off a span costs
  under a microsecond.

Scopes nest: ``ATTENTION`` and ``SSD`` lie inside ``FWD_BWD`` (and ``EVAL``),
``NEWTON_SCHULZ`` inside ``INNER_OPT``, and
``PSEUDOGRAD``, ``REDUCE`` and ``OUTER_UPDATE`` inside ``OUTER_SYNC``.
"""

# device scopes
FWD_BWD = "repro.fwd_bwd"  # forward, backward and rematerialisation of each inner step
# the attention core of each layer, flash kernel or XLA; kept out of
# DEVICE_SCOPES until the benchmark's recorded scope trace carries it
ATTENTION = "repro.attention"
# Mamba2's intra-chunk SSD block, fused kernel or XLA; kept out of
# DEVICE_SCOPES for the same reason as ATTENTION
SSD = "repro.ssd"
INNER_OPT = "repro.inner_opt"  # the inner optimizer: Muon (momentum, NS, AdamW leaves) or AdamW
NEWTON_SCHULZ = "repro.newton_schulz"  # Muon's orthogonalisation
OUTER_SYNC = "repro.outer_sync"  # the whole sync, the three stages below
PSEUDOGRAD = "repro.pseudograd"  # the deltas outer - worker
REDUCE = "repro.reduce"  # compress / error feedback and the mean over workers
OUTER_UPDATE = "repro.outer_update"  # the outer descent and the worker reset
EVAL = "repro.eval"  # eval loss, folded into the round program or standalone
DATAGEN = "repro.datagen"  # the data sampler (also the host span around batch calls)

DEVICE_SCOPES = (FWD_BWD, INNER_OPT, NEWTON_SCHULZ, OUTER_SYNC, PSEUDOGRAD, REDUCE,
                 OUTER_UPDATE, EVAL, DATAGEN)

# host spans of repro.engine.run_rounds
RUN_ROUNDS = "repro.run_rounds"  # the whole call
DISPATCH = "repro.dispatch"  # engine.step / superstep; stats: round (the first), rounds (R)
DRAIN = "repro.drain"  # the blocking metric read of a dispatch and on_round
CHECKPOINT = "repro.checkpoint"  # on_state and the in-program checkpoint flush
RECOVERY = "repro.recovery"  # the rollback after a health flag

HOST_SPANS = (RUN_ROUNDS, DATAGEN, DISPATCH, DRAIN, CHECKPOINT, RECOVERY)
