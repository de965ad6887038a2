"""Names of the program's phases in a profiler trace.

Device phases are ``jax.named_scope``s: they compile to op metadata only
(the scope rides in each HLO op's ``op_name``, e.g.
``jit(_simulate)/while/body/walk.draw/reduce_sum``), so they change no
instruction, and a fused op carries the scope of its root. Host phases are
:func:`span`s, events on the profiler's host trace that cost one TraceMe
each while no profiler runs. Nothing is recorded or exported here: tracing
is on exactly when a profiler is running.

=================== ========================================================
device scope        what runs under it
=================== ========================================================
``walk.rng``        per-walker, per-step keys and uniforms
``walk.rows``       candidate-row gather, hot/cold select, degree lookups
``walk.probs``      second-order weights (membership test, alpha x w); the
                    fused kernel's call
``walk.draw``       inverse-CDF / alias draw, next-vertex gather, dead ends
``sgns.pairs``      pair generation, shuffle, per-step batch gathers
``sgns.negatives``  alias negatives
``sgns.grads``      forward and backward, with the dense gradient zeroing
``sgns.optimizer``  optimizer update and its application
=================== ========================================================

======================= ====================================================
host span               what the host does in it
======================= ====================================================
``walk.dispatch``       uploads a round's starts and enqueues its walk
``walk.fetch``          waits for a round's walks and copies them to host
``train.alias_refresh`` folds a round into the unigram counts and rebuilds
                        the negative-sampling alias table
``train.upload``        uploads walks and alias table (count ``bytes``)
``train.dispatch``      enqueues pair generation, shuffle and the epochs
======================= ====================================================
"""
from __future__ import annotations

import jax

SCOPES = ("walk.rng", "walk.rows", "walk.probs", "walk.draw",
          "sgns.pairs", "sgns.negatives", "sgns.grads", "sgns.optimizer")
SPANS = ("walk.dispatch", "walk.fetch", "train.alias_refresh",
         "train.upload", "train.dispatch")


def span(name: str, **counts: int) -> jax.profiler.TraceAnnotation:
    """Host span ``name``; each keyword count becomes a stat of the trace
    event, and the event keeps the bare name."""
    return jax.profiler.TraceAnnotation(name, **counts)
