#!/usr/bin/env python3
"""Records ``chipbench/tests/data/scoped.xplane.pb`` on a TPU: one tiny
round walked by ``WalkEngine.run`` and trained by
``StreamingSGNSTrainer.consume``, after a warm-up round, inside the
harness spans ``window``, ``walk.run`` and ``train.consume``. The
profiler's Python tracer is off (nothing reads its events), which keeps
the file under 2 MB.

    python3 chipbench/tests/record_scoped.py [OUT]

The graph is ``wec:k=14,deg=10,seed=1`` (16,384 vertices): a vocabulary
large enough that the host alias refresh takes most of the device's idle
gap between the walk and the training. ``test_scopes.py`` holds the
numbers this round must show.
"""
from __future__ import annotations

import glob
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "src"))

GRAPH = "wec:k=14,deg=10,seed=1"
WALKERS, LENGTH = 32, 12
SGNS = dict(dim=16, window=3, negatives=2, batch_size=128, seed=0)


def main(out: str) -> int:
    import jax
    import numpy as np

    from repro.engine import WalkEngine, WalkPlan
    from repro.train import StreamingSGNSTrainer

    if jax.devices()[0].platform != "tpu":
        print("record_scoped: needs a TPU", file=sys.stderr)
        return 1
    # compile afresh where a cached executable differs only in its scopes
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    engine = WalkEngine.build(GRAPH, WalkPlan(p=1.0, q=0.5, length=LENGTH))
    trainer = StreamingSGNSTrainer(engine.n, **SGNS)
    starts = np.arange(WALKERS, dtype=np.int32)
    trainer.consume(engine.run(starts, seed=1).walks)      # compile
    jax.block_until_ready(trainer.params)
    with tempfile.TemporaryDirectory() as trace_dir:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        with jax.profiler.TraceAnnotation("window"):
            with jax.profiler.TraceAnnotation("walk.run"):
                walks = engine.run(starts, seed=2).walks
            with jax.profiler.TraceAnnotation("train.consume"):
                trainer.consume(walks)
            jax.block_until_ready(trainer.params)
        jax.profiler.stop_trace()
        (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True)
        shutil.copy(path, out)
    print(f"record_scoped: {out} {os.path.getsize(out)} B")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1] if len(sys.argv) > 1 else os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "data",
        "scoped.xplane.pb")))
