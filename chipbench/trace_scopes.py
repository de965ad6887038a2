#!/usr/bin/env python3
"""Where one cell's time goes, by the program's own phases (``repro.obs``).

    python3 chipbench/trace_scopes.py --workload er20.sgns --seed 7 \
        --seconds 10 [--keep DIR]

Sets the cell up as ``chipbench/run.py`` does, then runs three windows of
``--seconds``: one with the profiler off, one traced as ``run.py`` traces,
and one traced with the profiler's Python tracer off; it reduces each
traced one with ``chipbench/core/scopes.py``. The last line of standard
output is one JSON object with, for each window, the cell's end-to-end
rate (against the untraced one: the cost of tracing) and, for the traced
ones, busy and window seconds, device self time by scope, the program's
spans, the idle time by span (harness and program spans together, and as
``chipbench/core/trace.py`` reads it), the top ops with their scopes, and
the per-layer numbers of ``scopes.per_layer``. ``--keep DIR`` copies the
traces into DIR. Exits 1 without a TPU.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", default=None)
    args = ap.parse_args(argv)

    import jax

    from chipbench.core import scopes, spec, trace as tr
    from chipbench.run import Spans
    from repro.launch.compile_cache import enable_compile_cache

    cell = spec.load_cell(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"trace_scopes: {args.workload} needs {cell.chips} TPU chips, "
              f"JAX found {len(devices)} {devices[0].platform}",
              file=sys.stderr)
        return 1
    devices = devices[:cell.chips]
    ids = [d.id for d in devices]
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    # the cache's key leaves op metadata out: an executable cached from a
    # program that differs only in its scopes would bring the old ones
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)

    spans = Spans()
    kind = spec.kind_module(cell.traffic["kind"])
    prepared = kind.prepare(cell, args.seed, args.seconds, spans.span)
    no_python = jax.profiler.ProfileOptions()
    no_python.python_tracer_level = 0
    # untraced; traced as chipbench/run.py traces; traced without the
    # Python tracer, which slows host Python (the alias refresh) under trace
    windows = {"untraced": None, "traced": jax.profiler.ProfileOptions(),
               "traced_no_python": no_python}
    result = {"workload": args.workload, "seed": args.seed,
              "device": {"platform": devices[0].platform,
                         "kind": devices[0].device_kind,
                         "count": len(devices)}}
    for label, options in windows.items():
        measure: dict = {}
        with tempfile.TemporaryDirectory() as trace_dir:
            if options is not None:
                jax.profiler.start_trace(trace_dir, profiler_options=options)
            with spans.span("window"):
                rounds = prepared.window(measure)
            if options is not None:
                jax.profiler.stop_trace()
                path = tr.find_xplane(trace_dir)
                summary = scopes.reduce(path, ids, spans.names,
                                        *scopes.program_names())
                harness = tr.reduce(path, ids, spans.names)
                if args.keep:
                    os.makedirs(args.keep, exist_ok=True)
                    shutil.copy(path, os.path.join(
                        args.keep, f"{args.workload}.{label}.xplane.pb"))
        counts = prepared.counts(rounds)
        rate = prepared.rates(counts, measure["window_s"])
        out = result[label] = {"rate": rate, "counts": counts}
        if options is None:
            continue
        busy = summary.busy_s
        out.update({
            "tracing_cost": {k: 1.0 - v / result["untraced"]["rate"][k]
                             for k, v in rate.items()},
            "busy_s": busy, "window_s": summary.window_s,
            "scopes": summary.scopes,
            "scope_shares": {k: v / busy for k, v in summary.scopes.items()}
            if busy else {},
            "spans": summary.spans, "idle": summary.idle,
            "idle_harness": tr.idle_by_span(harness),
            "top_ops": summary.top_ops,
            "per_layer": scopes.per_layer(summary, counts)})
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
