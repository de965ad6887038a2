#!/usr/bin/env python3
"""Run one cell of the benchmark on the chips of this machine.

    python3 chipbench/run.py --workload er20.walk --seed 7 --seconds 10 \
        --trace 0

Set-up builds the cell from its files (``chipbench/core/spec.py``), warms
up every shape it will use, and counts as ``setup_s`` from process start to
the first timed round. The window then measures for ``--seconds``. Once it
has closed, the peak device memory is read and what the window produced is
compared with the plain references. The last line of standard output is
one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics with ``--trace 0``, its per-layer metrics, read
from a profiler trace of the window, with ``--trace 1``), ``device``, with
``--trace 1`` a ``breakdown``, and last ``compared``: each number compared
with its limit. The same numbers end standard error.

Exits non-zero and prints no result without a TPU, or with fewer chips than
the cell asks for.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)


class Spans:
    """Host spans of the harness, written into the profiler's trace, where
    the trace reducer attributes the device's idle gaps to them."""

    def __init__(self):
        self.names: set = set()

    @contextlib.contextmanager
    def span(self, name: str):
        import jax
        self.names.add(name)
        with jax.profiler.TraceAnnotation(name):
            yield


def run_cell(cell, seed: int, seconds: float, trace: bool, devices, *,
             control: bool = False, t_process: float = T_PROCESS) -> dict:
    """One run of ``cell`` on ``devices``; returns the result object."""
    import jax

    from chipbench.core import spec, trace as tr
    from chipbench.core.phases import CompileCounter

    counter = CompileCounter()
    spans = Spans()
    kind = spec.kind_module(cell.traffic["kind"])
    prepared = kind.prepare(cell, seed, seconds, spans.span, control=control)
    measure: dict = {}
    with tempfile.TemporaryDirectory() as trace_dir:
        if trace:
            jax.profiler.start_trace(trace_dir)
        compiles0 = counter.compiles
        setup_s = time.perf_counter() - t_process
        with spans.span("window"):
            rounds = prepared.window(measure)
        window_compiles = counter.compiles - compiles0
        summary = None
        if trace:
            jax.profiler.stop_trace()
            summary = tr.reduce(tr.find_xplane(trace_dir),
                                [d.id for d in devices], spans.names)
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    counts = prepared.counts(rounds)
    checks = prepared.check(rounds)
    limits = cell.config["limits"]
    compared = {k: {"value": checks[k], "limit": limit}
                for group in kind.GROUPS
                for k, limit in limits[group].items()}
    correct = all(c["value"] <= c["limit"] for c in compared.values())

    device_kind = devices[0].device_kind
    device = {"platform": devices[0].platform, "kind": device_kind,
              "count": len(devices),
              "memory_peak_bytes": max(peaks) if peaks else None}
    metrics = {}
    if not trace:
        values = {**prepared.rates(counts, measure["window_s"]),
                  "setup_s": setup_s}
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    result = {"correct": correct, "attempted": checks["attempted"],
              "failed": checks["failed"], "metrics": metrics,
              "device": device}
    if trace:
        ctx = {"counts": counts, "trace": summary, "info": prepared.info,
               "peaks": spec.peaks_for(device_kind)
               if devices[0].platform == "tpu" else None}
        for m in cell.per_layer:
            value = spec.metric_reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if summary is not None and summary.busy_s > 0:
            device["busy_s"] = summary.busy_s
            device["window_s"] = summary.window_s
            result["breakdown"] = {
                "device_ops": summary.top_ops,
                "idle_gaps": tr.idle_by_span(summary)}
    result["setup"] = {"setup_s": setup_s, **prepared.info,
                       "window_s": measure["window_s"],
                       "window_compiles": window_compiles,
                       "compiles": counter.compiles,
                       "cache_hits": counter.cache_hits,
                       "law_detail": checks.get("law_detail")}
    result["compared"] = compared
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    try:
        from chipbench.core import spec
        import repro  # noqa: F401  (the system under test)
    except ImportError as e:
        print(f"chipbench: cannot import the benchmark or the program: {e}",
              file=sys.stderr)
        return 2
    cell = spec.load_cell(args.workload)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chipbench: needs a TPU, JAX found {devices[0].platform}; "
              f"no measurement", file=sys.stderr)
        return 1
    if len(devices) < cell.chips:
        print(f"chipbench: {args.workload} needs {cell.chips} chips, JAX "
              f"found {len(devices)}", file=sys.stderr)
        return 1
    spec.peaks_for(devices[0].device_kind)   # an unknown device is an error

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    # every program of the cell into the cache, however quick its compile
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      devices[:cell.chips])
    for name, c in result["compared"].items():
        print(f"compared {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
