"""Reduction of a profiler trace to the program's own phases: device time
by ``jax.named_scope``, host spans with their counts, and the device's idle
gaps attributed to the harness's and the program's spans together.

* window, busy: as ``chipbench/core/trace.py`` reads them (the harness's
  ``window`` span; the union of the ``XLA Ops`` intervals inside it, mean
  over chips), from the same nanosecond times ``ProfileData`` gives.
* scopes: each op's self time (``trace._self_times``) inside the window
  goes to the innermost ``/``-component of its ``tf_op`` (the HLO
  ``op_name``, kept in the op's event metadata) that is one of the
  program's scopes; a fused op carries the ``op_name`` of its root, so its
  whole self time goes to that root's scope. Everything else is
  ``unscoped``. Self times of nested ops add up to busy.
* spans: each program span that starts inside the window: how many, their
  seconds (clipped to the window), and each numeric stat summed
  (``train.upload``'s ``bytes``).
* idle: each gap of the window in which no op ran goes to the innermost of
  the harness's and the program's spans around its midpoint (``other``
  where none is), as ``trace.idle_by_span`` does with the harness's alone.

The program's scope and span names come from ``repro.obs``; a program
without that module gives none, and then every op is ``unscoped``.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import re

from . import trace as tr
from . import xplane

UNSCOPED = "unscoped"
OPS, MODULES = "XLA Ops", "XLA Modules"


@dataclasses.dataclass
class ScopeSummary:
    busy_s: float
    window_s: float
    scopes: dict        # {scope or "unscoped": device self seconds}
    spans: dict         # {span: {"count", "seconds", stat: sum, ...}}
    idle: list          # [[span, idle seconds], ...] most first
    top_ops: list       # [[<program>/<op>, scope, seconds], ...] at most 10
    chips: int = 1


def program_names():
    """(scopes, spans) the program under test names, or two empty tuples
    where it predates ``repro.obs``."""
    try:
        from repro import obs
    except ImportError:
        return (), ()
    return tuple(obs.SCOPES), tuple(obs.SPANS)


def scope_of(tf_op: str, scopes) -> str:
    """The innermost component of ``tf_op`` that is in ``scopes``."""
    for part in reversed(tf_op.split("/")):
        if part in scopes:
            return part
    return UNSCOPED


def _ns(line, event):
    start = line.timestamp_ns + event.offset_ps // 1000
    return start, start + event.duration_ps // 1000


def reduce(path: str, device_ids, harness_spans, scopes=(),
           program_spans=()) -> ScopeSummary:
    """Summary of the trace at ``path`` over the chips ``device_ids``.
    ``harness_spans`` holds ``window``; ``scopes`` and ``program_spans``
    are the program's names (:func:`program_names`)."""
    wanted = set(harness_spans) | set(program_spans)
    spans, devices = [], {}
    for plane in xplane.read(path).planes:
        meta, stat_names = xplane.tables(plane)
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    name = meta[e.metadata_id].name
                    if name in wanted:
                        spans.append((name, *_ns(line, e),
                                      xplane.stats(e.stats, stat_names)))
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m and int(m.group(1)) in device_ids:
            lines = {ln.name: ln for ln in plane.lines}
            ops = []
            for e in getattr(lines.get(OPS), "events", []):
                md = meta[e.metadata_id]
                tf_op = xplane.stats(md.stats, stat_names).get("tf_op", "")
                ops.append((md.name, scope_of(str(tf_op), scopes),
                            *_ns(lines[OPS], e)))
            mods = sorted((*_ns(lines[MODULES], e),
                           tr._module_name(meta[e.metadata_id].name))
                          for e in getattr(lines.get(MODULES), "events", []))
            devices[int(m.group(1))] = (ops, mods)
    windows = [(a, b) for n, a, b, _ in spans if n == tr.WINDOW]
    if len(windows) != 1:
        raise ValueError(f"{len(windows)} '{tr.WINDOW}' spans in the trace")
    w0, w1 = windows[0]

    span_sum: dict = {}
    for name, a, b, stats in spans:
        if name in program_spans and w0 <= a <= w1:
            s = span_sum.setdefault(name, {"count": 0, "seconds": 0.0})
            s["count"] += 1
            s["seconds"] += (min(b, w1) - a) * 1e-9
            for k, v in stats.items():
                if isinstance(v, (int, float)):
                    s[k] = s.get(k, 0) + v

    busy_total = 0.0
    by_scope, by_op, idle = (collections.Counter() for _ in range(3))
    for ops, mods in devices.values():
        ops = [(n, sc, max(a, w0), min(b, w1))
               for n, sc, a, b in ops if b > w0 and a < w1]
        busy = tr._union([(a, b) for _, _, a, b in ops])
        busy_total += sum(b - a for a, b in busy)
        self_t = tr._self_times([(a, b) for _, _, a, b in ops])
        starts = [a for a, _, _ in mods]
        for i, (name, sc, a, _) in enumerate(ops):
            by_scope[sc] += self_t[i]
            k = bisect.bisect_right(starts, a) - 1
            mod = mods[k][2] if k >= 0 and mods[k][1] >= a else "?"
            by_op[(f"{mod}/{tr._op_name(name)}", sc)] += self_t[i]
        edges = [w0] + [x for ab in busy for x in ab] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                mid = (a + b) / 2
                inner = [(e - s, n) for n, s, e, _ in spans
                         if n != tr.WINDOW and s <= mid <= e]
                idle[min(inner)[1] if inner else "other"] += b - a
    n = max(len(devices), 1)
    return ScopeSummary(
        busy_s=busy_total * 1e-9 / n, window_s=(w1 - w0) * 1e-9,
        scopes={k: t * 1e-9 / n for k, t in sorted(by_scope.items())},
        spans=span_sum,
        idle=[[k, t * 1e-9 / n] for k, t in idle.most_common()],
        top_ops=[[op, sc, t * 1e-9 / n]
                 for (op, sc), t in by_op.most_common(10)],
        chips=len(devices))


# per-layer metric: (scope, counts key, seconds -> unit)
SCOPE_METRICS = {
    "walk_rng_ns_per_step": ("walk.rng", "walk_steps", 1e9),
    "walk_rows_ns_per_step": ("walk.rows", "walk_steps", 1e9),
    "walk_probs_ns_per_step": ("walk.probs", "walk_steps", 1e9),
    "walk_draw_ns_per_step": ("walk.draw", "walk_steps", 1e9),
    "sgns_pairs_us_per_step": ("sgns.pairs", "sgns_steps", 1e6),
    "sgns_negatives_us_per_step": ("sgns.negatives", "sgns_steps", 1e6),
    "sgns_grads_us_per_step": ("sgns.grads", "sgns_steps", 1e6),
    "sgns_optimizer_us_per_step": ("sgns.optimizer", "sgns_steps", 1e6),
}


def per_layer(summary: ScopeSummary, counts: dict) -> dict:
    """The per-layer numbers the summary gives for a run whose work
    ``counts`` the cell's kind made; a number whose scope or span nothing in
    the window carries is left out."""
    out = {}
    for metric, (scope, key, unit) in SCOPE_METRICS.items():
        if scope in summary.scopes and counts.get(key):
            out[metric] = summary.scopes[scope] * unit / counts[key]
    idle = dict(summary.idle)
    if "train.alias_refresh" in summary.spans and summary.chips:
        out["sgns_alias_idle_share"] = \
            100.0 * idle.get("train.alias_refresh", 0.0) / summary.window_s
    upload = summary.spans.get("train.upload")
    if upload and "bytes" in upload:
        out["sgns_h2d_bytes_per_round"] = upload["bytes"] / upload["count"]
    return out
