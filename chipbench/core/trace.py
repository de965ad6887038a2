"""Reduction of a profiler trace (``.xplane.pb``, read with
``jax.profiler.ProfileData``) to the numbers the per-layer metrics read.

* window: the harness's host span ``window`` (the measured rounds).
* busy: the union of the intervals of the device's ``XLA Ops`` events,
  clipped to the window, averaged over the chips in use.
* top ops: device self time (an op's time less that of the ops nested in
  it) by ``<program>/<op>``, the program being the ``XLA Modules`` event
  the op starts in.
* idle gaps: the parts of the window in which no op ran, each attributed to
  the innermost harness span around its midpoint (``other`` where none is).

Host and device events of one trace are on clocks that agree to about a
millisecond on a TPU v5e host (a device op can read as starting that much
before the host span that launched it), so a gap is attributed by its
midpoint and the window is read on the host's clock.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re

WINDOW = "window"


@dataclasses.dataclass
class TraceSummary:
    busy_s: float                 # mean over the chips in use
    window_s: float
    top_ops: list                 # [[name, seconds], ...] at most 10
    gaps: list                    # [(span name, seconds), ...] every gap
    chips: int = 1


def find_xplane(trace_dir: str) -> str:
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise FileNotFoundError(f"{len(found)} .xplane.pb files under "
                                f"{trace_dir}, expected one")
    return found[0]


def _union(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _op_name(text: str) -> str:
    m = re.match(r"%?([^\s=]+)", text)
    return m.group(1) if m else text


def _module_name(text: str) -> str:
    return re.sub(r"\(\d+\)$", "", text)


def _self_times(events):
    """{event index: self time} for properly nested [(start, end)]."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][0], -events[i][1]))
    self_t = {i: events[i][1] - events[i][0] for i in order}
    stack = []
    for i in order:
        a, b = events[i]
        while stack and events[stack[-1]][1] <= a:
            stack.pop()
        if stack:
            self_t[stack[-1]] -= b - a
        stack.append(i)
    return self_t


def reduce(path: str, device_ids, span_names) -> TraceSummary:
    """Summary of the trace at ``path`` over the chips ``device_ids``;
    ``span_names`` are the harness's host spans, ``window`` among them."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    spans, devices = [], {}
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                          for e in line.events if e.name in span_names]
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m and int(m.group(1)) in device_ids:
            lines = {ln.name: [(e.name, e.start_ns,
                                e.start_ns + e.duration_ns)
                               for e in ln.events] for ln in plane.lines}
            devices[int(m.group(1))] = lines
    windows = [(a, b) for n, a, b in spans if n == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"{len(windows)} '{WINDOW}' spans in the trace")
    w0, w1 = windows[0]
    window_s = (w1 - w0) * 1e-9
    if not devices:
        return TraceSummary(0.0, window_s, [], [], 0)

    busy_total = 0.0
    op_time = collections.Counter()
    gaps = []
    for lines in devices.values():
        ops = [(n, max(a, w0), min(b, w1))
               for n, a, b in lines.get("XLA Ops", []) if b > w0 and a < w1]
        busy = _union([(a, b) for _, a, b in ops])
        busy_total += sum(b - a for a, b in busy)
        mods = sorted((a, b, _module_name(n))
                      for n, a, b in lines.get("XLA Modules", []))
        starts = [a for a, _, _ in mods]
        self_t = _self_times([(a, b) for _, a, b in ops])
        for i, (name, a, _) in enumerate(ops):
            k = bisect.bisect_right(starts, a) - 1
            mod = mods[k][2] if k >= 0 and mods[k][1] >= a else "?"
            op_time[f"{mod}/{_op_name(name)}"] += self_t[i]
        edges = [w0] + [x for ab in busy for x in ab] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                mid = (a + b) / 2
                inner = [(e - s, n) for n, s, e in spans
                         if n != WINDOW and s <= mid <= e]
                gaps.append((min(inner)[1] if inner else "other",
                             (b - a) * 1e-9))
    n = len(devices)
    top = [[name, t * 1e-9 / n] for name, t in op_time.most_common(10)]
    return TraceSummary(busy_total * 1e-9 / n, window_s, top, gaps, n)


def idle_by_span(summary: TraceSummary) -> list:
    """[[span, idle seconds], ...]: the window's idle time by what the host
    was doing, most first, at most 10 entries, averaged over the chips."""
    per = collections.Counter()
    for name, s in summary.gaps:
        per[name] += s
    return [[name, s / max(summary.chips, 1)]
            for name, s in per.most_common(10)]
