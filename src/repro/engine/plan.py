"""WalkPlan / WalkStats / WalkResult — the engine's declarative surface.

A :class:`WalkPlan` is the single description of *what* to walk (p/q/length/
mode/eps) and *how* (backend + layout/capacity knobs); :class:`WalkEngine`
turns it into an executable. ``WalkStats`` is the structured diagnostics
record the old call paths used to drop on the floor (dropped requests,
superstep count, collective-bytes estimate from ``repro.roofline``).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

BACKENDS = ("reference", "sharded", "fused")


@dataclasses.dataclass(frozen=True)
class WalkPlan:
    """Frozen, hashable description of a walk workload.

    Layout knobs (``cap``/``hot_cap``) select the paper's FN variant:
    ``cap=None`` -> FN-Base (rows at max degree, no hot set);
    ``cap < max degree`` -> FN-Cache (popular rows replicated). ``mode``
    selects the sampling strategy (exact / approx / approx_always) and
    ``backend`` the execution substrate — the same plan runs bit-identically
    on all three backends (tested).
    """
    p: float = 1.0
    q: float = 1.0
    length: int = 80
    mode: str = "exact"               # exact | approx | approx_always
    approx_eps: float = 1e-3
    backend: str = "reference"        # reference | sharded | fused
    cap: Optional[int] = None         # cold row width (None -> FN-Base)
    hot_cap: Optional[int] = None     # hot row width (None -> max hot degree)
    capacity: Optional[object] = None  # sharded: request slots per
                                      # destination *per exchange* (pipelined
                                      # mode runs two half-size exchanges per
                                      # superstep). int, None (zero-drop
                                      # worst case), or "auto" (derived from
                                      # the cold degree mass —
                                      # ``roofline.traffic.
                                      # walk_auto_capacity``)
    strict_drops: bool = False        # raise (not warn) when requests drop
    pipeline: bool = False            # async superstep pipeline (DESIGN §12):
                                      # sharded -> double-buffered cohort
                                      # exchange overlapped with compute;
                                      # reference / fused -> no-op.
                                      # Walks are bit-identical either way.

    def __post_init__(self):
        if self.backend not in BACKENDS:
            raise ValueError(
                f"backend must be one of {BACKENDS}, got {self.backend!r}")
        if self.length < 1:
            raise ValueError(f"length must be >= 1, got {self.length}")
        cap = self.capacity
        ok = cap is None or cap == "auto" or \
            (isinstance(cap, (int, np.integer)) and cap >= 1)
        if not ok:
            raise ValueError(
                f"capacity must be None, 'auto', or a positive int, "
                f"got {cap!r}")

    def params(self):
        """Legacy ``WalkParams`` view (for the deprecated shims)."""
        from repro.core.walk import WalkParams
        return WalkParams(p=self.p, q=self.q, length=self.length,
                          mode=self.mode, approx_eps=self.approx_eps)

    def sampler(self):
        from repro.engine.sampler import Sampler
        return Sampler(p=self.p, q=self.q, mode=self.mode,
                       eps=self.approx_eps, fused=self.backend == "fused")

    @staticmethod
    def from_params(params, **overrides) -> "WalkPlan":
        """Lift a legacy ``WalkParams`` into a plan (shim entry points)."""
        return WalkPlan(p=params.p, q=params.q, length=params.length,
                        mode=params.mode, approx_eps=params.approx_eps,
                        **overrides)


@dataclasses.dataclass(frozen=True)
class WalkStats:
    """Structured per-run diagnostics.

    ``dropped``            — NEIG requests beyond the static exchange
                             capacity (walker stayed put for that step);
                             always 0 on single-device backends.
    ``supersteps``         — Pregel supersteps executed (== walk length).
    ``collective_bytes``   — analytic per-device NEIG-exchange estimate from
                             ``repro.roofline.traffic`` (0 off-mesh); the
                             measured-from-HLO number comes from
                             ``WalkEngine.analyze()``.
    ``exposed_collective_bytes`` — the subset of ``collective_bytes`` that
                             sits on the superstep critical path (cannot
                             hide behind walker compute). Barrier mode:
                             equal to ``collective_bytes``. Pipelined mode:
                             strictly smaller (``roofline.traffic.
                             walk_overlap_model``).
    ``overlap_efficiency`` — ``1 - exposed/total`` collective bytes; 0 when
                             nothing is on the wire or nothing overlaps.
    ``graph_version``      — the GraphStore delta counter this run's walks
                             were sampled against (stamped at dispatch time,
                             so streamed rounds report the version they
                             actually walked); 0 without a store.
    ``delta_edges``        — cumulative edge add+remove events applied to
                             this engine via ``update()`` so far.
    ``invalidated_shard_fraction`` — fraction of shards whose device rows
                             the *last* ``update()`` rewrote (1.0 on a full
                             relayout, 0.0 before any update).
    """
    backend: str
    walkers: int
    supersteps: int
    dropped: int = 0
    collective_bytes: int = 0
    exposed_collective_bytes: int = 0
    overlap_efficiency: float = 0.0
    graph_version: int = 0
    delta_edges: int = 0
    invalidated_shard_fraction: float = 0.0


@dataclasses.dataclass(frozen=True)
class WalkResult:
    """Host-side walks [W, length] i32 plus their stats."""
    walks: np.ndarray
    stats: WalkStats
