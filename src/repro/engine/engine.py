"""WalkEngine — one entry point over the reference, sharded, and fused
backends (DESIGN.md §3).

    engine = WalkEngine.build(graph, plan, mesh=None)
    result = engine.run(starts=None, seed=0)     # WalkResult(walks, stats)
    for r in engine.rounds(10, seed=0): ...      # FN-Multi streaming rounds

``build`` accepts a spec string / host :class:`CSRGraph` / ``Dataset`` /
:class:`~repro.data.store.GraphStore` (normalized through
``repro.data.open_graph``, and the engine keeps the store so
:meth:`WalkEngine.update` can apply edge deltas incrementally), a prebuilt
:class:`PaddedGraph`, or — for the sharded backend only — a
:class:`ShardedGraph`, which may be fully *abstract*
(``jax.ShapeDtypeStruct`` leaves) for compile-only roofline analysis via
:meth:`WalkEngine.analyze` (the dry-run path).

Walker identity: ``walker_ids`` default to the start vertex ids (the paper's
one-walk-per-vertex convention, and what the sharded partitioning requires),
so the same plan + seed gives bit-identical walks on every backend.
"""
from __future__ import annotations

import time
import warnings
from typing import Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro import obs
from repro.core.graph import PaddedGraph
from repro.core.walk import run_reference
from repro.core.walk_distributed import (ShardedGraph, make_distributed_walk)
from repro.engine.plan import WalkPlan, WalkResult, WalkStats
from repro.engine.update import UpdateReport, patch_padded, patch_sharded
from repro.launch.mesh import make_rw_mesh
from repro.roofline import analysis as roof
from repro.roofline.traffic import (walk_auto_capacity,
                                    walk_collective_bytes, walk_overlap_model)


def round_seed(seed: int, r: int) -> int:
    """Per-round seed for FN-Multi rounds (stable across engine versions —
    checkpointed runs resume bit-identically)."""
    return seed * 1000003 + r


class WalkEngine:
    """Executable walk workload: a plan bound to a graph (and mesh)."""

    def __init__(self, plan: WalkPlan, *, pg: Optional[PaddedGraph] = None,
                 sg: Optional[ShardedGraph] = None,
                 mesh: Optional[Mesh] = None, fn=None,
                 capacity: Optional[int] = None, store=None):
        self.plan = plan
        self.pg = pg
        self.sg = sg
        self.mesh = mesh
        self._fn = fn
        self.capacity = capacity
        self.store = store              # GraphStore (update() source of truth)
        self._sampler = plan.sampler()
        self._delta_edges = 0           # cumulative churn via update()
        self._last_invalidated_fraction = 0.0

    # ------------------------------------------------------------- build --
    @classmethod
    def build(cls, graph, plan: WalkPlan,
              mesh: Optional[Mesh] = None) -> "WalkEngine":
        """Bind ``plan`` to ``graph``. ``mesh`` is only consulted by the
        sharded backend (default: a 1-D 'rw' mesh over all devices).

        ``graph`` may be anything ``repro.data.open_graph`` accepts — a
        spec string (``"wec:k=10,deg=30"``, ``"edgelist:/path.txt"``, ...),
        a host :class:`CSRGraph`, a ``Dataset``, or a ``GraphStore`` — in
        which case the engine holds the (possibly freshly opened) store and
        supports incremental :meth:`update`. Prebuilt device layouts
        (:class:`PaddedGraph`/:class:`ShardedGraph`) are also accepted but
        carry no store, so ``update()`` is unavailable. CSR input on the
        sharded backend takes the shard-by-shard ``ShardedGraph.from_csr``
        path: no dense whole-graph ``PaddedGraph`` intermediate."""
        store = None
        if not isinstance(graph, (PaddedGraph, ShardedGraph)):
            from repro.data import open_graph
            store = open_graph(graph)
            graph = store.graph
        if isinstance(graph, ShardedGraph) and plan.backend != "sharded":
            raise ValueError(
                f"ShardedGraph input requires backend='sharded', "
                f"got {plan.backend!r}")
        if plan.backend in ("reference", "fused"):
            pg = graph if isinstance(graph, PaddedGraph) else \
                PaddedGraph.build(graph, cap=plan.cap, hot_cap=plan.hot_cap)
            return cls(plan, pg=pg, store=store)

        rw = make_rw_mesh(mesh)
        num_shards = int(np.prod([rw.shape[a] for a in rw.axis_names]))
        pg = None
        if isinstance(graph, ShardedGraph):
            sg = graph
            if sg.num_shards != num_shards:
                raise ValueError(
                    f"ShardedGraph built for {sg.num_shards} shards but the "
                    f"mesh has {num_shards} devices")
        elif isinstance(graph, PaddedGraph):
            pg = graph
            sg = ShardedGraph.build(pg, num_shards)
        else:
            # CSRGraph: pack shard by shard, skipping the dense PaddedGraph
            sg = ShardedGraph.from_csr(graph, num_shards, cap=plan.cap,
                                       hot_cap=plan.hot_cap)
        if not isinstance(sg.adj, jax.ShapeDtypeStruct):
            sg = sg.place(rw)
        # capacity default = one full walker block per destination: zero
        # drops, any skew. FN-Multi rounds are the lever for lowering it.
        # Pipelined mode exchanges per *cohort* (half blocks), so the
        # zero-drop default halves too — total bytes per superstep stay at
        # the barrier level while each exchange hides behind the other
        # cohort's compute.
        per_cohort = (sg.n_local + 1) // 2 if plan.pipeline else sg.n_local
        if plan.capacity == "auto":
            # derive from the cold degree mass: hot vertices are replicated
            # and never consume slots, so on skewed graphs the expected
            # per-destination demand is far below the worst case.
            if isinstance(sg.deg, jax.ShapeDtypeStruct):
                raise ValueError(
                    "capacity='auto' needs the concrete degree array; an "
                    "abstract ShardedGraph (analyze-only) must pass an "
                    "explicit capacity")
            capacity = walk_auto_capacity(
                np.asarray(sg.deg[:sg.n_orig]), cap=sg.cap,
                num_shards=sg.num_shards, walkers_per_shard=per_cohort)
        elif plan.capacity is not None:
            capacity = plan.capacity
        else:
            capacity = per_cohort
        fn = make_distributed_walk(sg, rw, plan.params(), capacity,
                                   length=plan.length,
                                   pipeline=plan.pipeline)
        return cls(plan, pg=pg, sg=sg, mesh=rw, fn=fn, capacity=capacity,
                   store=store)

    # --------------------------------------------------------------- run --
    @property
    def n(self) -> int:
        """Number of real (unpadded) vertices."""
        return self.sg.n_orig if self.sg is not None else self.pg.n

    def _abstract(self) -> bool:
        return self.sg is not None and isinstance(self.sg.adj,
                                                  jax.ShapeDtypeStruct)

    def _sharded_args(self, starts, walker_ids, key):
        g = self.sg
        return (g.adj, g.wgt, g.alias_p, g.alias_i, g.deg, g.hot_pack(),
                starts, walker_ids, key)

    def _update_meta(self):
        """(graph_version, delta_edges, invalidated fraction) snapshot —
        taken at *dispatch* time so streamed rounds report the graph state
        they actually walked, not the one current at finalize."""
        gv = self.store.version if self.store is not None else 0
        return (gv, self._delta_edges, self._last_invalidated_fraction)

    def _dispatch(self, starts, seed: int, walker_ids):
        """Launch one run asynchronously; returns
        (walks, drops, slice_to, update_meta)."""
        with obs.span("walk.dispatch"):
            key = jax.random.PRNGKey(seed)
            if self.plan.backend in ("reference", "fused"):
                if starts is None:
                    starts = np.arange(self.pg.n, dtype=np.int32)
                starts = jnp.asarray(starts, jnp.int32)
                walker_ids = starts if walker_ids is None else \
                    jnp.asarray(walker_ids, jnp.int32)
                walks = run_reference(self.pg, starts, walker_ids, key,
                                      self._sampler, self.plan.length)
                return walks, None, None, self._update_meta()

            if self._abstract():
                raise ValueError("engine was built from an abstract "
                                 "ShardedGraph — only analyze() is available")
            slice_to = None
            if starts is None:
                starts = np.arange(self.sg.n, dtype=np.int32)
                slice_to = self.sg.n_orig   # padding vertices walk self-loops
            starts = np.asarray(starts, np.int32)
            if starts.shape[0] % self.sg.num_shards:
                raise ValueError(
                    f"walker count {starts.shape[0]} must divide evenly over "
                    f"{self.sg.num_shards} shards")
            # walkers are co-located with their start vertex: walker block s
            # gets starts[s*W:(s+1)*W] and reads the start row locally, so
            # each start must live on the shard its position lands on (else
            # the first step would silently clamp to a wrong local row).
            w_local = starts.shape[0] // self.sg.num_shards
            owner = starts // self.sg.n_local
            placed = np.arange(starts.shape[0]) // w_local
            if not np.array_equal(owner, placed):
                bad = int(np.nonzero(owner != placed)[0][0])
                raise ValueError(
                    f"starts must be grouped by owning shard (vertex id // "
                    f"{self.sg.n_local}): starts[{bad}]={int(starts[bad])} "
                    f"belongs to shard {int(owner[bad])} but is placed on "
                    f"shard {int(placed[bad])}")
            walker_ids = starts if walker_ids is None else \
                np.asarray(walker_ids, np.int32)
            walks, drops = self._fn(*self._sharded_args(
                jnp.asarray(starts), jnp.asarray(walker_ids), key))
            return walks, drops, slice_to, self._update_meta()

    def _finalize(self, dispatched) -> WalkResult:
        walks, drops, slice_to, update_meta = dispatched
        with obs.span("walk.fetch"):
            walks = np.asarray(walks)
        if slice_to is not None:
            walks = walks[:slice_to]
        dropped = int(drops) if drops is not None else 0
        if dropped:
            msg = (f"{dropped} NEIG requests dropped (capacity="
                   f"{self.capacity}); affected walkers stayed put for those"
                   f" steps — raise WalkPlan.capacity or walk fewer vertices"
                   f" per round (FN-Multi)")
            if self.plan.strict_drops:
                raise RuntimeError(msg)
            warnings.warn(msg, RuntimeWarning, stacklevel=3)
        overlap = self._overlap_estimate(int(walks.shape[0]))
        gv, delta_edges, inv_frac = update_meta
        stats = WalkStats(
            backend=self.plan.backend, walkers=int(walks.shape[0]),
            supersteps=self.plan.length, dropped=dropped,
            collective_bytes=overlap["total_bytes"],
            exposed_collective_bytes=overlap["exposed_bytes"],
            overlap_efficiency=overlap["efficiency"],
            graph_version=gv, delta_edges=delta_edges,
            invalidated_shard_fraction=inv_frac)
        return WalkResult(walks=walks, stats=stats)

    def _collective_estimate(self) -> int:
        if self.sg is None:
            return 0
        w_bytes = np.dtype(self.sg.wgt.dtype).itemsize
        return walk_collective_bytes(self.sg.num_shards, self.capacity,
                                     self.sg.cap, self.plan.length,
                                     w_bytes=w_bytes)

    def _overlap_estimate(self, walkers: int) -> dict:
        """Analytic total/exposed collective bytes for a run of ``walkers``
        walkers (``roofline.traffic.walk_overlap_model``)."""
        if self.sg is None:
            return {"total_bytes": 0, "exposed_bytes": 0, "efficiency": 0.0}
        g = self.sg
        w_bytes = np.dtype(g.wgt.dtype).itemsize
        width = g.cap if self._sampler.mode == "approx_always" else g.hot_cap
        return walk_overlap_model(
            g.num_shards, self.capacity, g.cap, self.plan.length,
            walkers_per_shard=max(walkers // g.num_shards, 1),
            pipeline=self.plan.pipeline and self.plan.length >= 2,
            w_bytes=w_bytes, width=width)

    def run(self, starts=None, seed: int = 0, walker_ids=None) -> WalkResult:
        """Walk ``starts`` (default: every vertex) with the bound plan."""
        return self._finalize(self._dispatch(starts, seed, walker_ids))

    def rounds(self, num_rounds: int, seed: int = 0,
               start: int = 0) -> Iterator[WalkResult]:
        """FN-Multi streaming rounds: round ``k+1`` is *dispatched* (async
        jax execution) before round ``k`` is finalized and yielded, so the
        consumer (SGNS training) overlaps with the next round's walk."""
        if num_rounds <= start:
            return
        pending = self._dispatch(None, round_seed(seed, start), None)
        for r in range(start, num_rounds):
            nxt = self._dispatch(None, round_seed(seed, r + 1), None) \
                if r + 1 < num_rounds else None
            yield self._finalize(pending)
            pending = nxt

    # ------------------------------------------------------------ update --
    def update(self, deltas) -> UpdateReport:
        """Apply edge deltas to the resident graph *without* a whole-graph
        rebuild: the store patches the host CSR shard-locally, then only the
        affected rows' packed adjacency / alias tables / FN-Cache hot
        entries are spliced into the device layout. Unaffected shards'
        buffers stay resident and the compiled walk fn is reused; a full
        relayout (fresh layout + fn) happens only when the static shapes
        can no longer represent the new graph (see ``repro.engine.update``).

        Frozen across updates (bounded staleness, reopen/rebuild to refresh):
        the exchange ``capacity`` (plan ``"auto"`` is derived once at build)
        and, under ``relabel=degree``, the degree ranking. Walks after
        ``update()`` are bit-identical to a from-scratch engine at the same
        store version (property-tested on all three backends).
        """
        if self.store is None:
            raise ValueError(
                "update() needs the engine's GraphStore — build the engine "
                "from a spec string, CSRGraph, Dataset, or GraphStore (a "
                "prebuilt PaddedGraph/ShardedGraph carries no host CSR to "
                "patch)")
        if self._abstract():
            raise ValueError("engine was built from an abstract ShardedGraph"
                             " — only analyze() is available")
        patch = self.store.apply(deltas)
        g = self.store.graph
        aff = patch.affected
        if self.plan.backend in ("reference", "fused"):
            self.pg, relayout, hot_rows = patch_padded(
                self.pg, g, aff, self.plan.cap, self.plan.hot_cap)
            device_shards = patch.num_shards
            invalidated = device_shards if relayout \
                else int(len(patch.affected_shards))
        else:
            self.sg, relayout, inv_shards, hot_rows = patch_sharded(
                self.sg, g, aff, self.plan.cap, self.plan.hot_cap)
            self.sg = self.sg.place(self.mesh)
            if relayout:
                # shapes may have changed (cap / hot set size) -> fresh fn;
                # capacity stays frozen so the exchange shapes are stable
                self._fn = make_distributed_walk(
                    self.sg, self.mesh, self.plan.params(), self.capacity,
                    length=self.plan.length, pipeline=self.plan.pipeline)
            device_shards = self.sg.num_shards
            invalidated = int(len(inv_shards))
        self._delta_edges += patch.delta_edges
        self._last_invalidated_fraction = invalidated / max(device_shards, 1)
        return UpdateReport(
            patch=patch, version=self.store.version, relayout=relayout,
            device_shards=device_shards,
            invalidated_device_shards=invalidated,
            hot_rows_updated=hot_rows)

    # ----------------------------------------------------------- analyze --
    def analyze(self, num_walkers: Optional[int] = None) -> dict:
        """Compile-only roofline measurement for the sharded backend: lower +
        compile the walk (works with an abstract ShardedGraph), then read
        FLOPs from ``cost_analysis`` and collective bytes from the optimized
        HLO. The superstep loop lowers to a ``while`` whose body appears once
        in the HLO, and cost_analysis does not multiply through while loops
        either (verified) — so the numbers are already per-superstep (plus a
        small step-0 constant outside the loop)."""
        if self.sg is None:
            raise ValueError("analyze() requires the sharded backend")
        g = self.sg
        if num_walkers is None:
            num_walkers = g.n
        starts = jax.ShapeDtypeStruct((num_walkers,), jnp.int32)
        key = jax.ShapeDtypeStruct((2,), jnp.uint32)
        t0 = time.time()
        lowered = self._fn.lower(*self._sharded_args(starts, starts, key))
        compiled = lowered.compile()
        t_compile = time.time() - t0
        ca = roof.cost_dict(compiled.cost_analysis())
        coll = roof.collective_bytes(compiled.as_text())
        counts = coll.pop("_counts")
        flops_step = float(ca.get("flops", 0.0))
        coll_total = float(sum(coll.values()))
        try:
            arg_bytes = compiled.memory_analysis().argument_size_in_bytes
        except Exception:
            arg_bytes = None
        graph_bytes = sum(
            int(np.prod(x.shape)) * np.dtype(x.dtype).itemsize
            for x in (g.adj, g.wgt, g.alias_p, g.alias_i)) // g.num_shards \
            + sum(int(np.prod(x.shape)) * np.dtype(x.dtype).itemsize
                  for x in g.hot_pack())
        overlap = self._overlap_estimate(num_walkers)
        return {
            "backend": self.plan.backend, "mode": self.plan.mode,
            "pipeline": self.plan.pipeline,
            "overlap_total_bytes": overlap["total_bytes"],
            "overlap_exposed_bytes": overlap["exposed_bytes"],
            "overlap_efficiency": overlap["efficiency"],
            "cap": g.cap, "hot_cap": g.hot_cap, "capacity": self.capacity,
            "shards": g.num_shards, "n": g.n,
            "walkers_per_shard": num_walkers // g.num_shards,
            "compile_seconds": t_compile,
            "flops_per_step_per_dev": flops_step,
            "coll_bytes_per_step_per_dev": coll_total,
            "coll_by_op_per_step": dict(coll),
            "coll_counts": counts,
            "t_compute": flops_step / roof.PEAK_FLOPS,
            "t_collective": coll_total / roof.LINK_BW,
            "analytic_coll_bytes_per_dev": self._collective_estimate(),
            "graph_bytes_per_dev": int(graph_bytes),
            "argument_bytes_per_dev": arg_bytes,
        }
