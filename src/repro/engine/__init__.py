"""repro.engine — the single entry point for running Node2Vec walks.

    from repro.engine import WalkEngine, WalkPlan

    plan = WalkPlan(p=0.5, q=2.0, length=80, cap=32, backend="sharded")
    engine = WalkEngine.build(graph, plan, mesh=mesh)
    result = engine.run(seed=0)             # -> WalkResult(walks, stats)
    for r in engine.rounds(10, seed=0):     # FN-Multi streaming rounds
        train_on(r.walks)

Backends: ``reference`` (single-device jnp), ``sharded`` (shard_map over the
device mesh), ``fused`` (Pallas 2nd-order step kernel; interpret mode on CPU).
All three share one sampling implementation (``repro.engine.sampler``) and
produce bit-identical walks from the same plan + seed (tested).

Graphs churn: ``engine.update(deltas)`` applies a
``repro.data.DeltaBatch`` through the engine's ``GraphStore`` and patches
only the affected shards' device rows (``repro.engine.update``, DESIGN.md
§15), returning an :class:`~repro.engine.update.UpdateReport`. The legacy
``simulate_walks``/``distributed_walks`` shims (deprecated in PR 7) were
removed in PR 9.
"""
from repro.engine.plan import BACKENDS, WalkPlan, WalkResult, WalkStats
from repro.engine.sampler import Sampler

__all__ = ["BACKENDS", "Sampler", "UpdateReport", "WalkEngine", "WalkPlan",
           "WalkResult", "WalkStats", "round_seed"]


def __getattr__(name):
    # WalkEngine is resolved lazily: engine.engine imports the backend
    # modules, which themselves import repro.engine.sampler — eager import
    # here would make that a cycle.
    if name in ("WalkEngine", "round_seed"):
        from repro.engine import engine as _engine
        return getattr(_engine, name)
    if name == "UpdateReport":
        from repro.engine.update import UpdateReport
        return UpdateReport
    raise AttributeError(f"module 'repro.engine' has no attribute {name!r}")
