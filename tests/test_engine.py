"""Unified WalkEngine API: backend parity, stats, rounds, validation.

The tri-backend parity tests are the PR's core guarantee: one WalkPlan +
seed -> bit-identical walks on `reference`, `sharded` (fake devices, run in
a subprocess because jax locks the device count at first init), and `fused`
(Pallas kernel, interpret mode). This exercises the `walker_key` RNG
contract: keys are fold_in(fold_in(seed, walker), step) — a pure function of
(walker, step), never of device layout or backend.
"""
import subprocess
import sys
import textwrap
import warnings

import numpy as np
import pytest

from repro.core import rmat
from repro.core.graph import PaddedGraph
from repro.engine import WalkEngine, WalkPlan, WalkStats, round_seed


@pytest.mark.parametrize("mode", ["exact", "approx", "approx_always"])
def test_reference_fused_parity(skewed_graph, mode):
    """The fused (Pallas) backend implements the Sampler's exact draw
    verbatim — walks must be bit-identical to the reference backend."""
    kw = dict(p=0.5, q=2.0, length=8, mode=mode, approx_eps=5e-2, cap=24)
    ref = WalkEngine.build(skewed_graph, WalkPlan(backend="reference", **kw))
    fus = WalkEngine.build(skewed_graph, WalkPlan(backend="fused", **kw))
    r = ref.run(seed=11)
    f = fus.run(seed=11)
    assert np.array_equal(r.walks, f.walks)
    assert r.stats.backend == "reference" and f.stats.backend == "fused"
    assert f.stats.supersteps == 8 and f.stats.dropped == 0


PARITY_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import numpy as np, jax
    from repro.core import rmat
    from repro.engine import WalkEngine, WalkPlan

    g = rmat.skew(4, k=8, avg_degree=16, seed=3)
    walks = {{}}
    for backend in ("reference", "sharded", "fused"):
        plan = WalkPlan(p=0.5, q=2.0, length=10, mode="{mode}",
                        approx_eps=5e-2, cap=24, backend=backend)
        res = WalkEngine.build(g, plan).run(seed=5)
        assert res.stats.dropped == 0, res.stats
        walks[backend] = res.walks
    assert np.array_equal(walks["reference"], walks["sharded"]), "sharded"
    assert np.array_equal(walks["reference"], walks["fused"]), "fused"
    print("OK", walks["reference"].shape)
""")


def _run_subprocess(code):
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                            "HOME": "/root", "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout


@pytest.mark.slow
@pytest.mark.parametrize("mode", ["exact", "approx", "approx_always"])
def test_three_backend_parity(mode):
    """reference == sharded (2 fake devices) == fused, bit-identical, from
    one WalkPlan + seed."""
    _run_subprocess(PARITY_SCRIPT.format(mode=mode))


DROPS_SCRIPT = textwrap.dedent("""
    import os, warnings
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import numpy as np, jax
    from repro.core import rmat
    from repro.engine import WalkEngine, WalkPlan

    g = rmat.skew(4, k=8, avg_degree=16, seed=3)
    plan = WalkPlan(p=0.5, q=2.0, length=8, cap=24, backend="sharded",
                    capacity=1)             # starve the request exchange
    eng = WalkEngine.build(g, plan, mesh=None)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        res = eng.run(seed=0)
    assert res.stats.dropped > 0, res.stats
    assert any("dropped" in str(w.message) for w in caught), caught
    strict = WalkEngine.build(g, WalkPlan(p=0.5, q=2.0, length=8, cap=24,
                                          backend="sharded", capacity=1,
                                          strict_drops=True))
    try:
        strict.run(seed=0)
        raise SystemExit("strict_drops did not raise")
    except RuntimeError as e:
        assert "dropped" in str(e)
    print("OK", res.stats.dropped)
""")


@pytest.mark.slow
def test_stats_surface_drops_and_strict_flag():
    """Starved exchange capacity -> WalkStats.dropped > 0 + warning;
    strict_drops upgrades the warning to an error."""
    _run_subprocess(DROPS_SCRIPT)


@pytest.mark.parametrize("wk,length", [(32, 8), (7, 5), (5, 2)])
def test_fused_persistent_pipeline_parity(small_graph, wk, length):
    """WalkPlan.pipeline on the fused backend (exact FN-Base walks through
    the per-step Pallas kernel) — walks must stay bit-identical to the
    reference backend, including odd walker counts and the minimal length-2
    walk."""
    kw = dict(p=0.5, q=2.0, length=length)       # cap=None -> FN-Base
    ref = WalkEngine.build(small_graph, WalkPlan(backend="reference", **kw))
    fus = WalkEngine.build(small_graph,
                           WalkPlan(backend="fused", pipeline=True, **kw))
    starts = ((np.arange(wk) * 3) % small_graph.n).astype(np.int32)
    wid = np.arange(wk, dtype=np.int32)
    r = ref.run(starts=starts, seed=11, walker_ids=wid)
    f = fus.run(starts=starts, seed=11, walker_ids=wid)
    assert np.array_equal(r.walks, f.walks)


@pytest.mark.parametrize("mode", ["approx", "approx_always"])
def test_fused_pipeline_fallback_parity(skewed_graph, mode):
    """Hot-cache layout and approx sampling with the pipeline flag on the
    fused backend — still bit-identical to the reference."""
    kw = dict(p=0.5, q=2.0, length=6, mode=mode, approx_eps=5e-2, cap=24)
    ref = WalkEngine.build(skewed_graph, WalkPlan(backend="reference", **kw))
    fus = WalkEngine.build(skewed_graph,
                           WalkPlan(backend="fused", pipeline=True, **kw))
    assert np.array_equal(ref.run(seed=3).walks, fus.run(seed=3).walks)


def test_pipeline_flag_noop_on_reference(small_graph):
    """pipeline=True is a no-op for the reference backend: identical walks
    and zero overlap accounting (nothing is on the wire)."""
    kw = dict(p=0.5, q=2.0, length=6, cap=16)
    a = WalkEngine.build(small_graph, WalkPlan(**kw)).run(seed=4)
    b = WalkEngine.build(small_graph,
                         WalkPlan(pipeline=True, **kw)).run(seed=4)
    assert np.array_equal(a.walks, b.walks)
    assert b.stats.exposed_collective_bytes == 0
    assert b.stats.overlap_efficiency == 0.0


PIPELINE_PARITY_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import numpy as np, jax
    from repro.core import rmat
    from repro.engine import WalkEngine, WalkPlan

    g = rmat.skew(4, k=8, avg_degree=16, seed=3)
    half = g.n // 2
    # odd per-shard counts give shard-misaligned cohort splits
    # (5 walkers/shard -> cohorts of 3 and 2); length 2 exercises the
    # peeled-epilogue-only pipeline
    for per_shard, length in ((8, 10), (5, 7), (3, 2)):
        a = (np.arange(per_shard, dtype=np.int32) * 7) % half
        starts = np.concatenate([a, a + half])
        wid = np.arange(starts.shape[0], dtype=np.int32)
        kw = dict(p=0.5, q=2.0, length=length, mode="{mode}",
                  approx_eps=5e-2, cap=24, strict_drops=True)
        runs = {{}}
        for name, plan in (
                ("reference", WalkPlan(backend="reference", **kw)),
                ("barrier", WalkPlan(backend="sharded", **kw)),
                ("pipelined", WalkPlan(backend="sharded", pipeline=True,
                                       **kw))):
            runs[name] = WalkEngine.build(g, plan).run(
                starts=starts, seed=5, walker_ids=wid)
        for name in ("barrier", "pipelined"):
            assert np.array_equal(runs["reference"].walks,
                                  runs[name].walks), (per_shard, length,
                                                      name)
            assert runs[name].stats.dropped == 0
        pip, bar = runs["pipelined"].stats, runs["barrier"].stats
        if length >= 2:
            assert pip.exposed_collective_bytes < pip.collective_bytes, pip
            assert pip.overlap_efficiency > 0, pip
            assert pip.exposed_collective_bytes < \\
                bar.exposed_collective_bytes, (pip, bar)
        assert bar.exposed_collective_bytes == bar.collective_bytes
    print("OK")
""")


@pytest.mark.slow
@pytest.mark.parametrize("mode", ["exact", "approx"])
def test_pipelined_vs_barrier_parity(mode):
    """Tentpole lockdown: double-buffered cohort pipeline == barrier ==
    reference, bit-identical, under strict_drops — including odd per-shard
    walker counts (shard-misaligned cohort splits) and length 2."""
    _run_subprocess(PIPELINE_PARITY_SCRIPT.format(mode=mode))


PIPELINE_DROPS_SCRIPT = textwrap.dedent("""
    import os, warnings
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import numpy as np, jax
    from repro.core import rmat
    from repro.engine import WalkEngine, WalkPlan

    g = rmat.skew(4, k=8, avg_degree=16, seed=3)
    kw = dict(p=0.5, q=2.0, length=8, cap=24, capacity=1)  # starved
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        bar = WalkEngine.build(g, WalkPlan(backend="sharded", **kw)).run(
            seed=0)
        pip = WalkEngine.build(g, WalkPlan(backend="sharded", pipeline=True,
                                           **kw)).run(seed=0)
    # capacity is per-destination *per exchange*; a cohort's request rank is
    # <= its joint barrier rank, so pipelined drops form a subset of barrier
    # drops at equal capacity
    assert 0 < pip.stats.dropped <= bar.stats.dropped, (pip.stats,
                                                        bar.stats)
    assert pip.stats.exposed_collective_bytes < pip.stats.collective_bytes
    print("OK", bar.stats.dropped, pip.stats.dropped)
""")


@pytest.mark.slow
def test_pipelined_drops_bounded_by_barrier():
    """Starved exchange: the pipeline never drops more than the barrier
    loop at equal per-exchange capacity."""
    _run_subprocess(PIPELINE_DROPS_SCRIPT)


def test_rounds_stream_matches_individual_runs(small_graph):
    plan = WalkPlan(p=0.5, q=2.0, length=6, cap=16)
    eng = WalkEngine.build(small_graph, plan)
    streamed = [r.walks for r in eng.rounds(3, seed=9)]
    assert len(streamed) == 3
    for k, w in enumerate(streamed):
        direct = eng.run(seed=round_seed(9, k))
        assert np.array_equal(w, direct.walks), k
    # rounds differ from each other (seeds actually fold in the round)
    assert not np.array_equal(streamed[0], streamed[1])


def test_engine_stats_structure(small_graph):
    res = WalkEngine.build(small_graph, WalkPlan(length=4)).run(seed=0)
    assert isinstance(res.stats, WalkStats)
    assert res.stats.walkers == small_graph.n
    assert res.stats.collective_bytes == 0   # single-device: nothing on wire
    assert res.walks.shape == (small_graph.n, 4)


def test_build_accepts_prebuilt_padded_graph(small_graph):
    """A prebuilt PaddedGraph binds directly (no store, no repack) and
    walks identically to building from the CSR at the same plan."""
    pg = PaddedGraph.build(small_graph, cap=16)
    plan = WalkPlan(p=0.5, q=2.0, length=6, cap=16)
    direct = WalkEngine.build(pg, plan)
    assert direct.store is None
    via_csr = WalkEngine.build(small_graph, plan)
    assert via_csr.store is not None
    assert np.array_equal(direct.run(seed=3).walks,
                          via_csr.run(seed=3).walks)


def test_custom_starts_and_walker_ids(small_graph):
    """walker_ids default to start vertex ids; distinct explicit ids give
    distinct walks from the same start (the RNG folds in the walker id)."""
    eng = WalkEngine.build(small_graph, WalkPlan(length=5, cap=16))
    v = int(np.argmax(small_graph.deg))
    starts = np.full(8, v, np.int32)
    same = eng.run(starts=starts, seed=0)
    assert (same.walks == same.walks[0]).all()   # one walker id -> one walk
    distinct = eng.run(starts=starts, seed=0,
                       walker_ids=np.arange(8, dtype=np.int32))
    assert len({tuple(row) for row in distinct.walks}) > 1


def test_plan_validation():
    with pytest.raises(ValueError, match="backend"):
        WalkPlan(backend="gpu")
    with pytest.raises(ValueError, match="length"):
        WalkPlan(length=0)
    g = rmat.wec(6, avg_degree=8, seed=0)
    sharded_engine = WalkEngine.build(g, WalkPlan(length=4,
                                                  backend="sharded"))
    with pytest.raises(ValueError, match="analyze"):
        WalkEngine.build(g, WalkPlan(length=4)).analyze()
    del sharded_engine


def test_capacity_auto_validation():
    WalkPlan(capacity="auto")            # accepted
    WalkPlan(capacity=16)
    with pytest.raises(ValueError, match="capacity"):
        WalkPlan(capacity="turbo")
    with pytest.raises(ValueError, match="capacity"):
        WalkPlan(capacity=0)


def test_capacity_auto_headroom_skew5():
    """capacity='auto' on a Skew-5 graph: at least 2x headroom below the
    zero-drop worst case (capacity == walkers per shard), yet still above
    the max per-destination demand any exchange actually generates —
    checked by replaying reference walks through the NEIG slot accounting
    (a walker at a cold vertex owned by another shard consumes one request
    slot for that destination in its source shard's buffer)."""
    from repro.roofline.traffic import walk_auto_capacity

    g = rmat.skew(5, k=10, avg_degree=30, seed=0)
    cap, S, length = 32, 8, 12
    assert g.n % S == 0
    n_local = g.n // S
    deg = g.deg
    auto = walk_auto_capacity(deg, cap=cap, num_shards=S,
                              walkers_per_shard=n_local)
    worst = n_local                       # one slot per walker per dest
    assert auto * 2 <= worst, (auto, worst)

    plan = WalkPlan(backend="reference", cap=cap, length=length)
    walks = WalkEngine.build(g, plan).run(seed=0).walks
    is_hot = deg > cap                    # hot rows are replicated: no slot
    src = np.arange(g.n) // n_local       # walkers co-located with starts
    demand = 0
    for s in range(length - 1):           # superstep 0 reads the local row
        v = walks[:, s]
        need = (~is_hot[v]) & ((v // n_local) != src)
        counts = np.zeros((S, S), np.int64)
        np.add.at(counts, (src[need], v[need] // n_local), 1)
        demand = max(demand, int(counts.max()))
    assert 0 < demand <= auto, (demand, auto)


def test_capacity_auto_sharded_zero_drops():
    """End-to-end: a sharded engine built with capacity='auto' resolves to
    a concrete per-destination slot count and drops nothing."""
    g = rmat.skew(4, k=8, avg_degree=16, seed=3)
    plan = WalkPlan(length=8, cap=24, backend="sharded", capacity="auto",
                    strict_drops=True)
    eng = WalkEngine.build(g, plan)
    assert isinstance(eng.capacity, int) and 1 <= eng.capacity <= g.n
    res = eng.run(seed=5)
    assert res.stats.dropped == 0
