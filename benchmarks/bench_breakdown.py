"""Paper Fig. 1 — Node2Vec runtime breakdown: random-walk stage vs SGNS
optimization stage. The paper reports 98.8% in the walk stage for
Spark-Node2Vec; our walk engine is far faster, so the split shifts — the
derived column reports the walk share we measure.

Also reports the superstep-pipeline overlap breakdown on the Skew-5
synthetic (EXPERIMENTS.md §Overlap): analytic exposed-vs-total NEIG bytes
for barrier vs double-buffered pipelined mode at 8 shards, plus measured
``WalkStats`` from a 2-virtual-device subprocess run of both modes."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np

from benchmarks.common import row
from benchmarks import common
from repro.core.node2vec import Node2VecConfig, train_embeddings
from repro.engine import WalkEngine
from repro.roofline.traffic import walk_overlap_model

SKEW5_SPEC = "skew:s=5,k=9,deg=20,seed=3"

_MEASURED_SCRIPT = r"""
import json, sys
import numpy as np, jax
from benchmarks import common
from repro.engine import WalkEngine, WalkPlan
from repro.launch.mesh import make_rw_mesh

g = common.graph(sys.argv[1])
mesh = make_rw_mesh(None)
out = {}
for name, pipe in (("barrier", False), ("pipelined", True)):
    plan = WalkPlan(p=1.0, q=2.0, length=20, cap=24, backend="sharded",
                    pipeline=pipe)
    res = WalkEngine.build(g, plan, mesh=mesh).run(seed=0)
    out[name] = {"exposed": res.stats.exposed_collective_bytes,
                 "total": res.stats.collective_bytes,
                 "efficiency": res.stats.overlap_efficiency,
                 "dropped": res.stats.dropped}
print("RESULT " + json.dumps(out))
"""


def run_overlap():
    g = common.graph(SKEW5_SPEC)
    shards, cap, length = 8, 24, 20
    n_local = -(-g.n // shards)
    barrier = walk_overlap_model(shards, n_local, cap, length,
                                 walkers_per_shard=n_local, pipeline=False)
    pipe = walk_overlap_model(shards, (n_local + 1) // 2, cap, length,
                              walkers_per_shard=n_local, pipeline=True)
    row("overlap_barrier_exposed_bytes", barrier["exposed_bytes"],
        f"total={barrier['total_bytes']} eff={barrier['efficiency']:.4f}")
    row("overlap_pipelined_exposed_bytes", pipe["exposed_bytes"],
        f"total={pipe['total_bytes']} eff={pipe['efficiency']:.4f} "
        f"exposed_over_barrier="
        f"{pipe['exposed_bytes'] / barrier['exposed_bytes']:.4f}")
    # measured WalkStats on 2 virtual devices (subprocess: XLA device count
    # is process-global, same pattern as the sharded parity tests; pinned
    # to the CPU, since a parent that has touched JAX holds any accelerator)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in ("src", env.get("PYTHONPATH", "")) if p)
    proc = subprocess.run(
        [sys.executable, "-c", _MEASURED_SCRIPT, SKEW5_SPEC],
        capture_output=True, text=True, env=env, timeout=600)
    if proc.returncode:
        raise RuntimeError(f"2-device subprocess failed (rc "
                           f"{proc.returncode}):\n{proc.stderr[-2000:]}")
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULT ")][-1]
    meas = json.loads(line[len("RESULT "):])
    for name in ("barrier", "pipelined"):
        m = meas[name]
        row(f"overlap_measured_{name}_exposed_bytes", m["exposed"],
            f"total={m['total']} eff={m['efficiency']:.4f} "
            f"dropped={m['dropped']}")


def run():
    g = common.graph("wec:k=10,deg=20,seed=0")
    cfg = Node2VecConfig(p=1.0, q=2.0, walk_length=40, num_walks=2, dim=32,
                         window=5, epochs=1, batch_size=4096)
    eng = WalkEngine.build(g, cfg.plan())
    # warmup compile
    eng.run(seed=0)
    t0 = time.perf_counter()
    walks = [r.walks for r in eng.rounds(cfg.num_walks, seed=cfg.seed)]
    t_walk = time.perf_counter() - t0
    walks = np.concatenate(walks, 0)
    t0 = time.perf_counter()
    train_embeddings(g, walks, cfg)
    t_sgd = time.perf_counter() - t0
    share = t_walk / (t_walk + t_sgd)
    row("breakdown_walk", t_walk * 1e6, f"walk_share={share:.3f}")
    row("breakdown_sgns", t_sgd * 1e6,
        f"paper_spark_walk_share=0.988")
    run_overlap()


if __name__ == "__main__":
    run()
