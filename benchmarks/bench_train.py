"""Streamed walk→SGNS training benchmark (DESIGN.md §14).

Battery mode (``run()``, wired into ``benchmarks.run``) prints the usual
``name,us_per_call,derived`` CSV rows: end-to-end walk+train wall time for
the streamed on-device pipeline vs. the two generate-then-train baselines
(host corpus path, and the same device trainer without overlap), plus the
fused-kernel vs jnp per-step ratio.

Smoke mode (``--smoke [out.json]``) merges **ratio** metrics into the
``BENCH_smoke.json`` schema, gated by ``scripts/bench_compare.py --strict
--only train_`` (``make train-smoke``):

* ``train_stream_over_concat_us``   — end-to-end wall ratio of the streamed
                                      pipeline over generate-then-train
                                      through the host corpus path
                                      (interleaved runs; machine load
                                      cancels; < 1 means streaming wins).
* ``train_fused_over_jnp_step_us``  — per-train-step wall ratio of the
                                      fused Pallas SGNS backend over jnp
                                      autodiff (interpret mode on CPU, so
                                      > 1 here; on TPU the kernel is the
                                      arithmetic-intensity floor).
* ``train_shard_pairs_ratio``       — sharded-trainer (2 table shards)
                                      pairs/sec over the dense single-device
                                      trainer on the same rounds, measured
                                      in a 2-virtual-device subprocess on a
                                      vocabulary whose tables fit either
                                      way. The ISSUE-10 acceptance gate
                                      asserts >= 1.5x: the win is lazy
                                      row-Adam's O(rows·D) step vs dense
                                      Adam's O(V·D), not fake-device
                                      parallelism (one physical core here).
* ``train_shard_bit_identical``     — 1.0 iff the sharded trainer at 2
                                      shards reproduces the 1-shard run bit
                                      for bit (embeddings + loss history),
                                      jnp and fused backends.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import jax
import numpy as np

from benchmarks.common import graph, row, time_fn
from repro.core.node2vec import Node2VecConfig, generate_walks, \
    train_embeddings
from repro.core.skipgram import SGNSConfig, init_params, train_step
from repro.optim.optimizers import adam
from repro.runtime.fault_tolerance import WalkRoundRunner
from repro.train import StreamingSGNSTrainer

SPEC = "wec:k=9,deg=12,seed=1"
CFG = dict(p=0.5, q=2.0, walk_length=16, num_walks=3, window=5, dim=32,
           negatives=5, batch_size=512, seed=0)


def _cfg(**kw) -> Node2VecConfig:
    return Node2VecConfig(**{**CFG, **kw})


def _run_stream(g, cfg, backend: str = "jnp"):
    """Streamed pipeline: trainer consumes the runner's dispatch-ahead
    rounds (round k+1 walks while round k trains)."""
    trainer = StreamingSGNSTrainer.from_config(g.n, cfg,
                                               sgns_backend=backend,
                                               record_loss=False)
    t0 = time.perf_counter()
    _, stats = trainer.train(WalkRoundRunner(g, cfg).rounds())
    return time.perf_counter() - t0, stats


def _run_concat_host(g, cfg):
    """Generate-then-train through the host corpus path (the pre-streaming
    pipeline: np corpus, np pair expansion, per-batch H2D staging)."""
    t0 = time.perf_counter()
    walks = generate_walks(g, cfg)
    train_embeddings(g, walks, cfg)
    return time.perf_counter() - t0


def _run_concat_dev(g, cfg):
    """Generate-then-train through the *same* device trainer (no overlap):
    isolates the overlap win from the on-device-corpus win."""
    trainer = StreamingSGNSTrainer.from_config(g.n, cfg, record_loss=False)
    t0 = time.perf_counter()
    rounds = list(WalkRoundRunner(g, cfg).rounds())
    _, stats = trainer.train(iter(rounds))
    return time.perf_counter() - t0, stats


def _step_us(backend: str, cfg) -> float:
    """Per-train-step wall time for one fixed batch (5-step chain per call
    so the donated-buffer contract is exercised, init cost amortized)."""
    scfg = SGNSConfig(vocab=512, dim=cfg.dim, negatives=cfg.negatives)
    opt = adam(cfg.lr)
    rng = np.random.default_rng(0)
    batch = {
        "center": np.asarray(rng.integers(0, 512, cfg.batch_size), np.int32),
        "pos": np.asarray(rng.integers(0, 512, cfg.batch_size), np.int32),
        "neg": np.asarray(
            rng.integers(0, 512, (cfg.batch_size, cfg.negatives)), np.int32),
        "valid": np.ones(cfg.batch_size, np.float32),
    }

    def chain():
        params = init_params(scfg, jax.random.PRNGKey(0))
        state = opt.init(params)
        for _ in range(5):
            params, state, loss = train_step(params, state, batch, opt,
                                             backend)
        return loss

    return time_fn(chain, warmup=1, iters=3) / 5


# Runs in a 2-virtual-device subprocess (XLA_FLAGS in the parent env):
# times the dense single-device trainer vs the sharded trainer at 1 and 2
# table shards on identical synthetic rounds, and checks S=1 vs S=2
# bit-identity (embeddings + loss history, jnp and fused) on a small odd
# vocabulary so the pad-row path is exercised. Emits one "RESULT {json}"
# line. V=65536 makes dense Adam's O(V*D) per-step table work dominate,
# which is exactly the cost the lazy row-Adam path avoids.
_SHARD_SCRIPT = r"""
import json, sys, time
import numpy as np
import jax

from repro.launch.mesh import make_table_mesh
from repro.train import StreamingSGNSTrainer, train_epoch_sharded

assert jax.device_count() >= 2, jax.devices()

V, D, B, K, WINDOW = 65536, 64, 1024, 5, 4
ROUNDS, WALKERS, STEPS = 3, 1024, 9
rng = np.random.default_rng(0)
rounds = [np.asarray(rng.integers(0, V, (WALKERS, STEPS)), np.int32)
          for _ in range(ROUNDS)]


def trainer(**kw):
    return StreamingSGNSTrainer(V, dim=D, window=WINDOW, negatives=K,
                                batch_size=B, record_loss=False, **kw)


def timed(make):
    t0 = time.perf_counter()
    _, st = make().train(iter(rounds))
    return time.perf_counter() - t0, st


mk_dense = lambda: trainer()
mk_s1 = lambda: trainer(shard_tables=True, mesh=make_table_mesh(max_shards=1))
mk_s2 = lambda: trainer(shard_tables=True, mesh=make_table_mesh(max_shards=2))

for mk in (mk_dense, mk_s1, mk_s2):   # warmup: compile every program
    timed(mk)
t_d, t_1, t_2, st2 = [], [], [], None
for _ in range(2):                    # interleaved passes; load cancels
    t_d.append(timed(mk_dense)[0])
    t_1.append(timed(mk_s1)[0])
    dt, st2 = timed(mk_s2)
    t_2.append(dt)
pairs = st2.pairs

# bit-identity battery: small odd vocab -> pad row live on both tables
bit = 1.0
for backend in ("jnp", "fused"):
    embs, hists = [], []
    for s in (1, 2):
        tr = StreamingSGNSTrainer(
            257, dim=16, window=3, negatives=3, batch_size=256,
            sgns_backend=backend, shard_tables=True,
            mesh=make_table_mesh(max_shards=s))
        rng_b = np.random.default_rng(7)
        emb, _ = tr.train(iter(
            np.asarray(rng_b.integers(0, 257, (64, 9)), np.int32)
            for _ in range(2)))
        embs.append(np.asarray(emb))
        hists.append(tr.loss_history())
    if embs[0].tobytes() != embs[1].tobytes() or \
            hists[0].tobytes() != hists[1].tobytes():
        bit = 0.0
        print(f"BIT MISMATCH backend={backend}", file=sys.stderr)

print("RESULT " + json.dumps({
    "pps_dense": pairs / min(t_d),
    "pps_shard1": pairs / min(t_1),
    "pps_shard2": pairs / min(t_2),
    "bit_identical": bit,
    "collective_bytes": st2.collective_bytes,
    "compiles": train_epoch_sharded._cache_size(),
}))
"""


def _shard_subprocess() -> dict:
    """Run ``_SHARD_SCRIPT`` under 2 virtual CPU devices (pinned to the CPU:
    a parent that has touched JAX holds any accelerator); raises on
    failure."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in ("src", env.get("PYTHONPATH", "")) if p)
    proc = subprocess.run([sys.executable, "-c", _SHARD_SCRIPT],
                          capture_output=True, text=True, env=env)
    lines = [ln for ln in proc.stdout.splitlines()
             if ln.startswith("RESULT ")]
    if proc.returncode != 0 or not lines:
        raise RuntimeError(f"sharded 2-device subprocess failed "
                           f"(rc {proc.returncode}):\n{proc.stderr[-2000:]}")
    return json.loads(lines[-1][len("RESULT "):])


def _interleaved(g, cfg):
    """stream / concat-host / concat-dev, two interleaved passes each (min
    of the post-warmup passes; load cancels in the ratios)."""
    _run_stream(g, cfg)            # warmup: compiles walk + train programs
    _run_concat_host(g, cfg)
    _run_concat_dev(g, cfg)
    t_s, t_ch, t_cd, stats = [], [], [], None
    for _ in range(2):
        dt, stats = _run_stream(g, cfg)
        t_s.append(dt)
        t_ch.append(_run_concat_host(g, cfg))
        t_cd.append(_run_concat_dev(g, cfg)[0])
    return min(t_s), min(t_ch), min(t_cd), stats


def run() -> None:
    g = graph(SPEC)
    cfg = _cfg()
    t_s, t_ch, t_cd, st = _interleaved(g, cfg)
    row("train_stream", t_s * 1e6,
        f"pairs_per_sec={st.pairs / t_s:.0f};"
        f"tokens_per_sec={st.tokens / t_s:.0f}")
    row("train_concat_host", t_ch * 1e6,
        f"stream_speedup={t_ch / t_s:.2f}x")
    row("train_concat_dev", t_cd * 1e6,
        f"overlap_only_speedup={t_cd / t_s:.2f}x")
    jnp_us = _step_us("jnp", cfg)
    fused_us = _step_us("fused", cfg)
    row("train_step_jnp", jnp_us, "")
    row("train_step_fused", fused_us,
        f"fused_over_jnp={fused_us / jnp_us:.2f}x (interpret mode on CPU)")
    res = _shard_subprocess()
    row("train_shard2", 0,
        f"pairs_per_sec={res['pps_shard2']:.0f};"
        f"over_dense={res['pps_shard2'] / res['pps_dense']:.2f}x;"
        f"bit_identical={res['bit_identical']:.0f};"
        f"collective_bytes={res['collective_bytes']}")


def smoke_metrics(info: dict) -> dict:
    """The ratio metrics described in the module docstring."""
    g = graph(SPEC)
    cfg = _cfg()
    t_s, t_ch, t_cd, st = _interleaved(g, cfg)
    info.update({
        "train_stream_s": t_s,
        "train_concat_host_s": t_ch,
        "train_concat_dev_s": t_cd,
        "train_pairs": st.pairs,
        "train_steps": st.steps,
        "train_pairs_per_sec": st.pairs / t_s,
        "train_tokens_per_sec": st.tokens / t_s,
    })
    jnp_us = _step_us("jnp", cfg)
    fused_us = _step_us("fused", cfg)
    info["train_step_jnp_us"] = jnp_us
    info["train_step_fused_us"] = fused_us
    res = _shard_subprocess()
    ratio = res["pps_shard2"] / res["pps_dense"]
    # ISSUE-10 acceptance gates, enforced here (not just by bench_compare
    # drift): the sharded trainer must reproduce the 1-shard run bit for
    # bit AND beat the dense trainer's pairs/sec by >= 1.5x on 2 devices.
    assert res["bit_identical"] == 1.0, "sharded run not bit-identical"
    assert ratio >= 1.5, f"shard2/dense pairs/sec {ratio:.2f} < 1.5"
    info.update({
        "train_shard_pps_dense": res["pps_dense"],
        "train_shard_pps_shard1": res["pps_shard1"],
        "train_shard_pps_shard2": res["pps_shard2"],
        "train_shard_collective_bytes": res["collective_bytes"],
        "train_shard_epoch_compiles": res["compiles"],
    })
    return {
        "train_stream_over_concat_us": t_s / t_ch,
        "train_fused_over_jnp_step_us": fused_us / jnp_us,
        "train_shard_pairs_ratio": ratio,
        "train_shard_bit_identical": res["bit_identical"],
    }


def run_smoke(out_path: str = "BENCH_smoke.json") -> dict:
    """Merge train metrics into ``out_path`` (existing walk/serve metrics,
    if the file is already there, are preserved)."""
    try:
        with open(out_path) as f:
            doc = json.load(f)
    except FileNotFoundError:
        doc = {"version": 1, "metrics": {}, "info": {}}
    info = doc.setdefault("info", {})
    metrics = smoke_metrics(info)
    doc.setdefault("metrics", {}).update(metrics)
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
    for k in sorted(metrics):
        print(f"{k} = {metrics[k]:.4g}")
    print(f"wrote {out_path}")
    return doc


if __name__ == "__main__":
    if "--smoke" in sys.argv:
        args = [a for a in sys.argv[1:] if a != "--smoke"]
        run_smoke(args[0] if args else "BENCH_smoke.json")
    else:
        run()
