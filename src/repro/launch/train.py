"""End-to-end training launcher.

Two modes, selectable via ``--task``:

* ``node2vec``  — the paper's pipeline: RMAT graph -> distributed
  Fast-Node2Vec walks (FN-Multi rounds, checkpointed) -> SGNS embeddings.
  Stage 2 streams: the trainer optimizes round k-1 on device (resident
  walks, device pair-gen + alias negatives, ``--sgns-backend`` jnp/fused)
  while round k walks; ``--concat`` selects the generate-then-train
  host-corpus baseline.
* ``lm``        — train any assigned architecture (``--arch``) on the walk
  corpus (DeepWalk-style token streams) or on synthetic tokens, with the
  production sharding rules, checkpoint/restart, and (optionally) int8
  error-feedback gradient compression across data-parallel replicas.

This launcher runs real steps on whatever devices exist (the CPU backend in
tests, one TPU chip or a TPU host); the dry-run path (launch/dryrun.py)
covers the production mesh shapes. It keeps JAX's compile cache in
``JAX_COMPILATION_CACHE_DIR`` or else ``<checkout>/.jax_cache``.

Examples:
  PYTHONPATH=src python -m repro.launch.train --task node2vec --k 10 --rounds 2
  PYTHONPATH=src python -m repro.launch.train --task lm --arch yi-6b --smoke \
      --steps 20
"""
from __future__ import annotations

import argparse
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.checkpoint.checkpointer import Checkpointer
from repro.core.node2vec import Node2VecConfig, train_embeddings
from repro.data import open_graph
from repro.data.corpus import walks_to_lm_tokens
from repro.engine import WalkEngine, WalkPlan
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_rw_mesh
from repro.models import model as M
from repro.optim.optimizers import adamw, apply_updates
from repro.optim.grad_utils import clip_by_global_norm
from repro.runtime.fault_tolerance import WalkRoundRunner
from repro.train import StreamingSGNSTrainer


def graph_spec(args) -> str:
    """``--graph`` wins; otherwise the legacy --k/--avg-degree WeC knobs."""
    return args.graph or f"wec:k={args.k},deg={args.avg_degree:g}," \
                         f"seed={args.seed}"


def run_node2vec(args):
    g = open_graph(graph_spec(args), cache_dir=args.graph_cache).graph
    print(f"graph: {graph_spec(args)} -> n={g.n} m={g.m} "
          f"maxdeg={g.max_degree}")
    mesh = make_rw_mesh() if jax.device_count() > 1 else None
    n2v = Node2VecConfig(p=args.p, q=args.q, walk_length=args.walk_length,
                         num_walks=args.rounds, dim=args.dim,
                         window=args.window, negatives=args.negatives,
                         batch_size=args.sgns_batch,
                         sgns_backend=args.sgns_backend,
                         mode=args.mode, cap=args.cap, seed=args.seed)
    ckpt = Checkpointer(args.ckpt_dir)
    runner = WalkRoundRunner(g, n2v, mesh=mesh, checkpointer=ckpt)

    if args.concat:
        # generate-then-train baseline (the pre-streaming pipeline shape):
        # collect every round on host, then run the host corpus path
        walks = np.concatenate(list(runner.rounds()), axis=0)
        print(f"corpus: {walks.shape[0]} walks of {walks.shape[1]} steps")
        emb = train_embeddings(g, walks, n2v)
    else:
        # streamed stage 2: runner.rounds() dispatches round k+1 before
        # yielding round k, so the trainer optimizes k while k+1 walks —
        # the corpus never materializes on host
        trainer = StreamingSGNSTrainer.from_config(
            g.n, n2v, shard_tables=args.shard_tables, mesh=mesh)
        emb, ts = trainer.train(runner.rounds())
        print(f"train[{ts.backend}]: {ts.rounds} rounds, {ts.steps} steps, "
              f"{ts.pairs} pairs in {ts.wall_seconds:.1f}s "
              f"({ts.pairs_per_sec:.0f} pairs/s, "
              f"{ts.tokens_per_sec:.0f} tokens/s)")
        print(f"walk_wait {ts.walk_wait_seconds:.2f}s, "
              f"h2d {ts.h2d_bytes} B")
        if ts.shards > 1:
            print(f"shards: {ts.shards} table shards, "
                  f"collective {ts.collective_bytes} B "
                  f"({ts.exposed_collective_bytes} B exposed)")
    out = os.path.join(args.ckpt_dir, "embeddings.npy")
    np.save(out, emb)
    print(f"embeddings: {emb.shape} -> {out}")


def run_lm(args):
    cfg = (configs.smoke_config(args.arch) if args.smoke
           else configs.get_config(args.arch))
    key = jax.random.PRNGKey(args.seed)
    params = M.init_params(cfg, key)
    opt = adamw(lr=args.lr)
    opt_state = opt.init(params)
    ckpt = Checkpointer(args.ckpt_dir)
    start_step = 0
    if ckpt.latest_step() is not None:
        (params, opt_state), meta = ckpt.restore((params, opt_state))
        start_step = meta["step"]
        print(f"resumed from step {start_step}")

    # corpus: walks over a small graph -> token sequences
    g = open_graph(args.graph, cache_dir=args.graph_cache).graph \
        if args.graph \
        else open_graph(f"wec:k={max(args.k, 8)},deg=10,seed={args.seed}").graph
    walks = WalkEngine.build(
        g, WalkPlan(p=1.0, q=1.0, length=64)).run(seed=args.seed).walks
    seq = args.seq
    tokens = walks_to_lm_tokens(walks % cfg.vocab, seq + 1)
    print(f"corpus: {tokens.shape[0]} sequences of {seq + 1} tokens")

    @jax.jit
    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(
            lambda p: M.loss_fn(cfg, p, batch))(params)
        grads, gnorm = clip_by_global_norm(grads, 1.0)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = apply_updates(params, updates)
        return params, opt_state, loss, gnorm

    bsz = args.batch
    rng = np.random.default_rng(args.seed)
    t0 = time.time()
    for step in range(start_step, args.steps):
        idx = rng.integers(0, tokens.shape[0], size=bsz)
        seqs = tokens[idx]
        batch = {"tokens": jnp.asarray(seqs[:, :-1]),
                 "labels": jnp.asarray(seqs[:, 1:])}
        if cfg.enc_layers:
            batch["frames"] = jnp.zeros(
                (bsz, cfg.num_audio_frames, cfg.d_model), jnp.float32)
        if cfg.cross_every and not cfg.enc_layers:
            batch["patches"] = jnp.zeros(
                (bsz, cfg.num_image_tokens, cfg.d_model), jnp.float32)
        params, opt_state, loss, gnorm = train_step(params, opt_state, batch)
        if step % args.log_every == 0 or step == args.steps - 1:
            dt = time.time() - t0
            print(f"step {step:5d} loss {float(loss):.4f} "
                  f"gnorm {float(gnorm):.3f} ({dt:.1f}s)")
        if args.ckpt_every and step and step % args.ckpt_every == 0:
            ckpt.save(step, (params, opt_state), blocking=False)
    ckpt.save(args.steps, (params, opt_state))
    print("done; final loss", float(loss))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--task", choices=["node2vec", "lm"], default="node2vec")
    ap.add_argument("--arch", default="yi-6b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-sized)")
    ap.add_argument("--graph", default=None,
                    help="dataset spec (repro.data.open_graph): "
                         "'wec:k=12,deg=30', 'edgelist:/path/edges.txt', "
                         "'csr:/path/cache_dir', ... (overrides --k)")
    ap.add_argument("--graph-cache", default=None,
                    help="CSR cache dir for edgelist specs (build once, "
                         "memmap thereafter)")
    ap.add_argument("--k", type=int, default=10, help="RMAT log2 vertices")
    ap.add_argument("--avg-degree", type=float, default=20)
    ap.add_argument("--p", type=float, default=1.0)
    ap.add_argument("--q", type=float, default=1.0)
    ap.add_argument("--walk-length", type=int, default=80)
    ap.add_argument("--rounds", type=int, default=2)
    ap.add_argument("--mode", choices=["exact", "approx"], default="exact")
    ap.add_argument("--cap", type=int, default=None)
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--window", type=int, default=10)
    ap.add_argument("--negatives", type=int, default=5)
    ap.add_argument("--sgns-batch", type=int, default=1024,
                    help="SGNS batch size (fixed-shape device batches)")
    ap.add_argument("--sgns-backend", choices=["jnp", "fused"],
                    default="jnp",
                    help="stage-2 gradient backend: jnp autodiff or the "
                         "fused Pallas SGNS kernel (interpret mode on CPU)")
    ap.add_argument("--concat", action="store_true",
                    help="generate-then-train baseline instead of the "
                         "streamed on-device trainer")
    ap.add_argument("--shard-tables", action="store_true",
                    help="range-partition the SGNS tables + Adam moments "
                         "over the rw mesh (sparse-collective sharded "
                         "training; DESIGN.md §16). Bit-identical across "
                         "shard counts; needs >1 device to actually shard")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=5)
    args = ap.parse_args()
    enable_compile_cache()
    if args.task == "node2vec":
        run_node2vec(args)
    else:
        run_lm(args)


if __name__ == "__main__":
    main()
