"""Graph-embedding serving driver: the `repro.serve` counterpart of
``launch/serve.py`` (which serves the LM side).

Builds the full story end to end: dataset spec -> walks -> SGNS embeddings
-> resident :class:`~repro.serve.EmbeddingService` -> synthetic Zipf traffic
replayed against the real clock -> a ``ServeStats`` report (p50/p99 latency,
QPS, cache hit rate, batch occupancy).

  PYTHONPATH=src python -m repro.launch.serve_graph --smoke
  PYTHONPATH=src python -m repro.launch.serve_graph \
      --graph "rmat:k=14,deg=16,relabel=degree" --requests 20000 --alpha 1.2
"""
from __future__ import annotations

import argparse
import time

import numpy as np

from repro.core.node2vec import Node2VecConfig
from repro.data import open_graph
from repro.engine import WalkPlan
from repro.launch.compile_cache import enable_compile_cache
from repro.serve import EmbeddingService, synthetic_trace


def build_service(args) -> EmbeddingService:
    store = open_graph(args.graph, cache_dir=args.graph_cache)
    g = store.graph
    print(f"graph: {args.graph} -> n={g.n} m={g.m} maxdeg={g.max_degree}")
    cfg = Node2VecConfig(walk_length=args.walk_length, num_walks=args.rounds,
                         dim=args.dim, epochs=1, batch_size=4096,
                         cap=args.cap, seed=args.seed)
    t0 = time.time()
    svc = EmbeddingService.from_node2vec(
        store, cfg, plan=WalkPlan(backend="reference", cap=args.cap),
        cache_size=args.cache_size, linger_s=args.linger_ms * 1e-3,
        margin_s=args.margin_ms * 1e-3, walk_seed=args.seed)
    print(f"walk+SGNS+residency build: {time.time() - t0:.1f}s "
          f"(dim={args.dim}, cache={args.cache_size})")
    return svc


def replay(svc: EmbeddingService, args) -> None:
    trace = synthetic_trace(svc.graph.n, args.requests, alpha=args.alpha,
                            rank_share=args.rank_share, qps=args.qps,
                            deadline_s=args.deadline_ms * 1e-3,
                            seed=args.seed)
    # warm every bucket's jit cache so the report measures serving, not
    # compilation (and expiries mean real starvation, not compile stalls)
    for b in svc.batcher.buckets:
        nodes = [0] * b
        svc.embed(nodes, window=0)
        if args.window:
            svc.embed(nodes, window=args.window)
        svc.rank_neighbors(nodes, args.k)
    t0 = time.time()
    for ev in trace:
        svc.submit(ev.kind, ev.node, window=args.window, k=args.k,
                   deadline_s=ev.deadline_s)
        svc.pump()
    svc.drain()
    wall = time.time() - t0
    st = svc.stats()
    print(f"\ntrace: {args.requests} reqs, zipf a={args.alpha}, "
          f"rank share {args.rank_share:.0%}, deadline "
          f"{args.deadline_ms:.0f}ms, wall {wall:.2f}s")
    print(f"{'metric':<22}{'value':>14}")
    for name, val in [
        ("requests", f"{st.requests}"),
        ("expired", f"{st.expired}"),
        ("batches", f"{st.batches}"),
        ("p50 latency (us)", f"{st.p50_latency_us:.0f}"),
        ("p99 latency (us)", f"{st.p99_latency_us:.0f}"),
        ("QPS", f"{st.qps:.0f}"),
        ("cache hit rate", f"{st.cache_hit_rate:.3f}"),
        ("batch occupancy", f"{st.batch_occupancy:.3f}"),
    ]:
        print(f"{name:<22}{val:>14}")
    if st.requests + st.expired < args.requests:
        raise SystemExit("lost responses: "
                         f"{st.requests + st.expired} < {args.requests}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true", default=True,
                    help="small graph + short trace (default)")
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--graph", default=None,
                    help="dataset spec (repro.data.ingest registry)")
    ap.add_argument("--graph-cache", default=None)
    ap.add_argument("--dim", type=int, default=None)
    ap.add_argument("--cap", type=int, default=32,
                    help="FN-Cache cold row width (hot set = deg > cap)")
    ap.add_argument("--walk-length", type=int, default=20)
    ap.add_argument("--rounds", type=int, default=2)
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--alpha", type=float, default=1.2,
                    help="Zipf exponent of query popularity")
    ap.add_argument("--rank-share", type=float, default=0.5)
    ap.add_argument("--qps", type=float, default=20_000.0,
                    help="trace arrival rate (closed-loop replay)")
    ap.add_argument("--deadline-ms", type=float, default=50.0)
    ap.add_argument("--window", type=int, default=0,
                    help="walk-averaged embed context window (0 = gather)")
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--cache-size", type=int, default=512)
    ap.add_argument("--linger-ms", type=float, default=0.2)
    ap.add_argument("--margin-ms", type=float, default=1.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    if args.graph is None:
        args.graph = ("skew:s=4,k=9,deg=20,seed=3,relabel=degree"
                      if args.smoke else
                      "rmat:k=16,deg=16,seed=0,relabel=degree")
    if args.dim is None:
        args.dim = 64 if args.smoke else 128
    if args.requests is None:
        args.requests = 2000 if args.smoke else 50_000

    svc = build_service(args)
    replay(svc, args)


if __name__ == "__main__":
    main()
