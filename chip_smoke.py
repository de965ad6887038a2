#!/usr/bin/env python3
"""Chip smoke test: the walk -> SGNS -> serve path on a TPU, end to end,
through the same library calls the launcher makes (``launch/train.py``).

    python3 chip_smoke.py             # one chip
    python3 chip_smoke.py --chips 4   # the multi-chip path on a 4-chip host

One chip: a WeC graph of 16,384 vertices (~330k edges), node2vec-paper
widths (p=1, q=0.5, walk length 80, d=128, window 10, 5 negatives, batch
1024), two FN-Multi rounds through ``WalkRoundRunner``. Checks: sampled walks
follow CSR edges; the ``fused`` backend (compiled Pallas step kernel) gives
walks bit-identical to ``reference``; streamed SGNS losses are finite and
fall; embeddings are finite with unit norm; the fused SGNS kernel matches the
jnp closed form; queued ``EmbeddingService`` answers are bit-identical to
per-request answers, with cache hits.

``--chips 4``: only the multi-chip path and what it is compared with — the
sharded walk backend on a 4-device ``rw`` mesh against one-device
``reference`` walks, and 4-shard SGNS tables against 1-shard tables.

Each phase prints one line: seconds, backend compiles (persistent-cache
hits among them) and the largest ``peak_bytes_in_use`` over the devices in
use. Any failed check raises. The last line of a passing run is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Without a TPU the script exits non-zero before any phase runs.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

GRAPH = "wec:k=14,deg=20,seed=0"
CUTS = ("graph cut from wec:k=16 (65,536 vertices) to k=14: on one v5e the "
        "k=16 reference walk took 191.6 s for 2 rounds and SGNS about 280 s; "
        "SGNS lr 0.0025: dense Adam at the default 0.025 diverges from k=14 "
        "up (loss rises)")
SEED = 0
LR = 0.0025
SAMPLE = 1024          # walks checked against the CSR and the fused backend
SGNS_B, SGNS_K = 1024, 5
REQUESTS = 300
SHARD_TRAIN_WALKERS = 4096   # walks of round 0 the 4-chip table check trains on


def node2vec_config():
    from repro.core.node2vec import Node2VecConfig
    return Node2VecConfig(p=1.0, q=0.5, walk_length=80, num_walks=2, dim=128,
                          window=10, negatives=5, batch_size=1024, lr=LR,
                          seed=SEED)


class Phases:
    """Per-phase wall time, backend compile count and device peak bytes."""

    def __init__(self, devices):
        import jax
        self.devices = devices
        self.compiles = 0
        self.cache_hits = 0

        def on_duration(event, _secs, **_kw):
            # recorded once per executable built, persistent-cache hits too
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1

        def on_event(event, **_kw):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def _peak(self):
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in self.devices]
        peaks = [p for p in peaks if p is not None]
        return f"{max(peaks)} B" if peaks else "not reported"

    @contextlib.contextmanager
    def phase(self, name: str):
        c0, h0 = self.compiles, self.cache_hits
        t0 = time.perf_counter()
        notes: list = []
        yield notes
        dt = time.perf_counter() - t0
        print(f"phase {name}: {dt:.3f} s, {self.compiles - c0} compiles "
              f"({self.cache_hits - h0} cache hits), peak {self._peak()}"
              + "".join(f"; {n}" for n in notes), flush=True)


def check(cond, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


def edge_check(g, starts: np.ndarray, walks: np.ndarray) -> int:
    """Every step start -> walks[:, 0] -> ... is a CSR edge, or a stay at a
    vertex with no edges. Returns the number of steps checked."""
    path = np.concatenate([starts[:, None], walks], axis=1).astype(np.int64)
    src, dst = path[:, :-1].ravel(), path[:, 1:].ravel()
    deg = g.deg.astype(np.int64)
    edge_src = np.repeat(np.arange(g.n, dtype=np.int64), deg)
    keys = np.sort(edge_src * g.n + g.col.astype(np.int64))
    want = src * g.n + dst
    pos = np.minimum(np.searchsorted(keys, want), len(keys) - 1)
    ok = (keys[pos] == want) | ((deg[src] == 0) & (src == dst))
    bad = np.nonzero(~ok)[0]
    check(bad.size == 0, f"{bad.size} walk steps are not edges, first "
          f"{src[bad[:1]]} -> {dst[bad[:1]]}")
    return int(src.size)


def run_one_chip(ph: Phases) -> None:
    import jax
    import jax.numpy as jnp

    from repro.data import open_graph
    from repro.engine import WalkEngine, round_seed
    from repro.kernels import ops
    from repro.kernels.sgns import sgns_row_grads
    from repro.runtime.fault_tolerance import WalkRoundRunner
    from repro.serve import EmbeddingService
    from repro.train import StreamingSGNSTrainer

    cfg = node2vec_config()
    rng = np.random.default_rng(SEED)
    with ph.phase("graph") as notes:
        g = open_graph(GRAPH).graph
        notes.append(f"{GRAPH}: n={g.n} m={g.m} maxdeg={g.max_degree}")

    with ph.phase("walks") as notes:
        runner = WalkRoundRunner(g, cfg)
        rounds = list(runner.rounds())
        check(len(rounds) == cfg.num_walks, "missing walk rounds")
        for w in rounds:
            check(w.shape == (g.n, cfg.walk_length), f"walks {w.shape}")
        notes.append(f"backend {runner.engine.plan.backend}, "
                     f"{len(rounds)} rounds of {rounds[0].shape}, "
                     f"dropped {runner.total_dropped}")

    sample = np.sort(rng.choice(g.n, SAMPLE, replace=False)).astype(np.int32)
    with ph.phase("walk_edges") as notes:
        steps = sum(edge_check(g, sample, w[sample]) for w in rounds)
        notes.append(f"{steps} steps of {SAMPLE} walks x {len(rounds)} "
                     f"rounds are CSR edges")

    with ph.phase("fused_walks") as notes:
        check(not ops._interpret(), "Pallas interpret mode on a TPU")
        fused = WalkEngine.build(
            g, dataclasses.replace(runner.engine.plan, backend="fused"))
        for r, w in enumerate(rounds):
            got = fused.run(starts=sample, seed=round_seed(cfg.seed, r),
                            walker_ids=sample).walks
            diff = int(np.sum(got != w[sample]))
            check(diff == 0, f"round {r}: fused walks differ from reference "
                  f"in {diff} of {got.size} steps")
        notes.append(f"fused == reference bit for bit on {SAMPLE} walks x "
                     f"{len(rounds)} rounds")

    with ph.phase("train") as notes:
        trainer = StreamingSGNSTrainer.from_config(g.n, cfg)
        emb, ts = trainer.train(iter(rounds))
        losses = trainer.loss_history()
        check(losses.size == ts.steps and ts.steps > 0, "no SGNS steps")
        check(bool(np.all(np.isfinite(losses))), "non-finite SGNS loss")
        tenth = max(losses.size // 10, 1)
        first, last = float(losses[:tenth].mean()), float(
            losses[-tenth:].mean())
        check(last < first, f"loss did not fall: {first} -> {last}")
        check(bool(np.all(np.isfinite(emb))), "non-finite embeddings")
        norms = np.linalg.norm(emb, axis=1)
        check(bool(np.allclose(norms, 1.0, atol=1e-5)),
              f"embedding norms in [{norms.min()}, {norms.max()}]")
        notes.append(f"{ts.steps} steps, {ts.pairs} pairs, loss first 10% "
                     f"{first!r} -> last 10% {last!r}, emb {emb.shape}")

    with ph.phase("sgns_kernel") as notes:
        idx = [jnp.asarray(rng.integers(0, g.n, s), jnp.int32)
               for s in ((SGNS_B,), (SGNS_B,), (SGNS_B, SGNS_K))]
        ci = trainer.params["emb_in"][idx[0]]
        po = trainer.params["emb_out"][idx[1]]
        no = trainer.params["emb_out"][idx[2]]
        valid = jnp.asarray(rng.random(SGNS_B) > 0.1, jnp.float32)
        want = sgns_row_grads(ci, po, no, valid, backend="jnp")
        got = sgns_row_grads(ci, po, no, valid, backend="fused")
        for name, a, b in zip(("loss", "g_ci", "g_po", "g_no"), got, want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-4, err_msg=name)
        notes.append(f"fused == jnp within 1e-4 at B={SGNS_B} D={cfg.dim} "
                     f"K={SGNS_K}, loss {float(got[0])!r}")

    with ph.phase("serve") as notes:
        svc = EmbeddingService(g, emb)
        top = np.argsort(-g.deg, kind="stable")[:32]
        pool = np.concatenate([top, rng.choice(g.n, 32, replace=False)])
        zipf = 1.0 / np.arange(1, pool.size + 1)
        nodes = rng.choice(pool, REQUESTS, p=zipf / zipf.sum())
        kinds = [("embed", 0), ("embed", 5), ("rank", 0)]
        asked, answers = {}, {}
        for i, node in enumerate(nodes):
            kind, window = kinds[i % len(kinds)]
            rid = svc.submit(kind, int(node), window=window, k=10)
            asked[rid] = (kind, int(node), window)
            if i % 25 == 24:
                answers.update((r.rid, r) for r in svc.drain())
        answers.update((r.rid, r) for r in svc.drain())
        check(len(answers) == len(asked), "requests left unanswered")
        for rid, (kind, node, window) in asked.items():
            r = answers[rid]
            check(not r.expired, f"request {rid} expired")
            if kind == "embed":
                same = np.array_equal(r.value, svc.embed([node], window)[0])
            else:
                ids, scores = svc.rank_neighbors([node], 10)
                same = (np.array_equal(r.value[0], ids[0])
                        and np.array_equal(r.value[1], scores[0]))
            check(same, f"queued {kind} (window {window}) of node {node} "
                  f"differs from the per-request answer")
        check(svc.cache.hits > 0, "no cache hits")
        notes.append(f"{len(asked)} queued answers == per-request answers, "
                     f"{svc.cache.hits} cache hits, "
                     f"{svc.stats().batches} batches")


def run_four_chips(ph: Phases) -> None:
    from repro.data import open_graph
    from repro.launch.mesh import make_rw_mesh, make_table_mesh
    from repro.runtime.fault_tolerance import WalkRoundRunner
    from repro.train import StreamingSGNSTrainer

    cfg = node2vec_config()
    mesh = make_rw_mesh()            # every device, in the chip's ring order
    shards = mesh.devices.size
    with ph.phase("graph") as notes:
        g = open_graph(GRAPH).graph
        notes.append(f"{GRAPH}: n={g.n} m={g.m} maxdeg={g.max_degree}; "
                     f"rw mesh {[d.id for d in mesh.devices]}")

    with ph.phase("reference_walks") as notes:
        ref = list(WalkRoundRunner(g, cfg).rounds())
        notes.append(f"{len(ref)} rounds of {ref[0].shape} on one device")

    with ph.phase("sharded_walks") as notes:
        runner = WalkRoundRunner(g, cfg, mesh=mesh)
        check(runner.engine.plan.backend == "sharded", "not sharded")
        got = list(runner.rounds())
        for r, (a, b) in enumerate(zip(got, ref)):
            diff = int(np.sum(a != b))
            check(diff == 0, f"round {r}: sharded walks differ from "
                  f"reference in {diff} of {a.size} steps")
        notes.append(f"sharded ({runner.engine.sg.num_shards} shards) == "
                     f"reference bit for bit, {len(got)} rounds, dropped "
                     f"{runner.total_dropped}")

    walks = ref[0][:SHARD_TRAIN_WALKERS]
    out = {}
    for n in (1, shards):
        with ph.phase(f"train_{n}_shard") as notes:
            trainer = StreamingSGNSTrainer.from_config(
                g.n, cfg, shard_tables=True,
                mesh=make_table_mesh(mesh, max_shards=n))
            emb, ts = trainer.train(iter([walks]))
            out[n] = (emb, trainer.loss_history())
            check(bool(np.all(np.isfinite(out[n][1]))), "non-finite loss")
            notes.append(f"{ts.shards} table shards, {ts.steps} steps on "
                         f"the first {SHARD_TRAIN_WALKERS} walks of round 0")
    with ph.phase("shard_parity") as notes:
        (e1, l1), (en, ln) = out[1], out[shards]
        check(np.array_equal(l1, ln), "losses differ across shard counts")
        check(np.array_equal(e1, en), "embeddings differ across shard counts")
        notes.append(f"{shards}-shard tables == 1-shard tables bit for bit "
                     f"({l1.size} losses, emb {e1.shape})")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the one-chip path; 4: only the multi-chip path")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {platform}",
              file=sys.stderr)
        return 1
    if args.chips > 1 and len(devices) != args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} devices", file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    kind = devices[0].device_kind
    print(f"device: platform={platform} kind={kind} count={len(devices)}; "
          f"compile cache {cache}", flush=True)
    print(f"cuts: {CUTS}", flush=True)

    ph = Phases(devices[:args.chips])
    if args.chips == 1:
        run_one_chip(ph)
    else:
        run_four_chips(ph)
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
