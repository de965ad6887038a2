"""The walk half that the traffic kinds (``chipbench/kinds/``) share: the
configuration's graph in the program's walk engine, rounds of walkers on
it, and the check of their walks (:class:`Walker`); and what a kind's
``prepare`` returns (:class:`Prepared`).

Each round's starts are the next block of a permutation of the vertices
drawn from the seed, and walker id = start id, so every seed does the same
amount of work.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable

import numpy as np

from . import reference, work
from .graphs import load_graph

LAW_SAMPLE_STEPS = 20000       # steps held against the transition law
EDGE_SAMPLE_WALKS = 32768      # walks of the window held against the graph


@dataclasses.dataclass
class Prepared:
    info: dict
    window: Callable       # (measure: dict) -> rounds
    check: Callable        # (rounds) -> numbers compared, attempted, failed
    counts: Callable       # (rounds) -> work counts, for the metric readers
    rates: Callable        # (counts, window_s) -> {end-to-end metric: value}


def round_seeds(seed: int, count: int) -> np.ndarray:
    """31-bit seeds for ``count`` rounds, from any whole ``seed``."""
    ss = np.random.SeedSequence(seed % (1 << 64))
    return (ss.generate_state(count, np.uint32) >> 1).astype(np.int64)


class Walker:
    """The configuration's graph in the program's walk engine, and rounds of
    ``walkers`` walkers on it. ``control`` walks with q = 1, a first-order
    walk that breaks the configured law, while :meth:`check` still holds it
    to the configured q."""

    def __init__(self, cfg: dict, walkers: int, seed: int, span, *,
                 control: bool = False):
        import jax
        from repro.core.graph import CSRGraph
        from repro.engine import WalkEngine, WalkPlan

        self.cfg, self.seed = cfg, seed
        with span("setup.graph_load"):
            self.g = g = load_graph(cfg)
        plan = WalkPlan(p=cfg["p"], q=1.0 if control else cfg["q"],
                        length=cfg["walk_length"], mode=cfg["mode"],
                        backend=cfg["backend"], cap=cfg["cap"])
        t0 = time.perf_counter()
        with span("setup.graph_build"):
            self.engine = WalkEngine.build(
                CSRGraph(n=g.n, row_ptr=g.row_ptr, col=g.col, wgt=g.wgt),
                plan)
            jax.block_until_ready(self.engine.pg)
        self.graph_build_s = time.perf_counter() - t0
        self.walkers = min(int(walkers), g.n)
        self.order = np.random.default_rng(seed % (1 << 64)).permutation(g.n)
        self.seeds = round_seeds(seed, 1 << 16)
        self.expected = (self.walkers, cfg["walk_length"])

    def starts(self, r: int) -> np.ndarray:
        lo = (r * self.walkers) % self.g.n
        return np.take(self.order, np.arange(lo, lo + self.walkers),
                       mode="wrap").astype(np.int32)

    def run(self, r: int):
        """Round ``r``: (starts, walks on the host)."""
        s = self.starts(r)
        return s, self.engine.run(starts=s, seed=int(self.seeds[r]),
                                  walker_ids=s).walks

    def complete(self, rounds):
        """The rounds whose walks all came back."""
        return [(s, w) for s, w in rounds if np.shape(w) == self.expected]

    def check(self, rounds) -> dict:
        """A sample of the walks of ``rounds``, drawn from the seed, against
        the graph, and a sample of their steps against the transition law.
        A walk that did not come back counts as failed."""
        good = self.complete(rounds)
        missing = self.walkers * (len(rounds) - len(good))
        if good:
            starts = np.concatenate([s for s, _ in good])
            walks = np.concatenate([np.asarray(w) for _, w in good])
            if len(walks) > EDGE_SAMPLE_WALKS:
                pick = np.sort(np.random.default_rng(
                    self.seed % (1 << 64)).choice(
                        len(walks), EDGE_SAMPLE_WALKS, replace=False))
                starts, walks = starts[pick], walks[pick]
            faults = reference.edge_faults(self.g, starts, walks)
            law = reference.law_z(self.g, starts, walks, self.cfg["p"],
                                  self.cfg["q"], LAW_SAMPLE_STEPS,
                                  self.seed % (1 << 64))
        else:
            faults, law = np.zeros(0, np.int64), {"law_z": float("inf")}
        return {"attempted": self.walkers * len(rounds),
                "failed": missing + int(np.count_nonzero(faults)),
                "edge_faults": int(faults.sum()) + missing * self.expected[1],
                "law_z": law["law_z"], "law_detail": law}

    def counts(self, rounds) -> dict:
        good = self.complete(rounds)
        return {"walk_steps": sum(work.walk_steps(w) for _, w in good),
                "walk_bytes": sum(work.walk_bytes(s, w, self.g.deg)
                                  for s, w in good)}

    def info(self) -> dict:
        pg = self.engine.pg
        return {"graph_build_s": self.graph_build_s,
                "walkers_per_round": self.walkers, "n": self.g.n,
                "m": int(self.g.col.size),
                "max_degree": int(self.g.deg.max()),
                "cap": pg.cap, "hot_cap": pg.hot_cap}



def timed_rounds(seconds: float, measure: dict, one_round: Callable,
                 finish: Callable = lambda: None) -> list:
    """Whole rounds ``one_round(r)``, r = 1, 2, ..., back to back until
    ``seconds`` have passed. The window closes when ``finish()`` returns
    after the last round; its length goes into ``measure["window_s"]``."""
    rounds = []
    t_start = time.perf_counter()
    r = 1
    while True:
        rounds.append(one_round(r))
        r += 1
        if time.perf_counter() - t_start >= seconds:
            break
    finish()
    measure["window_s"] = time.perf_counter() - t_start
    return rounds
