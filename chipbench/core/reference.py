"""Plain references for what the walk engine produces.

Two checks, both on the host and both independent of the program:

* :func:`edge_faults` — every step ``start -> walks[:, 0] -> ...`` is an
  edge of the CSR, or a stay at a vertex with no edges (exact; copied from
  the program's chip smoke test).
* :func:`law_z` — the sampled steps follow the second-order node2vec
  transition law (Grover & Leskovec 2016, section 3.2), read against
  :func:`brute_force_probs` (copied from the program's test oracle). The
  statistic is the largest |z| among four martingale sums over a sample of
  steps drawn from the seed: per step category (return to u, common
  neighbour of u, farther), observed count minus the count the law expects,
  over the law's standard deviation; and a randomised probability integral
  transform of the chosen neighbour, whose mean is 1/2 under the law. Each
  is N(0, 1) in the limit for walks that follow the law, whatever the graph.
"""
from __future__ import annotations

import math
from typing import Dict

import numpy as np


def brute_force_probs(g, u: int, v: int, p: float,
                      q: float) -> Dict[int, float]:
    """Normalised transition probabilities at ``v`` having come from ``u``;
    ``u < 0`` gives the first-order law (the walk's first step)."""
    nu = set(int(x) for x in g.neighbors(u)) if u >= 0 else set()
    probs = {}
    for x, w in zip(g.neighbors(v), g.weights(v)):
        x = int(x)
        if u < 0:
            a = 1.0
        elif x == u:
            a = 1.0 / p
        elif x in nu:
            a = 1.0
        else:
            a = 1.0 / q
        probs[x] = probs.get(x, 0.0) + a * float(w)
    total = sum(probs.values())
    return {x: pw / total for x, pw in probs.items()} if total > 0 else {}


def paths(starts: np.ndarray, walks: np.ndarray) -> np.ndarray:
    """[W, L+1] int64: each walk with its start vertex in front."""
    return np.concatenate([np.asarray(starts)[:, None], walks],
                          axis=1).astype(np.int64)


def edge_faults(g, starts: np.ndarray, walks: np.ndarray) -> np.ndarray:
    """Per walk, the number of steps that are neither a CSR edge nor a stay
    at a vertex with no edges. Rows of the CSR are sorted, so the edge keys
    ``src * n + dst`` are sorted already."""
    path = paths(starts, walks)
    src, dst = path[:, :-1], path[:, 1:]
    deg = g.deg
    keys = np.repeat(np.arange(g.n, dtype=np.int64), deg) * g.n + g.col
    out_of_range = (src < 0) | (src >= g.n) | (dst < 0) | (dst >= g.n)
    src, dst = np.clip(src, 0, g.n - 1), np.clip(dst, 0, g.n - 1)
    want = src * g.n + dst
    pos = np.minimum(np.searchsorted(keys, want), len(keys) - 1)
    ok = (keys[pos] == want) | ((deg[src] == 0) & (src == dst))
    bad = ~ok | out_of_range
    return bad.sum(axis=1)


def law_z(g, starts: np.ndarray, walks: np.ndarray, p: float, q: float,
          sample: int, seed: int) -> dict:
    """Largest |z| of the transition law over ``sample`` steps drawn from
    ``seed``. Steps at a vertex with no edges, and steps that are no edge
    (which :func:`edge_faults` counts), are left out of the sums."""
    path = paths(starts, walks)
    w, l1 = path.shape
    rng = np.random.default_rng(seed)
    total = w * (l1 - 1)
    idx = rng.choice(total, size=min(sample, total), replace=False)
    rows, cols = idx // (l1 - 1), idx % (l1 - 1)
    jitter = rng.random(idx.size)
    obs = np.zeros(3)
    exp = np.zeros(3)
    var = np.zeros(3)
    pit = []
    for r, c, vj in zip(rows, cols, jitter):
        v, x = int(path[r, c]), int(path[r, c + 1])
        u = int(path[r, c - 1]) if c > 0 else -1
        if not (0 <= v < g.n and 0 <= x < g.n and u < g.n):
            continue
        probs = brute_force_probs(g, u, v, p, q)
        if not probs or x not in probs:
            continue
        nu = set(int(y) for y in g.neighbors(u)) if u >= 0 else set()
        pc = np.zeros(3)
        below = 0.0
        for y, py in probs.items():
            pc[0 if y == u else 1 if y in nu else 2] += py
            if y < x:
                below += py
        obs[0 if x == u else 1 if x in nu else 2] += 1
        exp += pc
        var += pc * (1.0 - pc)
        pit.append(below + vj * probs[x])
    z = {f"z_{name}": float((obs[i] - exp[i]) / math.sqrt(var[i]))
         if var[i] > 0 else 0.0
         for i, name in enumerate(("return", "common", "far"))}
    n = len(pit)
    z["z_pit"] = float((np.mean(pit) - 0.5) * math.sqrt(12.0 * n)) \
        if n else 0.0
    z["steps"] = n
    z["law_z"] = max(abs(z[k]) for k in ("z_return", "z_common", "z_far",
                                         "z_pit"))
    return z
