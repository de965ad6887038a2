"""The work a cell does, counted from what it produced and the graph alone,
so no implementation of the program changes the count.

Walk, per walker-step u -> v -> x: ``8 * deg(v)`` bytes (v's neighbour ids
and weights, or alias entries on the first step), ``4 * deg(u)`` (N(u) for
the distance test; none on the first step, which is first order) and 4 (the
id written).
"""
from __future__ import annotations

import numpy as np


def walk_steps(walks: np.ndarray) -> int:
    """Walker-steps sampled: one per entry of the [W, L] walks."""
    return int(walks.size)


def walk_bytes(starts: np.ndarray, walks: np.ndarray, deg: np.ndarray) -> int:
    """Essential bytes of the walks: see the module docstring."""
    path = np.concatenate([np.asarray(starts)[:, None], walks], axis=1)
    deg = np.asarray(deg, np.int64)
    at = deg[path[:, :-1]]                   # deg(v) for each step
    came = deg[path[:, :-2]]                 # deg(u) for steps 1..L-1
    return int(8 * at.sum() + 4 * came.sum() + 4 * walks.size)


def sgns_pairs_all(walks: np.ndarray, window: int) -> int:
    """Every ordered pair of positions of a walk at most ``window`` apart,
    self-pairs included: what one epoch over the round steps through."""
    w, length = walks.shape
    o = min(window, length - 1)
    return 2 * w * (o * length - o * (o + 1) // 2)


def sgns_pairs(walks: np.ndarray, window: int) -> int:
    """Valid (center, context) pairs of one round: every ordered pair of
    positions of a walk at most ``window`` apart, less self-pairs (the same
    vertex at both positions)."""
    w, length = walks.shape
    n = 0
    for off in range(1, min(window, length - 1) + 1):
        n += 2 * int(np.count_nonzero(walks[:, :-off] != walks[:, off:]))
    return n


def sgns_step_bytes(batch: int, negatives: int, dim: int) -> int:
    """Essential bytes of one SGNS batch step: the center, positive and
    ``negatives`` rows of ``dim`` float32, each read once and written once.
    Optimizer state is left out (plain SGD has none)."""
    return 2 * batch * (2 + negatives) * dim * 4
