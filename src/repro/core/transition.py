"""2nd-order Node2Vec transition probabilities — on-demand (paper §3.2).

The walk moved u -> v; for every candidate x in N(v):

    alpha_pq(u, v, x) = 1/p  if x == u          (dist(u,x) == 0)
                        1    if x in N(u)       (dist(u,x) == 1)
                        1/q  otherwise          (dist(u,x) == 2)
    pi_vx = alpha * w_vx   (normalized over N(v))

Nothing is ever precomputed or stored per (u, v) pair — this is the paper's
central memory-saving idea (Eq. 1: storing all pairs costs 8*sum(d_i^2) bytes).

Membership x in N(u) compares every candidate with every lane of u's neighbor
row and ORs the hits: one compare-and-reduce that XLA fuses into a single
reduce, with no gathers and no [D, Dp] temporary. It does not need the row
sorted. Pad candidates (PAD_ID = i32 max) are masked out, so a pad never
counts as a hit.

``approx_gap`` implements the FN-Approx bounds (paper Eq. 2-3), generalized to
any (p, q) ordering (the paper assumes 1/p <= 1 <= 1/q).
"""
from __future__ import annotations

from typing import Dict

import jax.numpy as jnp
import numpy as np

from repro.core.graph import PAD_ID, CSRGraph


def membership(prev_sorted: jnp.ndarray, cand_ids: jnp.ndarray) -> jnp.ndarray:
    """For each candidate id, is it present in the row ``prev_sorted``?

    prev_sorted: [..., Dp] i32 (PAD_ID padded); cand_ids: [..., D] i32.
    Returns [..., D] bool; a PAD_ID candidate is never a hit.
    """
    hit = jnp.any(cand_ids[..., :, None] == prev_sorted[..., None, :], axis=-1)
    return hit & (cand_ids != PAD_ID)


def unnormalized_probs(cand_ids: jnp.ndarray, cand_w: jnp.ndarray,
                       u: jnp.ndarray, prev_sorted: jnp.ndarray,
                       p: float, q: float) -> jnp.ndarray:
    """alpha_pq * w over one candidate row. Shapes: [D], [D], [], [Dp]."""
    is_u = cand_ids == u
    common = membership(prev_sorted, cand_ids)
    alpha = jnp.where(is_u, 1.0 / p, jnp.where(common, 1.0, 1.0 / q))
    valid = cand_ids != PAD_ID
    return jnp.where(valid, alpha * cand_w, 0.0)


def approx_gap(deg_u: jnp.ndarray, deg_v: jnp.ndarray, w_min_v: jnp.ndarray,
               w_max_v: jnp.ndarray, p: float, q: float) -> jnp.ndarray:
    """Width of the [LB, UB] interval for a single transition probability at v
    given only scalar summaries (paper Eq. 2-3, generalized).

    The number of common neighbors among v's non-u candidates is some
    c in [0, m], m = min(deg_u, deg_v - 1); bounding the numerator/denominator
    over c and the edge-weight range yields layout-free bounds, so the check
    costs O(1) and needs **no** neighbor traffic.
    """
    inv_p, inv_q = 1.0 / p, 1.0 / q
    dv = jnp.maximum(deg_v.astype(jnp.float32), 2.0)
    m = jnp.minimum(deg_u.astype(jnp.float32), dv - 1.0)
    base = inv_p + (dv - 1.0) * inv_q
    den_hi = w_max_v * (base + jnp.maximum(0.0, m * (1.0 - inv_q)))
    den_lo = w_min_v * (base + jnp.minimum(0.0, m * (1.0 - inv_q)))
    num_hi = max(1.0, inv_q) * w_max_v
    num_lo = min(1.0, inv_q) * w_min_v
    return num_hi / jnp.maximum(den_lo, 1e-30) - num_lo / jnp.maximum(
        den_hi, 1e-30)


def brute_force_probs(g: CSRGraph, u: int, v: int, p: float,
                      q: float) -> Dict[int, float]:
    """Python-set oracle for tests: exact normalized transition probs at v
    given previous vertex u."""
    nu = set(int(x) for x in g.neighbors(u))
    probs = {}
    for x, w in zip(g.neighbors(v), g.weights(v)):
        x = int(x)
        if x == u:
            a = 1.0 / p
        elif x in nu:
            a = 1.0
        else:
            a = 1.0 / q
        probs[x] = probs.get(x, 0.0) + a * float(w)
    total = sum(probs.values())
    return {x: pw / total for x, pw in probs.items()} if total > 0 else {}
