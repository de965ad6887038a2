"""2nd-order transition probabilities vs python-set oracle + FN-Approx
bound correctness (paper Eq. 2-3)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.graph import PAD_ID, CSRGraph, PaddedGraph
from repro.core.transition import (approx_gap, brute_force_probs, membership,
                                   unnormalized_probs)


def _random_graph(n, m, seed):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n, m)
    dst = rng.integers(0, n, m)
    w = rng.random(m).astype(np.float32) + 0.1
    return CSRGraph.from_edges(n, src, dst, w)


@pytest.mark.parametrize("n,m,seed", [
    (4, 6, 0), (4, 80, 1), (24, 6, 2), (8, 30, 3), (12, 50, 4), (16, 70, 5),
    (20, 40, 6), (24, 80, 7), (6, 15, 8), (10, 25, 10),
])
@pytest.mark.parametrize("pq", [(0.5, 2.0), (2.0, 0.5), (1.0, 1.0),
                                (4.0, 0.25)])
def test_probs_match_oracle(n, m, seed, pq):
    p, q = pq
    g = _random_graph(n, m, seed)
    pg = PaddedGraph.build(g)
    rng = np.random.default_rng(seed)
    for _ in range(5):
        v = int(rng.integers(0, n))
        if g.deg[v] == 0:
            continue
        nb = g.neighbors(v)
        u = int(nb[rng.integers(0, len(nb))])
        probs = np.asarray(unnormalized_probs(
            pg.adj[v], pg.wgt[v], jnp.int32(u), pg.adj[u], p, q))
        total = probs.sum()
        oracle = brute_force_probs(g, u, v, p, q)
        for slot, x in enumerate(np.asarray(pg.adj[v])):
            if x == PAD_ID:
                assert probs[slot] == 0.0
            else:
                np.testing.assert_allclose(probs[slot] / total,
                                           oracle[int(x)], atol=1e-5)


def test_membership_with_pads():
    prev = jnp.asarray([2, 5, 9, PAD_ID, PAD_ID], jnp.int32)
    cand = jnp.asarray([1, 2, 9, 10, PAD_ID], jnp.int32)
    got = np.asarray(membership(prev, cand))
    assert list(got) == [False, True, True, False, False]


def _sorted_rows(rng, rows, width, id_range):
    """``rows`` ascending rows of distinct ids in [0, id_range), each with a
    random number of live lanes and a PAD_ID tail."""
    out = np.full((rows, width), PAD_ID, np.int32)
    for r in range(rows):
        live = int(rng.integers(0, min(width, id_range) + 1))
        out[r, :live] = np.sort(rng.choice(id_range, live, replace=False))
    return out


@pytest.mark.parametrize("batched", [False, True])
@pytest.mark.parametrize("d,dp,case", [
    (8, 8, "random"), (5, 13, "random"), (13, 5, "random"),
    (28, 28, "random"), (40, 129, "random"), (6, 9, "all_pads"),
])
def test_membership_matches_set_oracle(d, dp, case, batched):
    rng = np.random.default_rng(d * 1000 + dp)
    rows = 16
    id_range = max(d, dp) + 8       # small id range: many common neighbors
    cand = _sorted_rows(rng, rows, d, id_range)
    prev = _sorted_rows(rng, rows, dp, id_range)
    if case == "all_pads":
        cand[:] = PAD_ID
    if batched:
        got = np.asarray(jax.vmap(membership)(jnp.asarray(prev),
                                              jnp.asarray(cand)))
    else:
        got = np.stack([np.asarray(membership(jnp.asarray(p_row),
                                              jnp.asarray(c_row)))
                        for p_row, c_row in zip(prev, cand)])
    want = np.array([[int(x) != PAD_ID and int(x) in set(p_row.tolist())
                      for x in c_row] for p_row, c_row in zip(prev, cand)])
    assert got.shape == (rows, d) and got.dtype == np.bool_
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("n,m,seed", [
    (4, 20, 0), (4, 150, 1), (30, 20, 2), (10, 60, 3), (15, 90, 4),
    (20, 120, 5), (25, 150, 6), (30, 150, 7), (8, 40, 8), (12, 75, 0),
])
@pytest.mark.parametrize("pq", [(0.5, 2.0), (2.0, 0.5), (1.0, 4.0)])
def test_approx_bounds_contain_true_probs(n, m, seed, pq):
    """Paper Eq. 2-3 (generalized): every actual transition prob for a
    non-u candidate lies within [LB-ish, UB-ish]; we verify the *gap*
    computed from scalars bounds the true spread of non-u probabilities."""
    p, q = pq
    g = _random_graph(n, m, seed)
    rng = np.random.default_rng(seed)
    for _ in range(5):
        v = int(rng.integers(0, n))
        if g.deg[v] < 3:
            continue
        nb = g.neighbors(v)
        u = int(nb[rng.integers(0, len(nb))])
        oracle = brute_force_probs(g, u, v, p, q)
        non_u = [pr for x, pr in oracle.items() if x != u]
        w = g.weights(v)
        gap = float(approx_gap(jnp.int32(g.deg[u]), jnp.int32(g.deg[v]),
                               jnp.float32(w.min()), jnp.float32(w.max()),
                               p, q))
        spread = max(non_u) - min(non_u)
        assert spread <= gap + 1e-6, (spread, gap)
