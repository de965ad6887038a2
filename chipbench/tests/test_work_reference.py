"""Work counts and references, hand-worked on a five-vertex graph, and the
transition-law statistic on walks drawn from the law and from a law that
breaks it."""
import numpy as np
import pytest

from chipbench.core import graphs, reference, work

# 0-1, 0-2, 1-2, 2-3, 3-4: degrees 2, 2, 3, 2, 1
FIVE = graphs.csr_from_edges(5, np.array([0, 0, 1, 2, 3]),
                             np.array([1, 2, 2, 3, 4]))
STARTS = np.array([0, 4], np.int32)
WALKS = np.array([[1, 2, 3], [3, 2, 1]], np.int32)


def test_five_vertex_graph():
    assert FIVE.deg.tolist() == [2, 2, 3, 2, 1]
    assert FIVE.neighbors(2).tolist() == [0, 1, 3]


def test_walk_counts_by_hand():
    # walk 0->1->2->3: 8*2+4, 8*2+4*2+4, 8*3+4*2+4 = 20 + 28 + 36
    # walk 4->3->2->1: 8*1+4, 8*2+4*1+4, 8*3+4*2+4 = 12 + 24 + 36
    assert work.walk_steps(WALKS) == 6
    assert work.walk_bytes(STARTS, WALKS, FIVE.deg) == 84 + 72


def test_edge_faults_by_hand():
    assert reference.edge_faults(FIVE, STARTS, WALKS).tolist() == [0, 0]
    bad = WALKS.copy()
    bad[1, 1] = 0                              # 3 -> 0 is no edge; 0 -> 1 is
    assert reference.edge_faults(FIVE, STARTS, bad).tolist() == [0, 1]
    bad[0, 0] = 9                              # out of range
    assert reference.edge_faults(FIVE, STARTS, bad)[0] >= 1


def test_edge_faults_stay_at_isolated_vertex():
    g = graphs.csr_from_edges(3, np.array([0]), np.array([1]))
    starts = np.array([2, 0], np.int32)
    walks = np.array([[2, 2], [1, 1]], np.int32)
    assert reference.edge_faults(g, starts, walks).tolist() == [0, 1]


def test_brute_force_probs_by_hand():
    # from 1 at 2: 0 is a common neighbour (1), 1 the return (1/p), 3 far
    got = reference.brute_force_probs(FIVE, 1, 2, p=1.0, q=0.5)
    assert got == pytest.approx({0: 0.25, 1: 0.25, 3: 0.5})
    first = reference.brute_force_probs(FIVE, -1, 2, p=1.0, q=0.5)
    assert first == pytest.approx({0: 1 / 3, 1: 1 / 3, 3: 1 / 3})


def _draw_walks(g, p, q, walkers, length, seed):
    rng = np.random.default_rng(seed)
    starts = rng.integers(0, g.n, walkers).astype(np.int32)
    walks = np.zeros((walkers, length), np.int32)
    for i, s in enumerate(starts):
        u, v = -1, int(s)
        for j in range(length):
            probs = reference.brute_force_probs(g, u, v, p, q)
            x = v if not probs else int(rng.choice(
                list(probs), p=np.array(list(probs.values()))))
            walks[i, j] = x
            u, v = v, x
    return starts, walks


@pytest.fixture(scope="module")
def small_graph():
    return graphs.rmat_graph({"scale": 8, "avg_degree": 6,
                              "rmat": [0.25, 0.25, 0.25, 0.25],
                              "graph_seed": 3})


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_law_z_small_on_walks_that_follow_the_law(small_graph, seed):
    starts, walks = _draw_walks(small_graph, 1.0, 0.5, 150, 30, seed)
    assert reference.edge_faults(small_graph, starts, walks).sum() == 0
    z = reference.law_z(small_graph, starts, walks, 1.0, 0.5, 4000, seed)
    assert z["steps"] == 4000
    assert z["law_z"] < 4.0, z


def test_law_z_large_on_first_order_walks(small_graph):
    starts, walks = _draw_walks(small_graph, 1.0, 1.0, 150, 30, 0)
    z = reference.law_z(small_graph, starts, walks, 1.0, 0.5, 4000, 0)
    assert z["law_z"] > 8.0, z


def test_sgns_counts_by_hand():
    # one walk 1 2 1 3, window 2: offsets 1 (3 position pairs) and 2 (2),
    # both directions: 10 ordered pairs; positions 0 and 2 hold the same
    # vertex, so 2 of them are self-pairs
    walks = np.array([[1, 2, 1, 3]])
    assert work.sgns_pairs_all(walks, 2) == 10
    assert work.sgns_pairs(walks, 2) == 8
    # 1,024 pairs x (center, positive, 5 negatives) x 128 f32, read + write
    assert work.sgns_step_bytes(1024, 5, 128) == 2 * 1024 * 7 * 128 * 4


def test_sgns_reference_pairs_match_the_count():
    from chipbench.core import sgns_reference
    walks = np.array([[1, 2, 1, 3], [0, 4, 4, 2]])
    c, x = sgns_reference.pairs(walks, 2)
    assert c.size == work.sgns_pairs_all(walks, 2)
    assert int(np.count_nonzero(c != x)) == work.sgns_pairs(walks, 2)
