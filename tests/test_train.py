"""Streamed on-device SGNS training (repro.train) — parity battery.

Contracts:
* device pair-gen emits exactly the host ``sgns_pairs`` stream (order and
  all) with self-pairs masked instead of compacted;
* device alias negatives follow the unigram^0.75 distribution;
* streamed consumption (train round k-1 while round k walks) is
  bit-identical to collecting all rounds first and replaying them;
* the fused Pallas kernel behind ``train_step(backend="fused")`` matches
  the jnp autodiff path (loss trajectory and final tables);
* fixed-shape batching never retraces across rounds;
* TrainStats accounting (pairs, steps, H2D bytes) is exact.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.node2vec import Node2VecConfig
from repro.core.skipgram import SGNSConfig, init_params, train_step
from repro.data.corpus import NegativeSampler, sgns_pairs, \
    walks_to_sgns_batches
from repro.optim.optimizers import adam
from repro.runtime.fault_tolerance import WalkRoundRunner
from repro.train import (StreamingSGNSTrainer, device_negatives, device_pairs,
                         num_pairs)
from repro.train.stream import _train_epoch


def _cfg(**kw):
    base = dict(p=0.5, q=2.0, walk_length=10, num_walks=3, window=4,
                dim=16, negatives=3, batch_size=256, seed=0)
    base.update(kw)
    return Node2VecConfig(**base)


@pytest.fixture(scope="module")
def tiny_graph():
    from repro.data import open_graph
    return open_graph("wec:k=7,deg=10,seed=1").graph    # 128 vertices


# ------------------------------------------------------------ pair gen --
@pytest.mark.parametrize("w,l,window,seed", [
    (1, 2, 1, 0), (4, 8, 3, 1), (16, 12, 5, 2), (7, 5, 10, 3), (3, 2, 4, 4),
])
def test_device_pairs_matches_host(w, l, window, seed):
    rng = np.random.default_rng(seed)
    walks = rng.integers(0, 50, (w, l)).astype(np.int32)
    # inject dead-end self-loop tails so the validity mask is exercised
    walks[:, -1] = walks[:, -2]
    c, x, valid = jax.device_get(device_pairs(jnp.asarray(walks), window))
    assert c.shape == (num_pairs(w, l, window),)
    hc, hx = sgns_pairs(walks, window)
    # same stream, same order — the host path just compacts the mask away
    np.testing.assert_array_equal(c[valid], hc)
    np.testing.assert_array_equal(x[valid], hx)
    assert np.all(c[~valid] == x[~valid])


def test_device_negatives_distribution():
    counts = np.array([300., 100., 25.])
    from repro.core.alias import build_alias
    prob, alias = build_alias(counts ** 0.75)
    draws = np.asarray(device_negatives(
        jax.random.PRNGKey(0), jnp.asarray(prob), jnp.asarray(alias),
        (40000,)))
    freq = np.bincount(draws, minlength=3) / 40000
    target = counts ** 0.75
    np.testing.assert_allclose(freq, target / target.sum(), atol=0.02)


# ---------------------------------------------------- streamed == concat --
def test_streamed_matches_concat(tiny_graph):
    cfg = _cfg(epochs=2)   # epochs > 1 exercises the per-epoch rng fold
    streamed = StreamingSGNSTrainer.from_config(tiny_graph.n, cfg)
    emb_s, st_s = streamed.train(WalkRoundRunner(tiny_graph, cfg).rounds())

    rounds = list(WalkRoundRunner(tiny_graph, cfg).rounds())
    concat = StreamingSGNSTrainer.from_config(tiny_graph.n, cfg)
    emb_c, st_c = concat.train(iter(rounds))

    assert np.array_equal(emb_s, emb_c)        # bit-identical embeddings
    np.testing.assert_array_equal(streamed.loss_history(),
                                  concat.loss_history())
    assert st_s.steps == st_c.steps and st_s.pairs == st_c.pairs


# ------------------------------------------------------- fused backend --
def test_fused_train_step_matches_jnp():
    cfg = SGNSConfig(vocab=60, dim=24, negatives=4)
    opt = adam(0.05)
    rng = np.random.default_rng(3)
    params = {"jnp": init_params(cfg, jax.random.PRNGKey(1)),
              "fused": init_params(cfg, jax.random.PRNGKey(1))}
    states = {k: opt.init(p) for k, p in params.items()}
    losses = {"jnp": [], "fused": []}
    for step in range(5):
        c = rng.integers(0, 60, 128).astype(np.int32)
        batch = {"center": jnp.asarray(c),
                 "pos": jnp.asarray((c + 1) % 60),
                 "neg": jnp.asarray(
                     rng.integers(0, 60, (128, 4)).astype(np.int32)),
                 "valid": jnp.asarray(
                     (rng.random(128) > 0.2).astype(np.float32))}
        for backend in ("jnp", "fused"):
            params[backend], states[backend], loss = train_step(
                params[backend], states[backend], batch, opt, backend)
            losses[backend].append(float(loss))
    np.testing.assert_allclose(losses["jnp"], losses["fused"],
                               rtol=1e-4, atol=1e-4)
    for k in ("emb_in", "emb_out"):
        np.testing.assert_allclose(np.asarray(params["jnp"][k]),
                                   np.asarray(params["fused"][k]),
                                   rtol=1e-4, atol=1e-4)


def test_fused_streamed_matches_jnp_streamed(tiny_graph):
    cfg = _cfg(num_walks=2)
    rounds = list(WalkRoundRunner(tiny_graph, cfg).rounds())
    emb = {}
    for backend in ("jnp", "fused"):
        tr = StreamingSGNSTrainer.from_config(tiny_graph.n, cfg,
                                              sgns_backend=backend)
        emb[backend], _ = tr.train(iter(rounds))
    np.testing.assert_allclose(emb["jnp"], emb["fused"],
                               rtol=2e-4, atol=2e-4)


# ------------------------------------------------------ compile economy --
def test_stream_rounds_do_not_retrace(tiny_graph):
    cfg = _cfg(num_walks=4)
    trainer = StreamingSGNSTrainer.from_config(tiny_graph.n, cfg)
    it = iter(list(WalkRoundRunner(tiny_graph, cfg).rounds()))
    trainer.consume(next(it))
    compiled_after_first = _train_epoch._cache_size()
    for walks in it:
        trainer.consume(walks)
    # rounds 2..4 share round 1's fixed shapes — zero new compiles
    assert _train_epoch._cache_size() == compiled_after_first


# ------------------------------------------------- host-path satellite --
def test_padded_rows_skip_negative_sampling():
    walks = np.random.default_rng(0).integers(0, 40, (6, 8)).astype(np.int32)
    window, negatives, batch_size, seed = 3, 4, 64, 7
    centers, _ = sgns_pairs(walks, window)
    n = len(centers)
    assert n % batch_size != 0          # the last batch really is padded
    batches = list(walks_to_sgns_batches(walks, 40, window, negatives,
                                         batch_size, seed=seed))
    # replay the exact rng stream: permutation, then per-batch draws sized
    # to the *live* rows only — if padded rows consumed draws, this diverges
    sampler = NegativeSampler(walks, 40)
    rng = np.random.default_rng(seed)
    rng.permutation(n)
    for lo, b in zip(range(0, n, batch_size), batches):
        live = min(batch_size, n - lo)
        np.testing.assert_array_equal(
            b["neg"][:live], sampler.sample(rng, (live, negatives)))
        assert np.all(b["neg"][live:] == 0)
        assert np.all(b["valid"][live:] == 0)


# ---------------------------------------------------------- accounting --
def test_train_stats_accounting(tiny_graph):
    cfg = _cfg(num_walks=2, epochs=2)
    rounds = list(WalkRoundRunner(tiny_graph, cfg).rounds())
    trainer = StreamingSGNSTrainer.from_config(tiny_graph.n, cfg)
    _, st = trainer.train(iter(rounds))

    want_pairs, want_steps, want_h2d = 0, 0, 0
    for w in rounds:
        hc, _ = sgns_pairs(w, cfg.window)
        want_pairs += len(hc) * cfg.epochs
        steps = -(-num_pairs(*w.shape, cfg.window) // cfg.batch_size)
        want_steps += steps * cfg.epochs
        want_h2d += w.astype(np.int32).nbytes + tiny_graph.n * 8
    assert st.pairs == want_pairs
    assert st.steps == want_steps
    assert st.h2d_bytes == want_h2d
    assert st.tokens == sum(w.size for w in rounds)
    assert st.pairs_per_sec > 0 and st.wall_seconds > 0
