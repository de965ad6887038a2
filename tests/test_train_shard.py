"""Sharded SGNS trainer (repro.train.shard; DESIGN.md §16).

In-process tests run on the 1-device default backend with a 1-shard mesh —
the shard_map program, sparse gathers, and lazy row-Adam all execute, just
without a second shard. Cross-shard behavior (2 table shards: bit-identity
vs the 1-shard run, collective accounting, zero retrace) runs in
subprocesses that set XLA_FLAGS before importing jax."""
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.alias import build_alias
from repro.core.skipgram import SGNSConfig, init_params
from repro.core.walk_distributed import RW_AXIS
from repro.data.corpus import NegativeSampler
from repro.launch.mesh import make_table_mesh
from repro.optim.optimizers import adam_rows
from repro.train import (StreamingSGNSTrainer, init_sharded_params,
                         pow2_bucket, shard_opt_state, table_rows,
                         train_epoch_sharded)
from repro.train.pairs import device_negatives
from jax.sharding import PartitionSpec as P


def test_pow2_bucket():
    assert [pow2_bucket(n) for n in (1, 2, 3, 256, 257, 1024)] == \
        [1, 2, 4, 256, 512, 1024]


def test_table_rows_pads_to_shard_multiple():
    assert table_rows(257, 1) == 257
    assert table_rows(257, 2) == 258
    assert table_rows(256, 2) == 256
    assert table_rows(10, 4) == 12


# ------------------------------------------------------- numpy oracle --
def _np_adam_rows(g, mu, nu, count, lr=0.025, b1=0.9, b2=0.999, eps=1e-8):
    """adam_rows.update in float32 numpy (count = already-incremented)."""
    f32 = np.float32
    mu = f32(b1) * mu + f32(1 - b1) * g
    nu = f32(b2) * nu + f32(1 - b2) * (g * g)
    bc1 = f32(1) - f32(b1) ** count
    bc2 = f32(1) - f32(b2) ** count
    upd = -f32(lr) * (mu / bc1) / (np.sqrt(nu / bc2) + f32(eps))
    return upd, mu, nu


def _np_sgns_rows(ci, po, no, v):
    """sgns_row_grads closed form in float64 (reference precision)."""
    sig = lambda x: 1.0 / (1.0 + np.exp(-x))
    pos = np.sum(ci * po, -1, keepdims=True)
    neg = np.sum(no * ci[:, None, :], -1)
    loss = np.logaddexp(0, -pos[:, 0]) + np.logaddexp(0, neg).sum(-1)
    cp = (sig(pos) - 1.0) * v[:, None]
    cn = sig(neg) * v[:, None]
    g_ci = cp * po + np.sum(cn[:, :, None] * no, axis=1)
    g_po = cp * ci
    g_no = cn[:, :, None] * ci[:, None, :]
    return float((loss * v).sum()), g_ci, g_po, g_no


def test_sharded_epoch_matches_numpy_reference():
    """One sharded epoch (1-shard mesh) == a numpy replay of the lazy
    row-Adam semantics: dedup per unique row, segment-sum grads in batch
    order, Adam only on touched rows. Negatives are taken from the same
    (already unit-tested) device draw so the oracle only re-derives the
    sharded math itself."""
    V, D, B, K, steps = 67, 8, 16, 3, 4
    rng = np.random.default_rng(0)
    n = steps * B - 5
    c = rng.integers(0, V, steps * B).astype(np.int32)
    x = rng.integers(0, V, steps * B).astype(np.int32)
    valid = rng.random(steps * B) < 0.9
    perm2d = rng.permutation(steps * B).astype(np.int32).reshape(steps, B)
    prob_np, alias_np = build_alias(rng.random(V) + 0.1)
    key = jax.random.PRNGKey(3)
    mesh = make_table_mesh(max_shards=1)
    opt = adam_rows(0.025)
    cfg = SGNSConfig(vocab=V, dim=D, negatives=K)
    ref = {k: np.asarray(v, np.float64)
           for k, v in init_params(cfg, jax.random.PRNGKey(0)).items()}
    params = init_sharded_params(cfg, jax.random.PRNGKey(0), mesh)
    state = shard_opt_state(params, mesh)
    u_in, u_out = pow2_bucket(B), pow2_bucket(B * (1 + K))
    p2, s2, losses = train_epoch_sharded(
        params, state, jnp.asarray(c), jnp.asarray(x), jnp.asarray(valid),
        jnp.asarray(perm2d), jnp.asarray(prob_np), jnp.asarray(alias_np),
        key, mesh=mesh, opt=opt, negatives=K, backend="jnp", n_pairs=n,
        u_in=u_in, u_out=u_out)

    mu = {k: np.zeros_like(v) for k, v in ref.items()}
    nu = {k: np.zeros_like(v) for k, v in ref.items()}
    want = []
    for s in range(steps):
        idx = perm2d[s]
        v = (valid[idx] & (s * B + np.arange(B) < n)).astype(np.float64)
        neg = np.asarray(device_negatives(
            jax.random.fold_in(key, s), jnp.asarray(prob_np),
            jnp.asarray(alias_np), (B, K)))
        ci, po, no = ref["emb_in"][c[idx]], ref["emb_out"][x[idx]], \
            ref["emb_out"][neg]
        loss, g_ci, g_po, g_no = _np_sgns_rows(ci, po, no, v)
        denom = max(v.sum(), 1.0)
        want.append(loss / denom)
        uc = np.unique(c[idx])
        uo = np.unique(np.concatenate([x[idx], neg.reshape(-1)]))
        g_uc = np.zeros((uc.size, D))
        np.add.at(g_uc, np.searchsorted(uc, c[idx]), g_ci / denom)
        g_uo = np.zeros((uo.size, D))
        np.add.at(g_uo, np.searchsorted(uo, x[idx]), g_po / denom)
        np.add.at(g_uo, np.searchsorted(uo, neg.reshape(-1)),
                  g_no.reshape(B * K, -1) / denom)
        for tab, u, g in (("emb_in", uc, g_uc), ("emb_out", uo, g_uo)):
            upd, mu_n, nu_n = _np_adam_rows(g, mu[tab][u], nu[tab][u], s + 1)
            ref[tab][u] += upd
            mu[tab][u], nu[tab][u] = mu_n, nu_n
    got = jax.device_get(p2)
    np.testing.assert_allclose(got["emb_in"][:V], ref["emb_in"],
                               rtol=0, atol=1e-5)
    np.testing.assert_allclose(got["emb_out"][:V], ref["emb_out"],
                               rtol=0, atol=1e-5)
    np.testing.assert_allclose(np.asarray(losses), want, rtol=0, atol=1e-5)
    assert int(jax.device_get(s2.count)) == steps


@pytest.fixture(scope="module")
def tiny_graph():
    from repro.data import open_graph
    return open_graph("wec:k=7,deg=10,seed=1").graph    # 128 vertices


def _rounds(vocab, n=3, w=32, l=9, seed=5):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, (w, l)).astype(np.int32)
            for _ in range(n)]


def _sharded_trainer(vocab=129, **kw):
    base = dict(dim=16, window=3, negatives=3, batch_size=64,
                shard_tables=True, mesh=make_table_mesh(max_shards=1))
    base.update(kw)
    return StreamingSGNSTrainer(vocab, **base)


def test_sharded_fused_matches_jnp():
    """Fused Pallas backend under the sharded epoch == jnp closed form."""
    embs = {}
    for backend in ("jnp", "fused"):
        tr = _sharded_trainer(sgns_backend=backend)
        emb, _ = tr.train(iter(_rounds(129)))
        embs[backend] = np.asarray(emb)
    np.testing.assert_allclose(embs["fused"], embs["jnp"], rtol=0, atol=2e-5)


def test_sharded_streamed_matches_concat():
    """Streamed consumption == replaying the collected rounds (the dense
    trainer's bit-identity contract holds for the sharded path too)."""
    rounds = _rounds(129)
    a, _ = _sharded_trainer().train(iter(rounds))
    b, _ = _sharded_trainer().train(iter(list(rounds)))
    assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


def test_sharded_rounds_do_not_retrace():
    """Same round shape -> ONE compile across all rounds x epochs, with
    params/opt donated through every call."""
    tr = _sharded_trainer(epochs=2, batch_size=32)   # shape unique to this
    before = train_epoch_sharded._cache_size()       # test -> fresh compile
    tr.train(iter(_rounds(129, n=4, w=24)))
    assert train_epoch_sharded._cache_size() == before + 1


def test_sharded_stats_accounting():
    """Shard/collective fields: 1 shard -> no exchange, overlap 0."""
    _, st = _sharded_trainer().train(iter(_rounds(129)))
    assert st.shards == 1
    assert st.collective_bytes == 0
    assert st.exposed_collective_bytes == 0


# ----------------------------------------- negative-sampling parity --
def test_alias_tables_match_global_sampler(tiny_graph):
    """The sharded trainer's incrementally maintained alias tables equal
    NegativeSampler's built from the full corpus at GLOBAL vocabulary —
    sharding partitions table rows, never the unigram counts."""
    tr = _sharded_trainer(vocab=tiny_graph.n)
    rounds = _rounds(tiny_graph.n, n=2)
    for r in rounds:
        tr.consume(r)
    prob, alias = tr._alias_refresh(np.zeros((0, 2), np.int32))
    ref = NegativeSampler(np.concatenate(rounds, axis=0), tiny_graph.n)
    np.testing.assert_allclose(np.asarray(prob), ref.prob, rtol=0,
                               atol=1e-12)
    np.testing.assert_array_equal(np.asarray(alias), ref.alias)


def test_sharded_negative_draws_replay_single_device_stream():
    """device_negatives replicated under shard_map == the plain call: the
    draw depends only on (key, tables, shape), so the sharded trainer's
    negative stream is the single-device stream bit for bit."""
    V = 61
    prob_np, alias_np = build_alias(np.random.default_rng(1).random(V) + .1)
    prob, alias = jnp.asarray(prob_np), jnp.asarray(alias_np)
    key = jax.random.PRNGKey(9)
    mesh = make_table_mesh(max_shards=1)
    direct = device_negatives(key, prob, alias, (32, 5))
    sharded = jax.shard_map(
        lambda p, a, k: device_negatives(k, p, a, (32, 5)), mesh=mesh,
        in_specs=(P(), P(), P()), out_specs=P(),
        check_vma=False)(prob, alias, key)
    np.testing.assert_array_equal(np.asarray(direct), np.asarray(sharded))


# -------------------------------------------------- 2-device parity --
TWO_DEV_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import numpy as np, jax
    from repro.launch.mesh import make_table_mesh
    from repro.train import StreamingSGNSTrainer, train_epoch_sharded

    assert jax.device_count() == 2
    V = 257                              # odd: pad row live on both tables
    rng = np.random.default_rng(5)
    rounds = [rng.integers(0, V, (48, 9)).astype(np.int32)
              for _ in range(3)]

    for backend in ("jnp", "fused"):
        out = {{}}
        for s in (1, 2):
            tr = StreamingSGNSTrainer(
                V, dim=16, window=3, negatives=3, batch_size=64, epochs=2,
                sgns_backend=backend, shard_tables=True,
                mesh=make_table_mesh(max_shards=s))
            before = train_epoch_sharded._cache_size()
            emb, st = tr.train(iter(list(rounds)))
            # zero retrace: one compile for all 3 rounds x 2 epochs
            assert train_epoch_sharded._cache_size() == before + 1, \\
                (s, backend, train_epoch_sharded._cache_size() - before)
            assert st.shards == s
            assert (st.collective_bytes > 0) == (s > 1), st
            out[s] = (np.asarray(emb), tr.loss_history())
        assert out[1][0].tobytes() == out[2][0].tobytes(), \\
            ("emb mismatch", backend)
        assert out[1][1].tobytes() == out[2][1].tobytes(), \\
            ("loss mismatch", backend)
    print("OK")
""")

TWO_DEV_STEP_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import numpy as np, jax, jax.numpy as jnp
    from repro.core.alias import build_alias
    from repro.core.skipgram import SGNSConfig
    from repro.launch.mesh import make_table_mesh
    from repro.optim.optimizers import adam_rows
    from repro.train import (init_sharded_params, pow2_bucket,
                             shard_opt_state, train_epoch_sharded)

    V, D, B, K, steps = 101, 8, 32, 3, 3
    rng = np.random.default_rng(2)
    n = steps * B - 7
    c = jnp.asarray(rng.integers(0, V, steps * B).astype(np.int32))
    x = jnp.asarray(rng.integers(0, V, steps * B).astype(np.int32))
    valid = jnp.asarray(rng.random(steps * B) < 0.9)
    perm2d = jnp.asarray(
        rng.permutation(steps * B).astype(np.int32).reshape(steps, B))
    prob_np, alias_np = build_alias(rng.random(V) + 0.1)
    prob, alias = jnp.asarray(prob_np), jnp.asarray(alias_np)
    key = jax.random.PRNGKey(4)
    opt = adam_rows(0.025)

    out = {{}}
    for s in (1, 2):
        mesh = make_table_mesh(max_shards=s)
        params = init_sharded_params(
            SGNSConfig(vocab=V, dim=D, negatives=K), jax.random.PRNGKey(0),
            mesh)
        state = shard_opt_state(params, mesh)
        p2, s2, losses = train_epoch_sharded(
            params, state, c, x, valid, perm2d, prob, alias, key,
            mesh=mesh, opt=opt, negatives=K, backend="jnp", n_pairs=n,
            u_in=pow2_bucket(B), u_out=pow2_bucket(B * (1 + K)))
        got = jax.device_get(p2)
        out[s] = (got["emb_in"][:V], got["emb_out"][:V],
                  np.asarray(losses))
    for a, b in zip(out[1], out[2]):
        assert a.tobytes() == b.tobytes()
    print("OK")
""")


def _run_subprocess(code):
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                            "HOME": "/root", "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout


@pytest.mark.slow
def test_two_device_streamed_bit_identity():
    """S=1 == S=2 bit for bit over a full streamed run (both backends),
    with zero retraces and collective accounting, on 2 fake devices."""
    _run_subprocess(TWO_DEV_SCRIPT.format())


@pytest.mark.slow
def test_two_device_epoch_bit_identity():
    """Single sharded epoch call: 1-shard == 2-shard tables + losses."""
    _run_subprocess(TWO_DEV_STEP_SCRIPT.format())
