"""Plain reference for the trainer: skip-gram with negative sampling
(Mikolov et al. 2013, as node2vec trains it), one epoch over each round of
walks in turn, with dense Adam, in straightforward ``jax.numpy``.

It follows the trainer's stated semantics and its seeds, not its code:
pairs are every (center, context) within ``window`` along each walk, both
directions, offset by offset; self-pairs weigh 0; the pairs are shuffled by
``jax.random.permutation`` of the round's key and cut into batches, the
last padded and masked; negatives are alias draws from the cumulative
unigram counts of the rounds so far to the power 0.75, the table built by
Vose's method; Adam's step count runs on from round to round; the
loss of a batch is the masked mean of -log s(c.p) - sum_k log s(-c.n_k);
Adam as Kingma & Ba. Float32 at ``highest`` matmul precision; the control
runs it in bfloat16.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def vose(w: np.ndarray):
    """Vose's alias table over weights ``w``: (prob f32, alias i32)."""
    k = len(w)
    prob = np.zeros(k, dtype=np.float32)
    alias = np.zeros(k, dtype=np.int32)
    total = float(w.sum())
    scaled = w.astype(np.float64) * (k / total)
    small = [i for i in range(k) if scaled[i] < 1.0]
    large = [i for i in range(k) if scaled[i] >= 1.0]
    while small and large:
        s, big = small.pop(), large.pop()
        prob[s] = scaled[s]
        alias[s] = big
        scaled[big] = (scaled[big] + scaled[s]) - 1.0
        (small if scaled[big] < 1.0 else large).append(big)
    for i in large + small:
        prob[i] = 1.0
        alias[i] = i
    return prob, alias


def pairs(walks: np.ndarray, window: int):
    """(centers, contexts) of one round, offset by offset, both ways."""
    w, l = walks.shape
    cs, xs = [], []
    for off in range(1, min(window, l - 1) + 1):
        a, b = walks[:, :l - off].reshape(-1), walks[:, off:].reshape(-1)
        cs += [a, b]
        xs += [b, a]
    return np.concatenate(cs), np.concatenate(xs)


def init_tables(key, vocab: int, dim: int, dtype):
    """Input table uniform in +-1/sqrt(dim) from the seed, output zeros."""
    k1, _ = jax.random.split(key)
    scale = 1.0 / jnp.sqrt(dim)
    emb_in = (jax.random.uniform(k1, (vocab, dim), jnp.float32) - 0.5) \
        * 2 * scale
    return {"emb_in": emb_in.astype(dtype),
            "emb_out": jnp.zeros((vocab, dim), dtype)}


@functools.partial(jax.jit, static_argnames=("negatives", "lr", "n_pairs"))
def _epoch(params, mu, nu, t0, c, x, valid, perm2d, prob, alias, skey, *,
           negatives, lr, n_pairs):
    """One epoch over one round; ``t0`` is Adam's step count before it."""
    b1, b2, eps = 0.9, 0.999, 1e-8
    batch = perm2d.shape[1]
    vocab = prob.shape[0]
    dtype = params["emb_in"].dtype

    def loss_fn(p, cen, pos, neg, v):
        ci, po, no = p["emb_in"][cen], p["emb_out"][pos], p["emb_out"][neg]
        s_pos = jnp.sum(ci * po, axis=-1)
        s_neg = jnp.einsum("bd,bkd->bk", ci, no)
        per = jnp.logaddexp(0.0, -s_pos) + jnp.sum(
            jnp.logaddexp(0.0, s_neg), -1)
        return jnp.sum(per * v) / jnp.maximum(jnp.sum(v), 1.0)

    def body(carry, s):
        p, m, n = carry
        idx = perm2d[s]
        k1, k2 = jax.random.split(jax.random.fold_in(skey, s))
        slots = jax.random.randint(k1, (batch, negatives), 0, vocab)
        u = jax.random.uniform(k2, (batch, negatives))
        neg = jnp.where(u >= prob[slots], alias[slots], slots)
        live = (s * batch + jnp.arange(batch)) < n_pairs
        v = (valid[idx] & live).astype(dtype)
        loss, g = jax.value_and_grad(loss_fn)(p, c[idx], x[idx], neg, v)
        t = (t0 + s + 1).astype(jnp.float32)
        m = jax.tree.map(lambda a, b: (b1 * a + (1 - b1) * b).astype(dtype),
                         m, g)
        n = jax.tree.map(lambda a, b: (b2 * a + (1 - b2) * b * b
                                       ).astype(dtype), n, g)
        p = jax.tree.map(
            lambda w, a, b: (w - lr * (a / (1 - b1 ** t))
                             / (jnp.sqrt(b / (1 - b2 ** t)) + eps)
                             ).astype(dtype), p, m, n)
        return (p, m, n), loss

    with jax.default_matmul_precision("highest"):
        (params, mu, nu), losses = jax.lax.scan(
            body, (params, mu, nu), jnp.arange(perm2d.shape[0]))
    return params, mu, nu, losses


def replay(rounds, *, vocab: int, dim: int, window: int, negatives: int,
           batch: int, lr: float, power: float, seed: int,
           dtype_name: str = "float32"):
    """The trainer's rounds 0, 1, ... over the walks ``rounds``, one epoch
    each, from its seed. The negatives of round r come from the counts of
    rounds 0..r, and Adam's step count runs on across rounds. Returns the
    per-step losses of every round, concatenated, and, per table, the norms
    of the first Adam moment and of the change of the table after the last
    round, as numpy."""
    dtype = jnp.dtype(dtype_name)
    key = jax.random.PRNGKey(seed)
    params = init_tables(key, vocab, dim, dtype)
    mu = {k: jnp.zeros_like(v) for k, v in params.items()}
    nu = {k: jnp.zeros_like(v) for k, v in params.items()}
    counts = np.zeros(vocab, np.float64)
    losses, t0 = [], 0
    for r, walks in enumerate(rounds):
        walks = np.asarray(walks, np.int32)
        counts += np.bincount(walks.reshape(-1), minlength=vocab)
        c, x = pairs(walks, window)
        n_pairs = int(c.size)
        if n_pairs == 0:
            continue
        steps = -(-n_pairs // batch)
        prob, alias = vose(counts ** power)
        pkey, skey = jax.random.split(
            jax.random.fold_in(jax.random.fold_in(key, r), 0))
        perm = jax.random.permutation(pkey, n_pairs)
        perm2d = jnp.pad(perm, (0, steps * batch - n_pairs)).reshape(
            steps, batch)
        params, mu, nu, got = _epoch(
            params, mu, nu, jnp.int32(t0), jnp.asarray(c), jnp.asarray(x),
            jnp.asarray(c != x), perm2d, jnp.asarray(prob),
            jnp.asarray(alias), skey, negatives=negatives, lr=lr,
            n_pairs=n_pairs)
        losses.append(np.asarray(got, np.float64))
        t0 += steps
    del nu
    start = init_tables(key, vocab, dim, dtype)
    norm = lambda a: float(jnp.linalg.norm(a.astype(jnp.float32)))  # noqa
    return {"losses": np.concatenate(losses) if losses else np.zeros(0),
            "mu_norm": {k: norm(mu[k]) for k in mu},
            "delta_norm": {k: norm(params[k].astype(jnp.float32)
                                   - start[k].astype(jnp.float32))
                           for k in params}}


def gaps(prog: dict, ref: dict) -> dict:
    """The numbers compared: the widest relative gap of a step's loss, and
    per table the gap of the norms of the first moment and of the change,
    over the reference's norm of that table or the median table's,
    whichever is larger, taken at the worst table."""
    lp, lr_ = np.asarray(prog["losses"]), np.asarray(ref["losses"])
    if lp.shape != lr_.shape:
        loss_gap = float("inf")
    else:
        loss_gap = float(np.max(np.abs(lp - lr_) / np.abs(lr_)))
    out = {"loss_gap": loss_gap}
    for name in ("mu_norm", "delta_norm"):
        r = ref[name]
        floor = float(np.median(list(r.values())))
        out[name.replace("_norm", "_gap")] = max(
            abs(prog[name][k] - r[k]) / max(r[k], floor) for k in r)
    return out
