"""StreamingSGNSTrainer — train SGNS on FN-Multi round *k−1* while the walk
engine generates round *k* (DESIGN.md §14).

Stage-2's "host corpus cliff" (ROADMAP): the old launcher collected every
round into one ``np.concatenate``, expanded all (center, context) pairs in
numpy, and re-uploaded every batch per step. Here the corpus never exists
on host:

* each round's walks upload to device **once** (plus a [V]-sized alias
  refresh); pair generation is window-offset gathers over the resident
  walks array (``repro.train.pairs``);
* negatives are O(1) device alias draws from the incrementally maintained
  unigram^0.75 counts (rounds 0..k when training round k);
* each epoch over a round is ONE device program (``lax.scan`` over the
  fixed [steps, batch] permutation grid) — one compile per (walkers,
  length) round shape, one dispatch per epoch, params/opt_state buffers
  donated, so round k+1 never retraces and the host never sits in the
  step loop;
* the fused Pallas SGNS kernel rides behind ``sgns_backend="fused"``
  (``repro.core.skipgram.sgns_grads``).

Streamed and concat consumption are **bit-identical**: every batch depends
only on (round index, epoch, step index) and the cumulative corpus counts
up to that round — never on arrival timing — so training on a live
dispatch-ahead round iterator equals collecting all rounds first and
replaying them (tested in tests/test_train.py).
"""
from __future__ import annotations

import functools
import math
import time
from typing import Iterable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.alias import build_alias
from repro.core.skipgram import (SGNSConfig, init_params, normalize_embeddings,
                                 sgns_grads)
from repro.optim.optimizers import adam, adam_rows, apply_updates
from repro.train.pairs import device_negatives, device_pairs, num_pairs
from repro.train.shard import (init_sharded_params, mesh_shards,
                               pow2_bucket, shard_opt_state,
                               train_epoch_sharded)
from repro.train.stats import TrainRecorder, TrainStats


@functools.partial(jax.jit, static_argnames=("window",))
def _gen_pairs(walks, window):
    """Resident-walks -> pair arrays + per-pair validity + valid count."""
    with jax.named_scope("sgns.pairs"):
        c, x, valid = device_pairs(walks, window)
        return c, x, valid, jnp.sum(valid)


@functools.partial(jax.jit, static_argnames=("n", "steps", "batch"))
def _perm_batches(key, n, steps, batch):
    """Device shuffle of ``n`` pair slots, padded to the fixed step grid and
    reshaped [steps, batch] (pad slots are masked by position in the step)."""
    with jax.named_scope("sgns.pairs"):
        perm = jax.random.permutation(key, n)
        return jnp.pad(perm, (0, steps * batch - n)).reshape(steps, batch)


@functools.partial(jax.jit,
                   static_argnames=("opt", "negatives", "backend", "n_pairs"),
                   donate_argnums=(0, 1))
def _train_epoch(params, opt_state, c, x, valid, perm2d, prob, alias, key,
                 *, opt, negatives, backend, n_pairs):
    """One epoch over one round as a single device program: lax.scan over
    the [steps, batch] permutation grid — per batch, a permutation-row
    gather + alias negatives + SGNS update. One dispatch per epoch (no
    per-step host round trips), one compile per round shape. Returns
    (params, opt_state, per-step losses [steps])."""
    batch_size = perm2d.shape[1]

    def body(carry, s):
        params, opt_state = carry
        with jax.named_scope("sgns.pairs"):
            idx = perm2d[s]
            in_bounds = (s * batch_size + jnp.arange(batch_size)) < n_pairs
            batch = {"center": c[idx], "pos": x[idx]}
        with jax.named_scope("sgns.negatives"):
            batch["neg"] = device_negatives(jax.random.fold_in(key, s), prob,
                                            alias, (batch_size, negatives))
        with jax.named_scope("sgns.pairs"):
            batch["valid"] = (valid[idx] & in_bounds).astype(jnp.float32)
        with jax.named_scope("sgns.grads"):
            loss, grads = sgns_grads(params, batch, backend)
        with jax.named_scope("sgns.optimizer"):
            updates, opt_state = opt.update(grads, opt_state, params)
            params = apply_updates(params, updates)
        return (params, opt_state), loss

    # the zero tables of the dense gradients are constants of the loop body,
    # lowered outside every scope inside it: the outer scope names them
    with jax.named_scope("sgns.grads"):
        (params, opt_state), losses = jax.lax.scan(
            body, (params, opt_state), jnp.arange(perm2d.shape[0]))
    return params, opt_state, losses


class StreamingSGNSTrainer:
    """Consume per-round walk arrays as they complete; keep all corpus work
    on device. One instance = one training run (params live across rounds).

    ``shard_tables=True`` range-partitions the embedding tables (and their
    Adam moments) across the 1-D ``rw`` mesh and runs each epoch under
    ``shard_map`` with sparse owner gathers + lazy row-Adam
    (``repro.train.shard``; DESIGN.md §16). The sharded run is bit-identical
    across shard counts for the same seeds; note it is a *different
    optimizer semantics* than the dense default (untouched rows keep their
    moments frozen), so compare sharded runs against ``shard_tables=True``
    on one device, not against the dense path.
    """

    def __init__(self, vocab: int, dim: int = 128, window: int = 10,
                 negatives: int = 5, batch_size: int = 1024,
                 lr: float = 0.025, epochs: int = 1, seed: int = 0,
                 sgns_backend: str = "jnp", power: float = 0.75,
                 record_loss: bool = True, shard_tables: bool = False,
                 mesh=None):
        self.vocab = vocab
        self.dim = dim
        self.window = window
        self.negatives = negatives
        self.batch_size = batch_size
        self.epochs = epochs
        self.seed = seed
        self.sgns_backend = sgns_backend
        self.power = power
        self.record_loss = record_loss
        self.shard_tables = bool(shard_tables)
        scfg = SGNSConfig(vocab=vocab, dim=dim, negatives=negatives)
        if self.shard_tables:
            # mesh-partitioned tables + lazy row-Adam (repro.train.shard):
            # same init values, padded to the shard multiple, range-sharded
            from repro.launch.mesh import make_table_mesh
            from jax.sharding import Mesh
            self.mesh = mesh if isinstance(mesh, Mesh) and \
                tuple(mesh.axis_names) == ("rw",) else make_table_mesh(mesh)
            self.shards = mesh_shards(self.mesh)
            self.params = init_sharded_params(scfg, jax.random.PRNGKey(seed),
                                              self.mesh)
            self._opt = adam_rows(lr)
            self.opt_state = shard_opt_state(self.params, self.mesh)
            self._u_in = pow2_bucket(batch_size)
            self._u_out = pow2_bucket(batch_size * (1 + negatives))
        else:
            self.mesh = None
            self.shards = 1
            self.params = init_params(scfg, jax.random.PRNGKey(seed))
            self._opt = adam(lr)
            self.opt_state = self._opt.init(self.params)
        self._counts = np.zeros(vocab, np.float64)
        self._key = jax.random.PRNGKey(seed)
        self._round = 0
        self._losses: list = []        # device scalars; fetched lazily
        self._pair_counts: list = []   # device scalars (valid pairs / round)
        self.recorder = TrainRecorder(sgns_backend, shards=self.shards)

    @classmethod
    def from_config(cls, vocab: int, cfg, **overrides
                    ) -> "StreamingSGNSTrainer":
        """Build from the SGNS half of a ``Node2VecConfig``-shaped object."""
        kw = dict(dim=cfg.dim, window=cfg.window, negatives=cfg.negatives,
                  batch_size=cfg.batch_size, lr=cfg.lr, epochs=cfg.epochs,
                  seed=cfg.seed,
                  sgns_backend=getattr(cfg, "sgns_backend", "jnp"))
        kw.update(overrides)
        return cls(vocab, **kw)

    # ---------------------------------------------------------- one round --
    def _alias_refresh(self, walks: np.ndarray):
        """Fold the round into the cumulative unigram counts and rebuild the
        [V] negative-sampling alias table (O(V) host); returns the host
        (prob, alias)."""
        with obs.span("train.alias_refresh"):
            self._counts += np.bincount(walks.reshape(-1),
                                        minlength=self.vocab)
            freq = self._counts ** self.power
            if freq.sum() == 0:
                freq = np.ones(self.vocab)
            return build_alias(freq)

    def consume(self, walks: np.ndarray) -> None:
        """Train one epoch pass (``epochs`` sub-passes) over one round."""
        t0 = time.perf_counter()
        walks = np.ascontiguousarray(walks, np.int32)  # host-ok: round input
        w, l = walks.shape
        n_pairs = num_pairs(w, l, self.window)
        prob_np, alias_np = self._alias_refresh(walks)
        if n_pairs == 0:
            self._round += 1
            self.recorder.round_trained(time.perf_counter() - t0, 0, 0,
                                        w * l, 0)
            return
        h2d = walks.nbytes + prob_np.nbytes + alias_np.nbytes
        with obs.span("train.upload", bytes=h2d):
            dev_walks, prob, alias = (jnp.asarray(a) for a in
                                      (walks, prob_np, alias_np))
        with obs.span("train.dispatch"):
            c, x, valid, n_valid = _gen_pairs(dev_walks, self.window)
            self._pair_counts.append(n_valid * self.epochs)
            steps = math.ceil(n_pairs / self.batch_size)
            rkey = jax.random.fold_in(self._key, self._round)
            for e in range(self.epochs):
                pkey, skey = jax.random.split(jax.random.fold_in(rkey, e))
                perm2d = _perm_batches(pkey, n_pairs, steps, self.batch_size)
                if self.shard_tables:
                    self.params, self.opt_state, losses = \
                        train_epoch_sharded(
                            self.params, self.opt_state, c, x, valid, perm2d,
                            prob, alias, skey,
                            mesh=self.mesh, opt=self._opt,
                            negatives=self.negatives,
                            backend=self.sgns_backend, n_pairs=n_pairs,
                            u_in=self._u_in, u_out=self._u_out)
                else:
                    self.params, self.opt_state, losses = _train_epoch(
                        self.params, self.opt_state, c, x, valid, perm2d,
                        prob, alias, skey,
                        opt=self._opt, negatives=self.negatives,
                        backend=self.sgns_backend, n_pairs=n_pairs)
                if self.record_loss:
                    self._losses.append(losses)
        self._round += 1
        coll = 0
        if self.shard_tables:
            from repro.roofline.traffic import sgns_exchange_bytes
            coll = steps * self.epochs * sgns_exchange_bytes(
                self._u_in + self._u_out, self.dim, self.shards)
        self.recorder.round_trained(
            time.perf_counter() - t0, steps * self.epochs, 0, w * l, h2d,
            collective_bytes=coll)

    # ------------------------------------------------------------- driver --
    def train(self, source: Iterable[np.ndarray],
              max_rounds: Optional[int] = None
              ) -> Tuple[np.ndarray, TrainStats]:
        """Drive training over ``source`` (an iterator of per-round ``[W, L]``
        walk arrays — e.g. ``WalkRoundRunner.rounds()``, whose dispatch-ahead
        means round k+1 walks while this trainer optimizes round k).
        Returns (L2-normalized [V, dim] embeddings, :class:`TrainStats`).
        """
        t_start = time.perf_counter()
        it = iter(source)
        seen = 0
        while max_rounds is None or seen < max_rounds:
            t0 = time.perf_counter()
            try:
                walks = next(it)
            except StopIteration:
                break
            self.recorder.walk_waited(time.perf_counter() - t0)
            self.consume(np.asarray(walks))  # host-ok: per-round, not batch
            seen += 1
        emb, stats = self.finish(time.perf_counter() - t_start)
        return emb, stats

    def finish(self, wall_seconds: Optional[float] = None
               ) -> Tuple[np.ndarray, TrainStats]:
        """Flush the async step queue, fetch embeddings, freeze stats."""
        t0 = time.perf_counter()
        # terminal fetch ([:vocab] strips the shard-padding rows)
        emb = np.asarray(jax.device_get(            # host-ok: terminal fetch
            normalize_embeddings(self.params)))[:self.vocab]
        if self._pair_counts:
            self.recorder.pairs = int(sum(
                int(p) for p in jax.device_get(     # host-ok: terminal fetch
                    self._pair_counts)))
            self._pair_counts = [jnp.asarray(self.recorder.pairs)]
        self.recorder.finalized(time.perf_counter() - t0)
        if wall_seconds is None:   # direct consume() use, no train() driver
            wall_seconds = sum(self.recorder._waits) + self.recorder._train_s
        return emb, self.recorder.snapshot(wall_seconds)

    def loss_history(self) -> np.ndarray:
        """Per-step losses, concatenated over epochs/rounds (device sync)."""
        if not self._losses:
            return np.zeros(0, np.float32)
        return np.asarray(jax.device_get(           # host-ok: terminal fetch
            jnp.concatenate(self._losses)))


def train_streamed(g, cfg, mesh=None, checkpointer=None, **overrides
                   ) -> Tuple[np.ndarray, TrainStats]:
    """End-to-end streamed node2vec stage 2: walk rounds through a
    :class:`~repro.runtime.fault_tolerance.WalkRoundRunner` (dispatch-ahead,
    checkpointed) feeding a :class:`StreamingSGNSTrainer`. The streamed
    counterpart of ``repro.core.node2vec.node2vec``; same round seeds, so a
    concat replay of the same config reproduces it bit-for-bit.
    """
    from repro.runtime.fault_tolerance import WalkRoundRunner
    runner = WalkRoundRunner(g, cfg, mesh=mesh, checkpointer=checkpointer)
    if overrides.get("shard_tables") and "mesh" not in overrides:
        overrides["mesh"] = mesh   # table shards align with graph shards
    trainer = StreamingSGNSTrainer.from_config(g.n, cfg, **overrides)
    emb, stats = trainer.train(runner.rounds())
    return emb, stats
