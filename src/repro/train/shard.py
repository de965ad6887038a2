"""Sharded SGNS: mesh-partitioned embedding tables, sparse-collective epochs
(DESIGN.md §16).

PR 8's streaming trainer keeps both [V, D] embedding tables (and their Adam
moments) on ONE device: trainable graph size is capped by one HBM, the §2
mesh idles through the train half of every streamed round, and the dense
Adam update touches all V·D entries per step. This module partitions
``emb_in``/``emb_out`` and their moments across the walk engine's 1-D ``rw``
mesh by **vertex range** — shard *s* owns rows ``[s·n_loc, (s+1)·n_loc)``,
the same ranges ``ShardedGraph`` gives graph shard *s* — and runs each
jitted ``lax.scan`` epoch under ``shard_map``:

* **replicated batch math** — pair gathers, negative alias draws, the
  unique-row dedup, the SGNS forward/backward (jnp closed form or the fused
  Pallas kernel), and the deduped gradient segment-sums run identically on
  every shard. Replication is what buys bit-identity across shard counts:
  every float reduction has an S-independent grouping, so the S-shard run
  equals the 1-shard run bit for bit (tested on 2 devices via subprocess).
* **sparse owner gather** — the per-batch unique row sets (bucketed to
  power-of-two sizes, the same anti-retrace trick as PR 9's update
  scatters) are fetched with one owner-masked psum per table: each shard
  contributes its owned rows, zeros elsewhere. ``x + 0.0`` is bitwise ``x``
  here (no ``-0.0`` can reach the table: params are never ``-0.0`` and
  masked lanes contribute ``+0.0``), so the gather is also S-independent.
* **owner-local lazy row-Adam** — gradients come back already deduped per
  unique row; each shard applies :func:`repro.optim.optimizers.adam_rows`
  to the rows it owns (`.at[].set(mode="drop")` on out-of-range redirects
  non-owned and fill rows) with per-shard donated moments. O(rows·D) table
  work per step instead of dense Adam's O(V·D) — that, not device
  parallelism, is where the pairs/sec win comes from on small hosts.

The epoch program's shapes depend only on (round shape, batch, caps), so
round k+1 never retraces; params/opt_state are donated through the jit.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.walk_distributed import RW_AXIS
from repro.kernels.sgns import sgns_row_grads
from repro.optim.optimizers import AdamState
from repro.train.pairs import device_negatives


def pow2_bucket(n: int) -> int:
    """Smallest power of two >= n: unique-row buffer caps snap to a small
    shape family so collective/scatter shapes never retrace when batch or
    negative counts vary across configs (cf. engine.update._pad_to_bucket).
    """
    return 1 << max(int(n) - 1, 0).bit_length()


def mesh_shards(mesh: Mesh) -> int:
    return int(np.prod([mesh.shape[a] for a in mesh.axis_names]))


def table_rows(vocab: int, shards: int) -> int:
    """Padded global row count: vocab rounded up to a shard multiple, so the
    range partition ``owner(v) = v // (rows/shards)`` is exact (same layout
    rule as ``ShardedGraph``). Padding rows are zero and never touched."""
    return shards * math.ceil(vocab / max(shards, 1))


def table_sharding(mesh: Mesh) -> NamedSharding:
    """Row-range sharding for a [rows, D] table over the 1-D ``rw`` mesh."""
    return NamedSharding(mesh, P(RW_AXIS))


def init_sharded_params(cfg, key, mesh: Mesh):
    """``init_params(cfg, key)`` padded to the mesh multiple, generated
    straight into the range-sharded layout: no device ever holds a whole
    table. Threefry is partitionable, so the values do not depend on the
    shard count."""
    from repro.core.skipgram import init_params
    vp = table_rows(cfg.vocab, mesh_shards(mesh))

    def init(key):
        return jax.tree.map(
            lambda t: jnp.pad(t, ((0, vp - t.shape[0]), (0, 0))),
            init_params(cfg, key))

    return jax.jit(init, out_shardings=table_sharding(mesh))(key)


def shard_opt_state(params_sharded, mesh: Mesh) -> AdamState:
    """Adam moments in the exact layout of the tables; count replicated.
    Every leaf (count included) is committed to the mesh up front so round 0
    presents the same input shardings the epoch's own outputs have — an
    uncommitted count would cost one avoidable round-1 recompile."""
    sh = table_sharding(mesh)

    def zeros(p):
        return jnp.zeros(p.shape, p.dtype, device=sh)

    count = jax.device_put(jnp.zeros((), jnp.int32),
                           NamedSharding(mesh, P()))
    return AdamState(count,
                     jax.tree.map(zeros, params_sharded),
                     jax.tree.map(zeros, params_sharded))


@functools.partial(jax.jit,
                   static_argnames=("mesh", "opt", "negatives", "backend",
                                    "n_pairs", "u_in", "u_out"),
                   donate_argnums=(0, 1))
def train_epoch_sharded(params, opt_state, c, x, valid, perm2d, prob, alias,
                        key, *, mesh, opt, negatives, backend, n_pairs,
                        u_in, u_out):
    """One epoch over one round, sharded: ``lax.scan`` over the [steps,
    batch] permutation grid under ``shard_map`` on ``mesh``. Same
    (round, epoch, step) keying as the dense ``_train_epoch`` — ``key`` is
    folded per step for negatives, ``perm2d`` rows pick the batch. Returns
    (params, opt_state, per-step losses [steps]); params/opt donated."""
    vp = params["emb_in"].shape[0]
    fill = jnp.int32(vp)         # unique-buffer pad id: out of every range
    batch_size = perm2d.shape[1]

    def epoch(params_loc, opt_loc, c, x, valid, perm2d, prob, alias, key):
        n_loc = params_loc["emb_in"].shape[0]
        row0 = jax.lax.axis_index(RW_AXIS) * n_loc

        def gather(tab, u):
            # owner-masked sparse gather: my rows or +0.0, psum routes them
            loc = u - row0
            safe = jnp.where((loc >= 0) & (loc < n_loc), loc, n_loc)
            rows = tab.at[safe].get(mode="fill", fill_value=0.0)
            return jax.lax.psum(rows, RW_AXIS)

        def owner_apply(tab, mu, nu, u, g_u, count):
            # lazy row-Adam on owned rows; non-owned/fill rows redirect to
            # the out-of-range index n_loc and are dropped (never negative:
            # jax wraps negative scatter indices even under mode="drop")
            loc = u - row0
            mine = (loc >= 0) & (loc < n_loc)
            li = jnp.where(mine, loc, n_loc)
            mu_r = mu.at[li].get(mode="fill", fill_value=0.0)
            nu_r = nu.at[li].get(mode="fill", fill_value=0.0)
            upd, mu_n, nu_n = opt.update(g_u, (mu_r, nu_r), count)
            p_n = tab.at[li].get(mode="fill", fill_value=0.0) + upd
            return (tab.at[li].set(p_n, mode="drop"),
                    mu.at[li].set(mu_n, mode="drop"),
                    nu.at[li].set(nu_n, mode="drop"))

        def body(carry, s):
            p, st = carry
            idx = perm2d[s]
            in_bounds = (s * batch_size + jnp.arange(batch_size)) < n_pairs
            center, pos = c[idx], x[idx]
            neg = device_negatives(jax.random.fold_in(key, s), prob, alias,
                                   (batch_size, negatives))
            v = (valid[idx] & in_bounds).astype(jnp.float32)

            # replicated dedup: sorted unique row sets + exact positions
            uc = jnp.unique(center, size=u_in, fill_value=fill)
            inv_c = jnp.searchsorted(uc, center).astype(jnp.int32)
            uo = jnp.unique(jnp.concatenate([pos, neg.reshape(-1)]),
                            size=u_out, fill_value=fill)
            inv_p = jnp.searchsorted(uo, pos).astype(jnp.int32)
            inv_n = jnp.searchsorted(uo, neg.reshape(-1)).astype(jnp.int32)

            rows_in = gather(p["emb_in"], uc)        # [u_in, D]
            rows_out = gather(p["emb_out"], uo)      # [u_out, D]
            ci = rows_in[inv_c]
            po = rows_out[inv_p]
            no = rows_out[inv_n].reshape(batch_size, negatives, -1)
            loss_sum, g_ci, g_po, g_no = sgns_row_grads(ci, po, no, v,
                                                        backend)
            denom = jnp.maximum(jnp.sum(v), 1.0)

            # deduped scatter-add onto the unique sets — replicated, in
            # batch order, so the reduction grouping is shard-independent
            g_uc = jnp.zeros_like(rows_in).at[inv_c].add(g_ci / denom)
            g_uo = (jnp.zeros_like(rows_out)
                    .at[inv_p].add(g_po / denom)
                    .at[inv_n].add(
                        g_no.reshape(batch_size * negatives, -1) / denom))

            count = st.count + 1
            emb_in, mu_in, nu_in = owner_apply(
                p["emb_in"], st.mu["emb_in"], st.nu["emb_in"], uc, g_uc,
                count)
            emb_out, mu_out, nu_out = owner_apply(
                p["emb_out"], st.mu["emb_out"], st.nu["emb_out"], uo, g_uo,
                count)
            new = ({"emb_in": emb_in, "emb_out": emb_out},
                   AdamState(count,
                             {"emb_in": mu_in, "emb_out": mu_out},
                             {"emb_in": nu_in, "emb_out": nu_out}))
            return new, loss_sum / denom

        (params_loc, opt_loc), losses = jax.lax.scan(
            body, (params_loc, opt_loc), jnp.arange(perm2d.shape[0]))
        return params_loc, opt_loc, losses

    state_spec = AdamState(P(), P(RW_AXIS), P(RW_AXIS))
    sharded = jax.shard_map(
        epoch, mesh=mesh,
        in_specs=(P(RW_AXIS), state_spec,
                  P(), P(), P(), P(), P(), P(), P()),
        out_specs=(P(RW_AXIS), state_spec, P()), check_vma=False)
    return sharded(params, opt_state, c, x, valid, perm2d, prob, alias, key)
