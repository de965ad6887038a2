"""Distributed Fast-Node2Vec walk engine (shard_map over the device mesh) —
the ``"sharded"`` backend of ``repro.engine.WalkEngine``.

Pregel -> TPU-SPMD mapping (see DESIGN.md §2):

* One Pregel **superstep** == one iteration of a ``lax.scan``; the BSP barrier
  is the collective itself.
* The graph is **range-partitioned** by vertex id across the flattened mesh
  axis ``rw`` (all devices of the production mesh). Walkers are co-located
  with their start vertex, so the paper's STEP messages (sampled step sent
  back to the start vertex) become *local buffer writes* — zero traffic.
* The paper's NEIG message (neighbor list of the current vertex) becomes a
  **pull**: a two-phase ``all_to_all`` — request ids out, neighbor rows back.
  - FN-Local: the diagonal block of the all_to_all never crosses ICI, and
    fully-local requests skip the exchange entirely.
  - FN-Cache: rows of every vertex with degree > cap are replicated in the
    hot cache, so popular vertices never enter the exchange and the payload
    width is the *cold* cap, not the max degree. This is the statically
    visible collective-bytes reduction measured in the roofline.
  - FN-Approx: at a hot v reached from a cold u, if the Eq. 2-3 gap < eps the
    step is an O(1) alias draw from the replicated table — no wide prob row.
* The NEIG payload for the *next* step's dist(u, x) test is the row we just
  fetched — carried in walker state (Algorithm 1 line 22), cold width only;
  hot prev rows are re-read from the replicated cache at compute time.

All sampling math (exact inverse-CDF draw, approx gating, alias fast path)
lives in ``repro.engine.sampler`` and is shared verbatim with the reference
and fused backends; this module only owns the *layout*: partitioning, the
request/response exchange, and the candidate-row assembly.

RNG keys are ``fold_in(seed, global_walker_id, step)`` — identical to the
single-device reference, so distributed walks are **bit-identical** to
the reference backend (validated in tests).

Capacity: the request exchange has a static per-destination capacity ``C``
*per exchange*. Requests beyond C are *dropped* (walker stays put for that
step) and counted in the returned diagnostics (surfaced as
``WalkStats.dropped``); exact-mode callers size C so drops are zero (tests
assert this). The paper's FN-Multi (walker rounds) is the production lever
for bounding C — see ``runtime/fault_tolerance.py``.

Async superstep pipeline (``WalkPlan.pipeline``, DESIGN.md §12): walkers on
each shard split into two fixed cohorts (A = first ceil(W/2) local rows).
The barrier loop's issue-exchange/compute halves are re-interleaved so
cohort B's step-k NEIG exchange is on the wire while cohort A's walkers
advance through step k, and A's step-(k+1) exchange issues before B's
step-k compute — each collective hides behind the other cohort's sampling
work. Cohorts never read each other's state and per-(walker, step) RNG keys
are layout-independent, so pipelined walks are **bit-identical** to barrier
walks (tested). The last superstep is peeled out of the scan so no dangling
exchange is issued past the end of the walk. Cohort exchanges carry half
the walkers, so the zero-drop capacity default also halves — per-superstep
total bytes stay at the barrier level, split across two overlapped
messages.

The ``distributed_walks`` shim (deprecated in PR 7) was removed in PR 9;
all callers go through ``repro.engine.WalkEngine`` (DESIGN.md §4).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.graph import PAD_ID, PaddedGraph
from repro.core.walk import WalkParams, walker_key
from repro.engine.sampler import HotContext, Sampler, first_order_slots

RW_AXIS = "rw"


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["adj", "wgt", "alias_p", "alias_i", "deg", "hot_ids",
                 "hot_adj", "hot_wgt", "hot_alias_p", "hot_alias_i",
                 "hot_deg", "hot_wmin", "hot_wmax"],
    meta_fields=["n", "n_orig", "num_shards", "cap", "hot_cap"])
@dataclasses.dataclass
class ShardedGraph:
    """Host-built container of device-ready arrays for the sharded engine.

    Row-sharded over ``rw``: adj, wgt, alias_p, alias_i, deg.
    Replicated: hot arrays + per-hot-vertex scalars. :meth:`place` puts
    them on a mesh in that layout.
    """
    n: int            # padded vertex count (multiple of num_shards)
    n_orig: int
    num_shards: int
    cap: int
    hot_cap: int
    adj: jnp.ndarray          # [n, cap]
    wgt: jnp.ndarray          # [n, cap]
    alias_p: jnp.ndarray      # [n, cap]
    alias_i: jnp.ndarray      # [n, cap]
    deg: jnp.ndarray          # [n]
    hot_ids: jnp.ndarray      # [K] sorted ascending
    hot_adj: jnp.ndarray      # [K, hot_cap]
    hot_wgt: jnp.ndarray      # [K, hot_cap]
    hot_alias_p: jnp.ndarray  # [K, hot_cap]
    hot_alias_i: jnp.ndarray  # [K, hot_cap]
    hot_deg: jnp.ndarray      # [K]
    hot_wmin: jnp.ndarray     # [K]
    hot_wmax: jnp.ndarray     # [K]

    @property
    def n_local(self) -> int:
        return self.n // self.num_shards

    def hot_pack(self) -> tuple:
        return (self.hot_ids, self.hot_adj, self.hot_wgt, self.hot_alias_p,
                self.hot_alias_i, self.hot_deg, self.hot_wmin, self.hot_wmax)

    def place(self, mesh: Mesh) -> "ShardedGraph":
        """The same graph laid out on ``mesh`` as the walk program reads
        it, so no call reshards it and no device holds all the rows."""
        rows = NamedSharding(mesh, P(RW_AXIS))
        rep = NamedSharding(mesh, P())
        row_fields = ("adj", "wgt", "alias_p", "alias_i", "deg")
        return dataclasses.replace(self, **{
            f: jax.device_put(getattr(self, f),
                              rows if f in row_fields else rep)
            for f in row_fields + ("hot_ids", "hot_adj", "hot_wgt",
                                   "hot_alias_p", "hot_alias_i", "hot_deg",
                                   "hot_wmin", "hot_wmax")})

    @staticmethod
    def from_csr(g, num_shards: int, cap: Optional[int] = None,
                 hot_cap: Optional[int] = None) -> "ShardedGraph":
        """Shard-by-shard build straight from a host :class:`CSRGraph`.

        Packs each shard's padded rows (and alias tables) directly from CSR
        slices into the preallocated output arrays — no dense whole-graph
        :class:`PaddedGraph` intermediate (that path materializes a second
        full [n, cap] copy plus per-vertex scalars the sharded engine never
        reads). Bit-identical to
        ``ShardedGraph.build(PaddedGraph.build(g, cap, hot_cap), n)``
        (asserted in tests), including the no-hot sentinel row.
        """
        from repro.core.alias import build_alias_rows

        deg = g.deg                                   # [n] i32
        max_deg = g.max_degree
        if cap is None or cap >= max(max_deg, 1):
            cap = max(max_deg, 1)
        cap = max(int(cap), 1)
        hot_vertices = np.nonzero(deg > cap)[0].astype(np.int32)
        if hot_cap is None:
            hot_cap = int(deg[hot_vertices].max()) if len(hot_vertices) \
                else cap
        hot_cap = max(int(hot_cap), cap)
        n = g.n
        n_pad = ((n + num_shards - 1) // num_shards) * num_shards
        n_local = n_pad // num_shards

        def pack_block(vertices, out_adj, out_wgt):
            width = out_adj.shape[1]
            for i, v in enumerate(vertices):
                lo, hi = g.row_ptr[v], g.row_ptr[v + 1]
                d = min(int(hi - lo), width)
                out_adj[i, :d] = g.col[lo:lo + d]
                out_wgt[i, :d] = g.wgt[lo:lo + d]

        adj = np.full((n_pad, cap), PAD_ID, np.int32)
        wgt = np.zeros((n_pad, cap), np.float32)
        alias_p = np.zeros((n_pad, cap), np.float32)
        alias_i = np.zeros((n_pad, cap), np.int32)
        alias_p[n:] = 1.0                   # padding rows: build()'s pad fill
        deg_pad = np.zeros(n_pad, np.int32)
        deg_pad[:n] = deg
        for s in range(num_shards):
            lo_v, hi_v = s * n_local, min((s + 1) * n_local, n)
            if hi_v <= lo_v:
                break
            pack_block(range(lo_v, hi_v), adj[lo_v:hi_v], wgt[lo_v:hi_v])
            ap, ai = build_alias_rows(wgt[lo_v:hi_v])
            alias_p[lo_v:hi_v] = ap
            alias_i[lo_v:hi_v] = ai

        def row_min_max(v, width):
            lo = g.row_ptr[v]
            d = min(int(g.row_ptr[v + 1] - lo), width)
            if d == 0:
                return 1.0, 1.0
            w = g.wgt[lo:lo + d]
            return float(w.min()), float(w.max())

        if len(hot_vertices):
            k = len(hot_vertices)
            hot_ids = hot_vertices
            hot_adj = np.full((k, hot_cap), PAD_ID, np.int32)
            hot_wgt = np.zeros((k, hot_cap), np.float32)
            pack_block(hot_vertices, hot_adj, hot_wgt)
            hot_deg = deg[hot_vertices]
            mm = np.array([row_min_max(int(v), hot_cap)
                           for v in hot_vertices], np.float32)
            hot_wmin, hot_wmax = mm[:, 0], mm[:, 1]
        else:
            # sentinel row; the scalar lanes mirror build()'s clamped
            # pg.deg[PAD_ID] / w_min[PAD_ID] gathers (last real vertex)
            hot_ids = np.full(1, PAD_ID, np.int32)
            hot_adj = np.full((1, hot_cap), PAD_ID, np.int32)
            hot_wgt = np.zeros((1, hot_cap), np.float32)
            hot_deg = deg[n - 1:n]
            wmin, wmax = row_min_max(n - 1, cap)
            hot_wmin = np.full(1, wmin, np.float32)
            hot_wmax = np.full(1, wmax, np.float32)
        hot_alias_p, hot_alias_i = build_alias_rows(hot_wgt)

        # host arrays: place() uploads each shard's rows to its own device
        return ShardedGraph(
            n=n_pad, n_orig=n, num_shards=num_shards, cap=cap,
            hot_cap=hot_cap, adj=adj, wgt=wgt, alias_p=alias_p,
            alias_i=alias_i, deg=deg_pad, hot_ids=hot_ids, hot_adj=hot_adj,
            hot_wgt=hot_wgt, hot_alias_p=hot_alias_p,
            hot_alias_i=hot_alias_i, hot_deg=hot_deg, hot_wmin=hot_wmin,
            hot_wmax=hot_wmax)

    @staticmethod
    def build(pg: PaddedGraph, num_shards: int) -> "ShardedGraph":
        n_pad = ((pg.n + num_shards - 1) // num_shards) * num_shards

        def pad_rows(x, fill):
            if n_pad == pg.n:
                return x
            pad = jnp.full((n_pad - pg.n,) + x.shape[1:], fill, x.dtype)
            return jnp.concatenate([x, pad], axis=0)

        hot_deg = pg.deg[pg.hot_ids]
        hot_wmin = pg.w_min[pg.hot_ids]
        hot_wmax = pg.w_max[pg.hot_ids]
        return ShardedGraph(
            n=n_pad, n_orig=pg.n, num_shards=num_shards, cap=pg.cap,
            hot_cap=pg.hot_cap,
            adj=pad_rows(pg.adj, PAD_ID), wgt=pad_rows(pg.wgt, 0.0),
            alias_p=pad_rows(pg.alias_p, 1.0),
            alias_i=pad_rows(pg.alias_i, 0),
            deg=pad_rows(pg.deg, 0),
            hot_ids=pg.hot_ids, hot_adj=pg.hot_adj, hot_wgt=pg.hot_wgt,
            hot_alias_p=pg.hot_alias_p, hot_alias_i=pg.hot_alias_i,
            hot_deg=hot_deg, hot_wmin=hot_wmin, hot_wmax=hot_wmax)


def _hot_lookup(hot_ids: jnp.ndarray, v: jnp.ndarray):
    """Replicated hot-set membership: (is_hot, position)."""
    k = hot_ids.shape[0]
    pos = jnp.minimum(jnp.searchsorted(hot_ids, v), k - 1)
    return hot_ids[pos] == v, pos


def _bucket_requests(dest: jnp.ndarray, needs_remote: jnp.ndarray,
                     v: jnp.ndarray, num_shards: int, capacity: int):
    """Pack remote requests into per-destination slots of width ``capacity``.

    Returns (buf [S*C] request ids, slot_of_walker [W] (-1 if none), dropped
    mask [W]). Deterministic: walkers are ranked by (dest, walker order).
    """
    w = dest.shape[0]
    sort_key = jnp.where(needs_remote, dest, num_shards)
    order = jnp.argsort(sort_key, stable=True)
    sorted_key = sort_key[order]
    first = jnp.searchsorted(sorted_key, sorted_key, side="left")
    rank_sorted = jnp.arange(w, dtype=jnp.int32) - first.astype(jnp.int32)
    rank = jnp.zeros_like(rank_sorted).at[order].set(rank_sorted)
    ok = needs_remote & (rank < capacity)
    size = num_shards * capacity
    # slot==size is a scratch lane for every non-request; sliced off below.
    slot = jnp.where(ok, dest * capacity + rank, size)
    buf = jnp.full((size + 1,), PAD_ID, jnp.int32)
    buf = buf.at[slot].set(v)[:size]
    slot = jnp.where(ok, slot, -1)
    dropped = needs_remote & ~ok
    return buf, slot, dropped


def _serve_requests(g: ShardedGraph, adj, wgt, recv_ids: jnp.ndarray,
                    shard_offset: jnp.ndarray):
    """Gather local rows for incoming request ids [R]. PAD_ID -> pad row."""
    local = jnp.clip(recv_ids - shard_offset, 0, adj.shape[0] - 1)
    valid = recv_ids != PAD_ID
    ids = jnp.where(valid[:, None], adj[local], PAD_ID)
    w = jnp.where(valid[:, None], wgt[local], 0.0)
    return ids, w


def _widen(x: jnp.ndarray, width: int, fill) -> jnp.ndarray:
    d = x.shape[-1]
    if d >= width:
        return x
    pad = jnp.full(x.shape[:-1] + (width - d,), fill, x.dtype)
    return jnp.concatenate([x, pad], axis=-1)


def _issue_exchange(g: ShardedGraph, adj, wgt, v, capacity: int):
    """Issue the two-phase NEIG pull for a walker cohort at positions ``v``.

    This is the *communication half* of a superstep: bucket the remote
    requests, all_to_all the ids out, gather the local rows for incoming
    requests, and all_to_all the rows back. It depends only on ``v`` (and
    the graph), never on the sampling state, so the pipelined walk body can
    issue one cohort's exchange before (= overlapped with) the other
    cohort's compute. Returns the exchange state consumed by
    ``_finish_step``: (resp_i [S*C, cap], resp_w, slot [Wc], dropped [Wc]).
    """
    num_shards = g.num_shards
    n_local = adj.shape[0]
    my_shard = jax.lax.axis_index(RW_AXIS)
    shard_offset = my_shard.astype(jnp.int32) * n_local

    is_hot_v, _ = _hot_lookup(g.hot_ids, v)
    dest = (v // n_local).astype(jnp.int32)
    is_local = dest == my_shard
    needs_remote = (~is_hot_v) & (~is_local)

    buf, slot, dropped = _bucket_requests(dest, needs_remote, v, num_shards,
                                          capacity)
    req = buf.reshape(num_shards, capacity)
    recv = jax.lax.all_to_all(req, RW_AXIS, split_axis=0, concat_axis=0,
                              tiled=True)
    rows_i, rows_w = _serve_requests(g, adj, wgt, recv.reshape(-1),
                                     shard_offset)
    rows_i = rows_i.reshape(num_shards, capacity, g.cap)
    rows_w = rows_w.reshape(num_shards, capacity, g.cap)
    resp_i = jax.lax.all_to_all(rows_i, RW_AXIS, 0, 0, tiled=True)
    resp_w = jax.lax.all_to_all(rows_w, RW_AXIS, 0, 0, tiled=True)
    resp_i = resp_i.reshape(num_shards * capacity, g.cap)
    resp_w = resp_w.reshape(num_shards * capacity, g.cap)
    return resp_i, resp_w, slot, dropped


def _finish_step(g: ShardedGraph, adj, wgt, u, v, prev_ids, prev_deg, step,
                 seed_key, walker_ids, sampler: Sampler, exchange):
    """Compute half of a superstep: candidate assembly + the 2nd-order draw,
    given the already-exchanged NEIG responses for this cohort."""
    resp_i, resp_w, slot, dropped = exchange
    n_local = adj.shape[0]
    my_shard = jax.lax.axis_index(RW_AXIS)
    shard_offset = my_shard.astype(jnp.int32) * n_local
    is_hot_v, hot_pos_v = _hot_lookup(g.hot_ids, v)

    # --- assemble candidate rows per walker (local / remote / hot) ---
    v_local_idx = jnp.clip(v - shard_offset, 0, n_local - 1)
    local_i, local_w = adj[v_local_idx], wgt[v_local_idx]
    safe_slot = jnp.maximum(slot, 0)
    remote_i, remote_w = resp_i[safe_slot], resp_w[safe_slot]
    use_remote = slot >= 0
    cold_i = jnp.where(use_remote[:, None], remote_i, local_i)
    cold_w = jnp.where(use_remote[:, None], remote_w, local_w)
    hp = jnp.maximum(hot_pos_v, 0)
    if sampler.mode == "approx_always":
        # beyond-paper FN-Approx: popular vertices ALWAYS take the O(1)
        # alias path, so the exact-prob pass runs at cold width only and the
        # [W, hot_cap] candidate assembly disappears entirely (static shapes
        # otherwise evaluate both branches — see EXPERIMENTS.md §Perf).
        cand_i = _widen(cold_i, g.cap, PAD_ID)
        cand_w = _widen(cold_w, g.cap, 0.0)
    else:
        cand_i = jnp.where(is_hot_v[:, None], g.hot_adj[hp],
                           _widen(cold_i, g.hot_cap, PAD_ID))
        cand_w = jnp.where(is_hot_v[:, None], g.hot_wgt[hp],
                           _widen(cold_w, g.hot_cap, 0.0))

    # --- previous row for dist(u, x): carried if cold, cache if hot ---
    is_hot_u, hot_pos_u = _hot_lookup(g.hot_ids, u)
    hpu = jnp.maximum(hot_pos_u, 0)
    prev_row = jnp.where(is_hot_u[:, None], g.hot_adj[hpu],
                         _widen(prev_ids, g.hot_cap, PAD_ID))
    deg_u = jnp.where(is_hot_u, g.hot_deg[hpu], prev_deg)

    # --- 2nd-order sampling: the shared Sampler (same math, all backends) ---
    keys = jax.vmap(lambda i: walker_key(seed_key, i, step))(walker_ids)
    hot = None
    if sampler.mode != "exact":
        hot = HotContext(
            is_hot_v=is_hot_v, is_hot_u=is_hot_u,
            deg_u=deg_u, deg_v=g.hot_deg[hp],
            w_min_v=g.hot_wmin[hp], w_max_v=g.hot_wmax[hp],
            alias_p=g.hot_alias_p[hp], alias_i=g.hot_alias_i[hp],
            alias_deg=g.hot_deg[hp])
    choice = sampler.choose(keys, cand_i, cand_w, u, prev_row, hot)
    if sampler.mode == "approx_always":
        # candidates stayed at cold width: hot next-ids come straight from
        # the replicated cache ([W] gather, O(1)/walker)
        nxt_hot = g.hot_adj[hp, choice.slot_alias]
        nxt_cold = jnp.take_along_axis(cand_i, choice.slot_exact[:, None],
                                       axis=1)[:, 0]
        nxt = jnp.where(choice.use_alias, nxt_hot, nxt_cold)
    else:
        nxt = jnp.take_along_axis(cand_i, choice.slot()[:, None],
                                  axis=1)[:, 0]
    deg_v = jnp.sum(cand_w > 0, axis=1).astype(jnp.int32)
    if sampler.mode == "approx_always":
        deg_v = jnp.where(is_hot_v, g.hot_deg[hp], deg_v)
    alive = (deg_v > 0) & ~dropped
    nxt = jnp.where(alive, nxt, v)

    # carried NEIG payload for the next step (cold width)
    new_prev_ids = jnp.where(is_hot_v[:, None], PAD_ID, cold_i)
    return nxt, new_prev_ids, deg_v, dropped


def _sharded_step(g: ShardedGraph, adj, wgt, alias_p, alias_i, deg,
                  u, v, prev_ids, prev_deg, step, seed_key, walker_ids,
                  sampler: Sampler, capacity: int):
    """One barrier superstep for the local walker block: exchange, then
    compute — the two halves back-to-back (runs inside shard_map)."""
    exchange = _issue_exchange(g, adj, wgt, v, capacity)
    return _finish_step(g, adj, wgt, u, v, prev_ids, prev_deg, step,
                        seed_key, walker_ids, sampler, exchange)


def _first_step_local(g: ShardedGraph, adj, wgt, alias_p, alias_i, deg,
                      starts, seed_key, walker_ids):
    """Step 0: starts are local by construction; 1st-order alias draw."""
    my_shard = jax.lax.axis_index(RW_AXIS)
    n_local = adj.shape[0]
    off = my_shard.astype(jnp.int32) * n_local
    li = jnp.clip(starts - off, 0, n_local - 1)
    is_hot, hp = _hot_lookup(g.hot_ids, starts)
    hp = jnp.maximum(hp, 0)
    ap = jnp.where(is_hot[:, None], g.hot_alias_p[hp],
                   _widen(alias_p[li], g.hot_cap, 0.0))
    ai = jnp.where(is_hot[:, None], g.hot_alias_i[hp],
                   _widen(alias_i[li], g.hot_cap, 0))
    ids = jnp.where(is_hot[:, None], g.hot_adj[hp],
                    _widen(adj[li], g.hot_cap, PAD_ID))
    keys = jax.vmap(lambda i: walker_key(seed_key, i, 0))(walker_ids)
    slots = first_order_slots(keys, ap, ai, deg[li])
    nxt = jnp.take_along_axis(ids, slots[:, None], axis=1)[:, 0]
    nxt = jnp.where(deg[li] > 0, nxt, starts)
    prev_ids = adj[li]
    prev_deg = deg[li]
    return nxt, prev_ids, prev_deg


def make_distributed_walk(g: ShardedGraph, mesh: Mesh, params: WalkParams,
                          capacity: int, length: Optional[int] = None,
                          pipeline: bool = False):
    """Build the jitted distributed walk fn over ``mesh`` (all axes flattened
    into the ``rw`` axis via an abstract mesh reshape is the caller's job —
    this function expects a 1-D mesh with axis name 'rw').

    ``pipeline=True`` selects the double-buffered async-superstep body: the
    local walker block is split into two independent cohorts (A = first
    ceil(W/2) rows, B = the rest; walks are per-walker so any split is
    legal), and each cohort's NEIG exchange is issued in program order
    *before* the other cohort's compute — on hardware with async collectives
    the exchange hides behind the sampling work (DESIGN.md §12). ``capacity``
    is per destination *per exchange* in both modes; because a cohort is a
    subset of the block, a walker's within-cohort request rank never exceeds
    its barrier-mode rank, so pipelined drops are a subset of barrier drops
    at equal capacity (and both are zero at the engine's defaults). Walks
    are bit-identical to the barrier body (tested).
    """
    length = length or params.length
    sampler = params.sampler() if isinstance(params, WalkParams) else params
    pspec_rows = P(RW_AXIS)
    rep = P()

    def make_local(hot_pack):
        return dataclasses.replace(
            g, hot_ids=hot_pack[0], hot_adj=hot_pack[1], hot_wgt=hot_pack[2],
            hot_alias_p=hot_pack[3], hot_alias_i=hot_pack[4],
            hot_deg=hot_pack[5], hot_wmin=hot_pack[6], hot_wmax=hot_pack[7])

    def walk_body(adj, wgt, alias_p, alias_i, deg, hot_pack, starts,
                  walker_ids, seed_key):
        gl = make_local(hot_pack)
        v1, prev_ids, prev_deg = _first_step_local(
            gl, adj, wgt, alias_p, alias_i, deg, starts, seed_key, walker_ids)

        def body(carry, s):
            u, v, p_ids, p_deg, drops = carry
            nxt, np_ids, deg_v, dropped = _sharded_step(
                gl, adj, wgt, alias_p, alias_i, deg, u, v, p_ids, p_deg, s,
                seed_key, walker_ids, sampler, capacity)
            drops = drops + jnp.sum(dropped.astype(jnp.int32))
            return (v, nxt, np_ids, deg_v, drops), v

        init = (starts, v1, prev_ids, prev_deg, jnp.zeros((), jnp.int32))
        (_, v_last, _, _, drops), steps = jax.lax.scan(
            body, init, jnp.arange(1, length, dtype=jnp.int32))
        walks = jnp.concatenate([steps.T, v_last[:, None]], axis=1)
        return walks, jax.lax.psum(drops, RW_AXIS)

    def walk_body_pipelined(adj, wgt, alias_p, alias_i, deg, hot_pack,
                            starts, walker_ids, seed_key):
        gl = make_local(hot_pack)
        w_local = starts.shape[0]
        wa = (w_local + 1) // 2          # cohort A size (static)
        v1, prev_ids, prev_deg = _first_step_local(
            gl, adj, wgt, alias_p, alias_i, deg, starts, seed_key, walker_ids)

        def split(x):
            return x[:wa], x[wa:]

        u_a, u_b = split(starts)
        v_a, v_b = split(v1)
        p_a, p_b = split(prev_ids)
        pd_a, pd_b = split(prev_deg)
        wid_a, wid_b = split(walker_ids)

        def finish(u, v, p_ids, p_deg, wids, s, exch):
            return _finish_step(gl, adj, wgt, u, v, p_ids, p_deg, s,
                                seed_key, wids, sampler, exch)

        # pipeline prologue: A's step-1 exchange (nothing to hide behind)
        exch_a = _issue_exchange(gl, adj, wgt, v_a, capacity)

        def body(carry, s):
            (u_a, v_a, p_a, pd_a, u_b, v_b, p_b, pd_b, exch_a, drops) = carry
            # B's step-s exchange: issued BEFORE A's compute — overlaps it
            exch_b = _issue_exchange(gl, adj, wgt, v_b, capacity)
            nxt_a, np_a, deg_a, drop_a = finish(u_a, v_a, p_a, pd_a, wid_a,
                                                s, exch_a)
            # A's step-(s+1) exchange: issued BEFORE B's compute
            exch_a = _issue_exchange(gl, adj, wgt, nxt_a, capacity)
            nxt_b, np_b, deg_b, drop_b = finish(u_b, v_b, p_b, pd_b, wid_b,
                                                s, exch_b)
            drops = drops + jnp.sum(drop_a.astype(jnp.int32)) \
                + jnp.sum(drop_b.astype(jnp.int32))
            emit = jnp.concatenate([v_a, v_b])
            return (v_a, nxt_a, np_a, deg_a, v_b, nxt_b, np_b, deg_b,
                    exch_a, drops), emit

        init = (u_a, v_a, p_a, pd_a, u_b, v_b, p_b, pd_b, exch_a,
                jnp.zeros((), jnp.int32))
        # peel the last superstep so no dangling prefetch is ever issued
        carry, steps = jax.lax.scan(
            body, init, jnp.arange(1, length - 1, dtype=jnp.int32))
        (u_a, v_a, p_a, pd_a, u_b, v_b, p_b, pd_b, exch_a, drops) = carry
        s_last = jnp.asarray(length - 1, jnp.int32)
        exch_b = _issue_exchange(gl, adj, wgt, v_b, capacity)
        nxt_a, _, _, drop_a = finish(u_a, v_a, p_a, pd_a, wid_a, s_last,
                                     exch_a)
        nxt_b, _, _, drop_b = finish(u_b, v_b, p_b, pd_b, wid_b, s_last,
                                     exch_b)
        drops = drops + jnp.sum(drop_a.astype(jnp.int32)) \
            + jnp.sum(drop_b.astype(jnp.int32))
        v_prev = jnp.concatenate([v_a, v_b])
        v_last = jnp.concatenate([nxt_a, nxt_b])
        walks = jnp.concatenate(
            [steps.T, v_prev[:, None], v_last[:, None]], axis=1) \
            if length > 2 else jnp.concatenate(
                [v_prev[:, None], v_last[:, None]], axis=1)
        return walks, jax.lax.psum(drops, RW_AXIS)

    # length 1 has no exchanging supersteps — nothing to pipeline
    body_fn = walk_body_pipelined if pipeline and length >= 2 else walk_body
    shard_fn = jax.shard_map(
        body_fn, mesh=mesh,
        in_specs=(pspec_rows, pspec_rows, pspec_rows, pspec_rows, pspec_rows,
                  rep, pspec_rows, pspec_rows, rep),
        out_specs=(pspec_rows, rep), check_vma=False)
    return jax.jit(shard_fn)
