"""Graph containers for Fast-Node2Vec.

Two representations:

* :class:`CSRGraph` — host-side (numpy) compressed-sparse-row graph. This is
  the build/IO format: edge lists come in, get symmetrized/deduped, and the
  per-row neighbor lists are **sorted ascending**, so every layout orders a
  vertex's slots alike and delta batches can slice rows by ``searchsorted``.

* :class:`PaddedGraph` — device-side (jnp) degree-capped padded adjacency plus
  a replicated **hot cache** holding the full rows of popular vertices. This is
  the TPU adaptation of the paper's FN-Cache: the static-shape exchange only
  ever carries rows of width ``cap`` (cold vertices); every vertex with degree
  > ``cap`` lives in the hot cache, which is replicated on all shards, so its
  neighbor list never crosses ICI (paper §3.4, FN-Cache).

Pad convention: neighbor ids are padded with ``PAD_ID`` (i32 max) so rows stay
sorted-ascending (pads sort last) and the membership test can tell pad
candidates apart; weights are padded with 0 so padded lanes carry zero
probability.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.alias import build_alias_rows

PAD_ID = np.iinfo(np.int32).max


@dataclasses.dataclass
class CSRGraph:
    """Host-side CSR graph with sorted neighbor lists."""

    n: int
    row_ptr: np.ndarray  # [n+1] int64
    col: np.ndarray      # [m]   int32, sorted within each row
    wgt: np.ndarray      # [m]   float32, > 0

    @property
    def m(self) -> int:
        return int(self.col.shape[0])

    @property
    def deg(self) -> np.ndarray:
        return (self.row_ptr[1:] - self.row_ptr[:-1]).astype(np.int32)

    @property
    def max_degree(self) -> int:
        return int(self.deg.max()) if self.n else 0

    def neighbors(self, v: int) -> np.ndarray:
        return self.col[self.row_ptr[v]:self.row_ptr[v + 1]]

    def weights(self, v: int) -> np.ndarray:
        return self.wgt[self.row_ptr[v]:self.row_ptr[v + 1]]

    @staticmethod
    def from_edges(
        n: int,
        src: np.ndarray,
        dst: np.ndarray,
        wgt: Optional[np.ndarray] = None,
        undirected: bool = True,
        dedup: bool = True,
    ) -> "CSRGraph":
        """Build a CSR graph from an edge list.

        Self loops are dropped; duplicate edges are deduped (first weight
        wins); for ``undirected`` the reverse edges are added before dedup.
        """
        src = np.asarray(src, dtype=np.int64)
        dst = np.asarray(dst, dtype=np.int64)
        if wgt is None:
            wgt = np.ones(src.shape[0], dtype=np.float32)
        wgt = np.asarray(wgt, dtype=np.float32)
        keep = src != dst
        src, dst, wgt = src[keep], dst[keep], wgt[keep]
        if undirected:
            src, dst = np.concatenate([src, dst]), np.concatenate([dst, src])
            wgt = np.concatenate([wgt, wgt])
        # sort by (src, dst); dedup
        order = np.lexsort((dst, src))
        src, dst, wgt = src[order], dst[order], wgt[order]
        if dedup and src.size:
            first = np.ones(src.shape[0], dtype=bool)
            first[1:] = (src[1:] != src[:-1]) | (dst[1:] != dst[:-1])
            src, dst, wgt = src[first], dst[first], wgt[first]
        counts = np.bincount(src, minlength=n).astype(np.int64)
        row_ptr = np.zeros(n + 1, dtype=np.int64)
        np.cumsum(counts, out=row_ptr[1:])
        return CSRGraph(n=n, row_ptr=row_ptr, col=dst.astype(np.int32),
                        wgt=wgt.astype(np.float32))

    def trim_top_weights(self, k: int) -> "CSRGraph":
        """Spark-Node2Vec's quality-destroying trim: keep only the ``k``
        highest-weight edges per vertex (paper §2.2). Used as the baseline."""
        keep_idx = []
        for v in range(self.n):
            lo, hi = self.row_ptr[v], self.row_ptr[v + 1]
            if hi - lo <= k:
                keep_idx.append(np.arange(lo, hi))
            else:
                w = self.wgt[lo:hi]
                top = np.argpartition(-w, k - 1)[:k]
                keep_idx.append(lo + np.sort(top))
        keep = np.concatenate(keep_idx) if keep_idx else np.zeros(0, np.int64)
        src = np.repeat(np.arange(self.n, dtype=np.int64),
                        [len(ix) for ix in keep_idx])
        return CSRGraph.from_edges(self.n, src, self.col[keep].astype(np.int64),
                                   self.wgt[keep], undirected=False)

    def transition_table_bytes(self) -> int:
        """Paper Eq. 1: memory to pre-store *all* 2nd-order transition
        probabilities with 8-byte alias entries — the quantity on-demand
        computation avoids."""
        d = self.deg.astype(np.int64)
        return int(8 * np.sum(d * d))


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["adj", "wgt", "deg", "alias_p", "alias_i", "w_min", "w_max",
                 "hot_pos", "hot_ids", "hot_adj", "hot_wgt", "hot_alias_p",
                 "hot_alias_i"],
    meta_fields=["n", "cap", "hot_cap"])
@dataclasses.dataclass
class PaddedGraph:
    """Device-side degree-capped adjacency + replicated hot cache.

    Invariant: every vertex with ``deg > cap`` is hot. Hot vertices' cold rows
    hold only their first ``cap`` neighbors (never read for sampling); exact
    reads for hot vertices go through the replicated hot arrays.
    """

    n: int
    cap: int              # cold row width  (D_cold)
    hot_cap: int          # hot row width   (D_hot >= max degree of hot set)
    adj: jnp.ndarray      # [n, cap]  i32, PAD_ID padded, sorted
    wgt: jnp.ndarray      # [n, cap]  f32, 0 padded
    deg: jnp.ndarray      # [n] i32   true degree
    alias_p: jnp.ndarray  # [n, cap]  f32 — 1st-order alias table (static weights)
    alias_i: jnp.ndarray  # [n, cap]  i32 — alias companion (local slot index)
    w_min: jnp.ndarray    # [n] f32  min edge weight per vertex (1.0 if isolated)
    w_max: jnp.ndarray    # [n] f32
    hot_pos: jnp.ndarray  # [n] i32  position in hot arrays, -1 if cold
    hot_ids: jnp.ndarray      # [K] i32 (K >= 1; row 0 is a dummy if no hot)
    hot_adj: jnp.ndarray      # [K, hot_cap] i32
    hot_wgt: jnp.ndarray      # [K, hot_cap] f32
    hot_alias_p: jnp.ndarray  # [K, hot_cap] f32
    hot_alias_i: jnp.ndarray  # [K, hot_cap] i32

    @property
    def num_hot(self) -> int:
        return int(self.hot_ids.shape[0])

    @staticmethod
    def build(g: CSRGraph, cap: Optional[int] = None,
              hot_cap: Optional[int] = None) -> "PaddedGraph":
        """``cap=None`` → cap = max degree (FN-Base layout: no hot set)."""
        deg = g.deg
        max_deg = g.max_degree
        if cap is None or cap >= max(max_deg, 1):
            cap = max(max_deg, 1)
        cap = max(int(cap), 1)
        hot_mask = deg > cap
        hot_vertices = np.nonzero(hot_mask)[0].astype(np.int32)
        k = max(1, len(hot_vertices))
        if hot_cap is None:
            hot_cap = int(deg[hot_vertices].max()) if len(hot_vertices) else cap
        hot_cap = max(int(hot_cap), cap)

        def pack_rows(vertices: np.ndarray, width: int):
            rows = np.full((len(vertices), width), PAD_ID, dtype=np.int32)
            wrows = np.zeros((len(vertices), width), dtype=np.float32)
            for i, v in enumerate(vertices):
                lo, hi = g.row_ptr[v], g.row_ptr[v + 1]
                d = min(int(hi - lo), width)
                rows[i, :d] = g.col[lo:lo + d]
                wrows[i, :d] = g.wgt[lo:lo + d]
            return rows, wrows

        all_v = np.arange(g.n, dtype=np.int32)
        adj, wgt = pack_rows(all_v, cap)
        if len(hot_vertices):
            hot_list = hot_vertices
            hot_adj, hot_wgt = pack_rows(hot_list, hot_cap)
        else:
            # sentinel hot set that can never match a real vertex id
            hot_list = np.full(1, PAD_ID, np.int32)
            hot_adj = np.full((1, hot_cap), PAD_ID, np.int32)
            hot_wgt = np.zeros((1, hot_cap), np.float32)

        hot_pos = np.full(g.n, -1, dtype=np.int32)
        if len(hot_vertices):
            hot_pos[hot_vertices] = np.arange(len(hot_vertices), dtype=np.int32)

        alias_p, alias_i = build_alias_rows(wgt)
        hot_alias_p, hot_alias_i = build_alias_rows(hot_wgt)

        w_min = np.ones(g.n, dtype=np.float32)
        w_max = np.ones(g.n, dtype=np.float32)
        nz = deg > 0
        # vectorized per-row min/max over the padded arrays (full row in hot)
        full_w = wgt.copy()
        if len(hot_vertices):
            pass  # cold rows of hot vertices are truncated; fix below from hot
        mask = adj != PAD_ID
        with np.errstate(invalid="ignore"):
            w_min[nz] = np.where(mask, full_w, np.inf).min(axis=1)[nz]
            w_max[nz] = np.where(mask, full_w, -np.inf).max(axis=1)[nz]
        if len(hot_vertices):
            hmask = hot_adj != PAD_ID
            w_min[hot_vertices] = np.where(hmask, hot_wgt, np.inf).min(axis=1)
            w_max[hot_vertices] = np.where(hmask, hot_wgt, -np.inf).max(axis=1)

        return PaddedGraph(
            n=g.n, cap=cap, hot_cap=hot_cap,
            adj=jnp.asarray(adj), wgt=jnp.asarray(wgt),
            deg=jnp.asarray(deg), alias_p=jnp.asarray(alias_p),
            alias_i=jnp.asarray(alias_i),
            w_min=jnp.asarray(w_min), w_max=jnp.asarray(w_max),
            hot_pos=jnp.asarray(hot_pos),
            hot_ids=jnp.asarray(hot_list),
            hot_adj=jnp.asarray(hot_adj), hot_wgt=jnp.asarray(hot_wgt),
            hot_alias_p=jnp.asarray(hot_alias_p),
            hot_alias_i=jnp.asarray(hot_alias_i),
        )
