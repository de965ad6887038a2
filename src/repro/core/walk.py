"""Single-device walk engine (FN-Base / FN-Cache / FN-Approx) — the
executable specification of the paper's Algorithm 1, and the substrate for
two ``WalkEngine`` backends:

* ``"reference"`` — all sampling in plain jnp;
* ``"fused"``     — the exact 2nd-order draw runs in the Pallas kernel
  (``kernels.node2vec_step`` via the ``kernels.ops`` padding contract),
  interpret mode on the CPU backend. Both are this module's
  ``run_reference`` with a different :class:`~repro.engine.sampler.Sampler`.

The walk is fully vectorized over walkers with a ``lax.scan`` over supersteps
(one scan iteration == one Pregel superstep; the BSP barrier is implicit in
SPMD dataflow). All sampling math lives in ``repro.engine.sampler`` —
shared, not duplicated, with the distributed engine (DESIGN.md §3).

RNG discipline: the key for walker ``i`` at step ``s`` is
``fold_in(fold_in(seed, i), s)`` — a pure function of (walker, step), never of
device layout. The distributed engine therefore produces **bit-identical**
walks (tested), which is how we validate the multi-device implementation
against this reference.

Modes:
  * ``exact``  — full 2nd-order sampling everywhere (FN-Base / FN-Cache;
    which one you get is a property of the PaddedGraph layout: cap == max
    degree -> FN-Base, cap < max degree + hot cache -> FN-Cache).
  * ``approx`` — FN-Approx: at a popular (hot) vertex v reached from an
    unpopular u, if the Eq. 2-3 bound gap < eps, sample from the *static*
    1st-order alias table: O(1) instead of O(deg) (paper §3.4).

The ``simulate_walks`` shim (deprecated in PR 7) was removed in PR 9; all
callers go through ``repro.engine.WalkEngine`` (DESIGN.md §4).
"""
from __future__ import annotations

import dataclasses
import functools
import warnings

import jax
import jax.numpy as jnp

from repro.core.graph import PAD_ID, PaddedGraph
from repro.engine.sampler import HotContext, Sampler, first_order_slots


@dataclasses.dataclass(frozen=True)
class WalkParams:
    """Legacy walk hyper-parameters. Prefer ``repro.engine.WalkPlan``, which
    adds the backend/layout knobs; this remains as the shim-level view."""
    p: float = 1.0
    q: float = 1.0
    length: int = 80
    mode: str = "exact"          # "exact" | "approx" | "approx_always"
    approx_eps: float = 1e-3

    def sampler(self, fused: bool = False) -> Sampler:
        return Sampler(p=self.p, q=self.q, mode=self.mode,
                       eps=self.approx_eps, fused=fused)


def walker_key(seed_key: jax.Array, walker_id: jnp.ndarray,
               step: jnp.ndarray) -> jax.Array:
    """Layout-independent per-(walker, step) key."""
    return jax.random.fold_in(jax.random.fold_in(seed_key, walker_id), step)


_DEPRECATION_WARNED: set = set()


def warn_deprecated_once(name: str, api: str) -> None:
    """One-shot ``DeprecationWarning`` for legacy shims (currently the
    ``load_graph``/``load_dataset`` names over ``repro.data.open_graph``).
    Shims sit on loops and fixtures, where one warning per process is
    actionable and one per call is noise."""
    if name in _DEPRECATION_WARNED:
        return
    _DEPRECATION_WARNED.add(name)
    warnings.warn(
        f"{name} is deprecated; use {api} "
        f"(this warning fires once per process)",
        DeprecationWarning, stacklevel=3)


def reset_deprecation_warnings() -> None:
    """Re-arm the one-shot shim warnings (test isolation)."""
    _DEPRECATION_WARNED.clear()


def unified_row(pg: PaddedGraph, v: jnp.ndarray):
    """Full-width (max(cap, hot_cap)) row lookup for one vertex id.

    Returns (ids, w, alias_p, alias_i, is_hot). Hot vertices read the
    replicated hot cache (exact, full degree); cold vertices read the capped
    local row. Output width is hot_cap (>= cap), pads appended.
    """
    hpos = pg.hot_pos[v]
    is_hot = hpos >= 0
    h = jnp.maximum(hpos, 0)
    width = pg.hot_cap

    def padded(x, fill):
        pad = jnp.full((width - pg.cap,), fill, x.dtype)
        return jnp.concatenate([x, pad])

    cold_ids = padded(pg.adj[v], PAD_ID)
    cold_w = padded(pg.wgt[v], 0.0)
    cold_ap = padded(pg.alias_p[v], 0.0)
    cold_ai = padded(pg.alias_i[v], 0)
    ids = jnp.where(is_hot, pg.hot_adj[h], cold_ids)
    w = jnp.where(is_hot, pg.hot_wgt[h], cold_w)
    ap = jnp.where(is_hot, pg.hot_alias_p[h], cold_ap)
    ai = jnp.where(is_hot, pg.hot_alias_i[h], cold_ai)
    return ids, w, ap, ai, is_hot


def _batched_rows(pg: PaddedGraph, v: jnp.ndarray):
    return jax.vmap(lambda vv: unified_row(pg, vv))(v)


@functools.partial(jax.jit, static_argnames=("sampler", "length"))
def _simulate(pg: PaddedGraph, starts: jnp.ndarray, walker_ids: jnp.ndarray,
              seed_key: jax.Array, sampler: Sampler, length: int):
    # step 0: 1st-order draw from static edge weights via the alias table
    with jax.named_scope("walk.rng"):
        k0 = jax.vmap(lambda i: walker_key(seed_key, i, 0))(walker_ids)
    with jax.named_scope("walk.rows"):
        ids0, _, ap0, ai0, _ = _batched_rows(pg, starts)
        deg0 = pg.deg[starts]
    with jax.named_scope("walk.draw"):
        slot0 = first_order_slots(k0, ap0, ai0, deg0)
        nxt0 = jnp.take_along_axis(ids0, slot0[:, None], axis=1)[:, 0]
        v1 = jnp.where(deg0 > 0, nxt0, starts)

    def body(carry, s):
        u, v, prev_ids = carry
        with jax.named_scope("walk.rng"):
            keys = jax.vmap(lambda i: walker_key(seed_key, i, s))(walker_ids)
        with jax.named_scope("walk.rows"):
            ids, w, ap, ai, is_hot = _batched_rows(pg, v)
            deg_v = pg.deg[v]
            hot = None
            if sampler.mode != "exact":
                hot = HotContext(
                    is_hot_v=is_hot, is_hot_u=pg.hot_pos[u] >= 0,
                    deg_u=pg.deg[u], deg_v=deg_v,
                    w_min_v=pg.w_min[v], w_max_v=pg.w_max[v],
                    alias_p=ap, alias_i=ai, alias_deg=deg_v)
        choice = sampler.choose(keys, ids, w, u, prev_ids, hot)
        with jax.named_scope("walk.draw"):
            nxt = jnp.take_along_axis(ids, choice.slot()[:, None],
                                      axis=1)[:, 0]
            nxt = jnp.where(deg_v > 0, nxt, v)  # dead end: stay
        return (v, nxt, ids), v

    (_, v_last, _), steps = jax.lax.scan(
        body, (starts, v1, ids0), jnp.arange(1, length, dtype=jnp.int32))
    # walks[:, 0] = first sampled step, then one column per later step
    walks = jnp.concatenate(
        [steps.T, v_last[:, None]], axis=1) if length > 1 else v1[:, None]
    return walks


def run_reference(pg: PaddedGraph, starts: jnp.ndarray,
                  walker_ids: jnp.ndarray, seed_key: jax.Array,
                  sampler: Sampler, length: int) -> jnp.ndarray:
    """Single-device backend entry point used by ``WalkEngine``."""
    return _simulate(pg, starts, walker_ids, seed_key, sampler=sampler,
                     length=length)
