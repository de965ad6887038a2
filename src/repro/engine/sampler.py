"""Shared 2nd-order sampling layer — the ``Sampler`` strategy (DESIGN.md §3).

Every walk backend draws the next step through this one implementation:

* ``repro.core.walk``             — single-device reference engine (vmap),
  which is also the **fused** backend: ``Sampler(fused=True)`` swaps the
  exact-slot computation for the Pallas kernel ``kernels.node2vec_step``
  (interpret mode on the CPU backend) with bit-identical results.
* ``repro.core.walk_distributed`` — shard_map engine; candidate rows arrive
  via the NEIG all_to_all instead of a local gather, but the sampling math is
  this module, not a copy.
* ``kernels/ref.py``              — the kernel's correctness oracle wraps
  :func:`exact_slots` directly, so the contract is written exactly once.

RNG contract (identical across backends, the bit-parity guarantee):
given the per-(walker, step) key ``k = fold_in(fold_in(seed, walker), step)``:

    k_exact, k_approx = split(k)
    r          = uniform(k_exact)                     # ONE uniform per walker
    slot_exact = count((prefix_sum(alpha * w) <= r * total) & valid)  # inv. CDF
    slot_alias = alias_sample(k_approx, ...)          # O(1) fast path

The Pallas kernel calls the same :func:`draw_slots`, so it matches bit for
bit; ``prefix_sum`` and ``total`` (the sum at the last live lane) do not
depend on the padded row width — FN-Base and FN-Cache layouts, and all three
backends, produce identical walks.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.alias import alias_sample
from repro.core.graph import PAD_ID
from repro.core.transition import approx_gap, unnormalized_probs

MODES = ("exact", "approx", "approx_always")


def split_keys(keys: jax.Array):
    """Per-walker (k_exact, k_approx) from a [W]-batch of step keys."""
    k_exact = jax.vmap(lambda k: jax.random.split(k)[0])(keys)
    k_approx = jax.vmap(lambda k: jax.random.split(k)[1])(keys)
    return k_exact, k_approx


def _roll_lanes(x: jnp.ndarray, k: int) -> jnp.ndarray:
    return jnp.roll(x, k, axis=-1)


def prefix_sum(x: jnp.ndarray, roll=_roll_lanes) -> jnp.ndarray:
    """Inclusive prefix sum over the last axis of a 2-D array, as
    log2(width) shift-and-add rounds (Hillis-Steele).

    Every element's sum is a fixed tree of f32 adds, so the XLA path
    (``roll`` = ``jnp.roll``) and the Pallas kernel (``pltpu.roll``) round
    identically — ``jnp.cumsum`` leaves the order to each backend. Lane i
    only ever adds lanes <= i, and rounds past i add exact zeros, so the
    value at a live lane does not depend on how far the row is padded.
    """
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    k = 1
    while k < x.shape[-1]:
        x = x + jnp.where(lane >= k, roll(x, k), 0.0)
        k *= 2
    return x


def draw_slots(probs: jnp.ndarray, valid: jnp.ndarray, rand: jnp.ndarray,
               roll=_roll_lanes) -> jnp.ndarray:
    """Inverse-CDF draw over unnormalized ``probs`` [W, D] whose ``valid``
    lanes are a prefix of each row; ``rand`` [W, 1] in [0, 1). Returns the
    slot [W, 1] i32: the count of live prefix sums <= rand * total, where
    total is the prefix sum at the last live lane (0 for an empty row)."""
    cum = prefix_sum(probs, roll)
    lane = jax.lax.broadcasted_iota(jnp.int32, probs.shape, 1)
    live = jnp.sum(valid.astype(jnp.int32), axis=-1, keepdims=True)
    total = jnp.sum(jnp.where(lane == live - 1, cum, 0.0), axis=-1,
                    keepdims=True)
    slot = jnp.sum(((cum <= rand * total) & valid).astype(jnp.int32),
                   axis=-1, keepdims=True)
    return jnp.minimum(slot, jnp.maximum(live - 1, 0))


def exact_slots(cand_ids: jnp.ndarray, cand_w: jnp.ndarray, u: jnp.ndarray,
                prev_rows: jnp.ndarray, rand: jnp.ndarray, p: float,
                q: float) -> jnp.ndarray:
    """Batched exact 2nd-order draw — THE definition the Pallas kernel fuses.

    cand_ids/cand_w [W, D] (PAD_ID / 0 padded, rows sorted), u [W],
    prev_rows [W, Dp] (sorted N(u)), rand [W] uniforms in [0, 1).
    Returns the sampled candidate slot per walker, [W] i32.
    """
    with jax.named_scope("walk.probs"):
        probs = jax.vmap(
            lambda ci, cw, uu, pr: unnormalized_probs(ci, cw, uu, pr, p, q))(
                cand_ids, cand_w, u, prev_rows)
    with jax.named_scope("walk.draw"):
        return draw_slots(probs, cand_ids != PAD_ID, rand[:, None])[:, 0]


def first_order_slots(keys: jax.Array, alias_p: jnp.ndarray,
                      alias_i: jnp.ndarray, deg: jnp.ndarray) -> jnp.ndarray:
    """Step-0 / fast-path draw from static edge weights (Vose alias), [W]."""
    return jax.vmap(alias_sample)(keys, alias_p, alias_i, deg)


@dataclasses.dataclass(frozen=True)
class HotContext:
    """Per-walker inputs the approx fast path needs, layout-free.

    Both engines can supply these from their own storage (reference: the
    PaddedGraph lookups; sharded: the replicated hot pack) — values only
    matter where ``is_hot_v`` is true, so cold-walker lanes may carry
    anything gather-safe.
    """
    is_hot_v: jnp.ndarray   # [W] bool — current vertex is popular
    is_hot_u: jnp.ndarray   # [W] bool — previous vertex is popular
    deg_u: jnp.ndarray      # [W] i32  true degree of u
    deg_v: jnp.ndarray      # [W] i32  true degree of v (where hot)
    w_min_v: jnp.ndarray    # [W] f32
    w_max_v: jnp.ndarray    # [W] f32
    alias_p: jnp.ndarray    # [W, Da] 1st-order alias table rows of v
    alias_i: jnp.ndarray    # [W, Da]
    alias_deg: jnp.ndarray  # [W] live width of the alias tables (deg of v)


@dataclasses.dataclass(frozen=True)
class StepChoice:
    """Outcome of one superstep's sampling; the backend owns the id gather
    (layouts differ: the sharded approx_always path keeps candidates at cold
    width and reads hot ids from the replicated cache)."""
    slot_exact: jnp.ndarray
    slot_alias: Optional[jnp.ndarray] = None
    use_alias: Optional[jnp.ndarray] = None

    def slot(self) -> jnp.ndarray:
        """Combined slot for backends whose candidate rows cover both paths."""
        if self.use_alias is None:
            return self.slot_exact
        return jnp.where(self.use_alias, self.slot_alias, self.slot_exact)


@dataclasses.dataclass(frozen=True)
class Sampler:
    """2nd-order step strategy: exact / approx / approx_always.

    Frozen + hashable so it can ride through ``jax.jit`` as a static
    argument. ``fused=True`` computes the exact slot with the Pallas kernel
    (``kernels.ops.node2vec_step_op``, interpret mode on CPU); the kernel
    implements :func:`exact_slots` verbatim, so results are bit-identical.
    """
    p: float = 1.0
    q: float = 1.0
    mode: str = "exact"
    eps: float = 1e-3
    fused: bool = False

    def __post_init__(self):
        if self.mode not in MODES:
            raise ValueError(f"mode must be one of {MODES}, got {self.mode!r}")

    def exact(self, rand, cand_ids, cand_w, u, prev_rows) -> jnp.ndarray:
        if self.fused:
            from repro.kernels.ops import node2vec_step_op
            with jax.named_scope("walk.probs"):
                return node2vec_step_op(cand_ids, cand_w, u, prev_rows, rand,
                                        self.p, self.q)
        return exact_slots(cand_ids, cand_w, u, prev_rows, rand, self.p,
                           self.q)

    def choose(self, keys, cand_ids, cand_w, u, prev_rows,
               hot: Optional[HotContext] = None) -> StepChoice:
        """One superstep draw for a [W]-batch of walkers."""
        with jax.named_scope("walk.rng"):
            k_exact, k_approx = split_keys(keys)
            rand = jax.vmap(jax.random.uniform)(k_exact)
        slot_exact = self.exact(rand, cand_ids, cand_w, u, prev_rows)
        if self.mode == "exact" or hot is None:
            return StepChoice(slot_exact)
        with jax.named_scope("walk.draw"):
            slot_alias = first_order_slots(k_approx, hot.alias_p,
                                           hot.alias_i, hot.alias_deg)
            if self.mode == "approx":
                gap = approx_gap(hot.deg_u, hot.deg_v, hot.w_min_v,
                                 hot.w_max_v, self.p, self.q)
                use = hot.is_hot_v & (~hot.is_hot_u) & (gap < self.eps)
            else:  # approx_always — beyond-paper O(1) path, every hot v
                use = hot.is_hot_v
        return StepChoice(slot_exact, slot_alias, use)
