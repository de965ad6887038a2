"""Pallas TPU kernel: fused Node2Vec 2nd-order step.

One kernel fuses the per-walker hot loop of the walk engine:

    membership  x in N(u)        (equality against every rotation of N(u))
    alpha_pq    {1/p, 1, 1/q}    (select)
    probs       alpha * w        (VPU)
    sampling    inverse-CDF      (shared prefix sum + compare-count)

The unfused jnp path leaves fusion to XLA: its membership compare fuses into
one reduce with no [W, D, Dp] temporary, but alpha, probs and the prefix
sum can still land in HBM as [W, D] tensors between fusions. The kernel keeps
everything for a walker block resident in VMEM, so the step reads the
candidate/prev rows once.

The draw itself is :func:`repro.engine.sampler.draw_slots` — the same
function the jnp path calls, with ``pltpu.roll`` as its lane shift — so the
kernel and the reference agree bit for bit by construction, on any backend.

Tiling: grid over walker blocks of BW rows; the candidate and prev row blocks
[BW, D] live in VMEM. Membership rotates the prev block one lane at a time
(D rotations, each a [BW, D] compare), so the working set stays at a few
[BW, D] blocks for any D. ``block_rows`` sizes BW to the padded width.

Layout contract (``kernels.ops`` pads to it):
  cand_ids  [W, D]  i32, PAD_ID padded, row-sorted
  cand_w    [W, D]  f32, 0 padded
  u         [W]     i32 (previous vertex)
  prev_ids  [W, D]  i32, PAD_ID padded (N(u)); same width as cand_ids
  rand      [W]     f32 uniform in [0, 1)
Returns
  slot      [W]     i32 sampled candidate slot (caller maps to id)

p, q are compile-time constants (walk hyper-parameters), baked into the
kernel body — no scalar operands needed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.graph import PAD_ID
from repro.engine.sampler import draw_slots

LANE = 128
BLOCK_ELEMS = 8192   # BW * D per block: 8 i32 vregs per [BW, D] value


def block_rows(width: int) -> int:
    """Walker rows per block at padded row ``width``: a power of two in
    [8, 256] holding about ``BLOCK_ELEMS`` elements per [BW, D] value."""
    bw = 8
    while bw < 256 and 2 * bw * width <= BLOCK_ELEMS:
        bw *= 2
    return bw


def _step_kernel(cand_ids_ref, cand_w_ref, u_ref, prev_ref, rand_ref,
                 slot_ref, *, p_inv: float, q_inv: float):
    cand = cand_ids_ref[...]          # [BW, D] i32
    w = cand_w_ref[...]               # [BW, D] f32
    u = u_ref[...]                    # [BW, 1] i32
    r = rand_ref[...]                 # [BW, 1] f32
    d = cand.shape[-1]

    def body(_, carry):
        hit, prev = carry
        hit = hit | (cand == prev).astype(jnp.int32)
        return hit, pltpu.roll(prev, 1, 1)

    hit, _ = jax.lax.fori_loop(
        0, d, body, (jnp.zeros(cand.shape, jnp.int32), prev_ref[...]))

    valid = cand != PAD_ID
    alpha = jnp.where(cand == u, p_inv, jnp.where(hit > 0, 1.0, q_inv))
    probs = jnp.where(valid, alpha * w, 0.0)      # [BW, D]
    slot_ref[...] = draw_slots(probs, valid, r,
                               roll=lambda x, k: pltpu.roll(x, k, 1))


@functools.partial(jax.jit,
                   static_argnames=("p", "q", "block_w", "interpret"))
def node2vec_step(cand_ids: jnp.ndarray, cand_w: jnp.ndarray, u: jnp.ndarray,
                  prev_ids: jnp.ndarray, rand: jnp.ndarray, p: float,
                  q: float, block_w: int = 256,
                  interpret: bool = False) -> jnp.ndarray:
    """Fused step over all walkers. D must be a multiple of 128, prev_ids as
    wide as cand_ids, and W a multiple of block_w (ops.py pads arbitrary
    shapes to this contract)."""
    wk, d = cand_ids.shape
    assert d % LANE == 0 and prev_ids.shape == cand_ids.shape, \
        (cand_ids.shape, prev_ids.shape)
    assert wk % block_w == 0, (wk, block_w)
    kernel = functools.partial(_step_kernel, p_inv=1.0 / p, q_inv=1.0 / q)
    rows = pl.BlockSpec((block_w, d), lambda i: (i, 0))
    col = pl.BlockSpec((block_w, 1), lambda i: (i, 0))
    out = pl.pallas_call(
        kernel,
        grid=(wk // block_w,),
        in_specs=[rows, rows, col, rows, col],
        out_specs=col,
        out_shape=jax.ShapeDtypeStruct((wk, 1), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(cand_ids, cand_w, u.reshape(wk, 1), prev_ids, rand.reshape(wk, 1))
    return out[:, 0]
