"""Pallas TPU kernel: fused SGNS forward + backward.

For a batch block of gathered embedding rows, computes the skip-gram
negative-sampling loss AND all three gradients in one VMEM-resident pass:

    s_p = sigmoid(ci.po)            g_po  = (s_p - 1) * ci
    s_nk = sigmoid(ci.no_k)         g_nok = s_nk * ci
    loss = -log s_p - sum_k log(1 - s_nk)
    g_ci = (s_p - 1) * po + sum_k s_nk * no_k

The jnp autodiff path materializes the [B, K, D] products twice (fwd + bwd);
the fused kernel reads ci/po/no exactly once and writes the three grads once —
the arithmetic-intensity floor for this op. Embedding dim D is the lane axis
(multiple of 128); negatives K is unrolled (small, e.g. 5-8).

Shapes: ci, po [B, D] f32; no [B, K, D] f32; valid [B] f32 mask.
Out: loss_sum (masked sum, added from per-block partials), g_ci, g_po [B, D],
g_no [B, K, D].
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128


def _sigmoid(x):
    return 1.0 / (1.0 + jnp.exp(-x))


def _sgns_kernel(ci_ref, po_ref, no_ref, valid_ref, loss_ref, gci_ref,
                 gpo_ref, gno_ref):
    ci = ci_ref[...]              # [B, D]
    po = po_ref[...]              # [B, D]
    valid = valid_ref[...]        # [B, 1]

    # loss = -log s_p - sum log(1 - s_n) = softplus(-x_p) + sum softplus(x_n)
    pos_score = jnp.sum(ci * po, axis=-1, keepdims=True)       # [B, 1]
    loss = jnp.logaddexp(0.0, -pos_score)
    coeff_p = (_sigmoid(pos_score) - 1.0) * valid              # [B, 1]
    gpo_ref[...] = coeff_p * ci
    g_ci = coeff_p * po
    for k in range(no_ref.shape[1]):                           # K unrolled
        no_k = no_ref[:, k, :]                                 # [B, D]
        neg_score = jnp.sum(ci * no_k, axis=-1, keepdims=True)
        loss = loss + jnp.logaddexp(0.0, neg_score)
        coeff_n = _sigmoid(neg_score) * valid
        gno_ref[:, k, :] = coeff_n * ci
        g_ci = g_ci + coeff_n * no_k
    gci_ref[...] = g_ci
    # this block's masked loss sum, broadcast over its own (8, 128) tile;
    # the wrapper adds the per-block partials
    loss_ref[...] = jnp.broadcast_to(jnp.sum(loss * valid), loss_ref.shape)


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def sgns_fused(ci: jnp.ndarray, po: jnp.ndarray, no: jnp.ndarray,
               valid: jnp.ndarray, block_b: int = 512,
               interpret: bool = False):
    """Fused SGNS loss+grads. B % block_b == 0, D % 128 == 0 required
    (ops.py pads)."""
    b, d = ci.shape
    k = no.shape[1]
    assert d % LANE == 0 and b % block_b == 0, (b, d)
    grid = (b // block_b,)
    rows = pl.BlockSpec((block_b, d), lambda i: (i, 0))
    negs = pl.BlockSpec((block_b, k, d), lambda i: (i, 0, 0))
    partial, g_ci, g_po, g_no = pl.pallas_call(
        _sgns_kernel,
        grid=grid,
        in_specs=[rows, rows, negs,
                  pl.BlockSpec((block_b, 1), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((1, 8, LANE), lambda i: (i, 0, 0)),
                   rows, rows, negs],
        out_shape=[
            jax.ShapeDtypeStruct((grid[0], 8, LANE), jnp.float32),
            jax.ShapeDtypeStruct((b, d), jnp.float32),
            jax.ShapeDtypeStruct((b, d), jnp.float32),
            jax.ShapeDtypeStruct((b, k, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(ci, po, no, valid.reshape(b, 1))
    return jnp.sum(partial[:, 0, 0]), g_ci, g_po, g_no


def sgns_row_grads(ci, po, no, valid, backend: str = "jnp"):
    """Loss (masked *sum*) + per-row gradients for gathered SGNS rows.

    The row-level counterpart of ``repro.core.skipgram.sgns_grads``: no
    table scatter — the caller owns where the rows live (the sharded trainer
    scatters them onto per-shard unique-row sets). ``backend="fused"`` runs
    the Pallas kernel above; ``backend="jnp"`` is the same closed form the
    kernel computes, kept here next to it so the two cannot drift.

    ci, po: [B, D]; no: [B, K, D]; valid: [B] f32.
    Returns (loss_sum, g_ci [B, D], g_po [B, D], g_no [B, K, D]).
    """
    if backend == "fused":
        from repro.kernels.ops import sgns_fused_op
        return sgns_fused_op(ci, po, no, valid)
    if backend != "jnp":
        raise ValueError(f"sgns backend must be jnp|fused, got {backend!r}")
    pos_score = jnp.sum(ci * po, axis=-1, keepdims=True)       # [B, 1]
    s_p = _sigmoid(pos_score)
    neg_score = jnp.sum(no * ci[:, None, :], axis=-1)          # [B, K]
    s_n = _sigmoid(neg_score)
    loss = (jnp.logaddexp(0.0, -pos_score[:, 0]) +
            jnp.sum(jnp.logaddexp(0.0, neg_score), axis=-1))   # [B]
    loss_sum = jnp.sum(loss * valid)
    coeff_p = (s_p - 1.0) * valid[:, None]                     # [B, 1]
    coeff_n = s_n * valid[:, None]                             # [B, K]
    g_po = coeff_p * ci
    g_no = coeff_n[:, :, None] * ci[:, None, :]
    g_ci = coeff_p * po + jnp.sum(coeff_n[:, :, None] * no, axis=1)
    return loss_sum, g_ci, g_po, g_no
