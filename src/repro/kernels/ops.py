"""Jitted public wrappers around the Pallas kernels.

Handles the shape contract (pad walker count / widths to tile multiples),
runs the kernels in Pallas interpret mode on the CPU backend only (it
executes the kernel body faithfully for validation; every accelerator
compiles them), and exposes drop-in replacements for the jnp paths in the
walk engine / SGNS trainer.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.graph import PAD_ID
from repro.kernels import node2vec_step as _step
from repro.kernels import sgns as _sgns


def _interpret() -> bool:
    """Pallas interpret mode runs the kernel bodies on the CPU backend only;
    every accelerator compiles them."""
    return jax.default_backend() == "cpu"


def _pad_axis(x, axis: int, mult: int, fill):
    size = x.shape[axis]
    target = ((size + mult - 1) // mult) * mult
    if target == size:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, target - size)
    return jnp.pad(x, pads, constant_values=fill)


def node2vec_step_op(cand_ids, cand_w, u, prev_ids, rand, p: float,
                     q: float) -> jnp.ndarray:
    """Fused 2nd-order step; pads candidate and prev rows to one lane-multiple
    width and the walker count to the block multiple, then unpads."""
    w = cand_ids.shape[0]
    width = _step.LANE * -(-max(cand_ids.shape[1], prev_ids.shape[1])
                          // _step.LANE)
    bw = min(_step.block_rows(width), max(8, 1 << (w - 1).bit_length()))

    def pad(x, fill):
        if x.ndim == 2:
            x = jnp.pad(x, ((0, 0), (0, width - x.shape[1])),
                        constant_values=fill)
        return _pad_axis(x, 0, bw, fill)

    slots = _step.node2vec_step(
        pad(cand_ids, PAD_ID), pad(cand_w, 0.0), pad(u, 0),
        pad(prev_ids, PAD_ID), pad(rand, 0.0), p, q, block_w=bw,
        interpret=_interpret())
    return slots[:w]


def flash_attention_op(q, k, v, window: int = 0, causal: bool = True,
                       block: int = 128):
    """Flash attention over model-layout tensors: q [B,S,H,dh],
    k/v [B,S,KV,dh] (GQA expanded here). Pads S to the block multiple and dh
    to the lane width."""
    from repro.kernels import flash_attention as _fa
    b, s, h, dh = q.shape
    kv = k.shape[2]
    if kv != h:
        k = jnp.repeat(k, h // kv, axis=2)
        v = jnp.repeat(v, h // kv, axis=2)
    bq = min(block, max(8, 1 << (s - 1).bit_length()))

    def to_bh(x):
        x = jnp.swapaxes(x, 1, 2).reshape(b * h, s, dh)
        x = _pad_axis(x, 2, 128, 0.0)
        return _pad_axis(x, 1, bq, 0.0)

    qq, kk, vv = map(to_bh, (q, k, v))
    out = _fa.flash_attention(qq, kk, vv, block=min(bq, qq.shape[1]),
                              window=window, causal=causal,
                              interpret=_interpret(), sm_scale=dh ** -0.5)
    out = out[:, :s, :dh].reshape(b, h, s, dh)
    return jnp.swapaxes(out, 1, 2)


def sgns_fused_op(ci, po, no, valid, block_b: int = 512):
    """Fused SGNS loss+grads; returns (loss_sum, g_ci, g_po, g_no)."""
    b, d = ci.shape
    bb = min(block_b, max(8, 1 << (b - 1).bit_length()))
    ci_p = _pad_axis(_pad_axis(ci, 1, _sgns.LANE, 0.0), 0, bb, 0.0)
    po_p = _pad_axis(_pad_axis(po, 1, _sgns.LANE, 0.0), 0, bb, 0.0)
    no_p = _pad_axis(_pad_axis(no, 2, _sgns.LANE, 0.0), 0, bb, 0.0)
    valid_p = _pad_axis(valid, 0, bb, 0.0)
    loss, g_ci, g_po, g_no = _sgns.sgns_fused(
        ci_p, po_p, no_p, valid_p, block_b=min(bb, ci_p.shape[0]),
        interpret=_interpret())
    return loss, g_ci[:b, :d], g_po[:b, :d], g_no[:b, :, :d]
