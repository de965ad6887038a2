"""Skip-gram with negative sampling (SGNS) — Node2Vec stage 2.

The paper focuses on the walk stage (98.8% of Spark runtime) but a complete
system needs the optimization stage too: this is the standard word2vec SGNS
objective [Mikolov'13] applied to walk corpora [Grover & Leskovec'16]:

    L = -log sigma(u_c . v_p) - sum_k log sigma(-u_c . v_nk)

Embedding tables are sharded over the ``model`` mesh axis on the vocab
(vertex) dimension so billion-vertex graphs scale: each device holds V/TP
rows; gathers/scatter-grads lower to collectives under pjit.

The fused forward/backward inner product is also available as a Pallas TPU
kernel (``repro.kernels.sgns``); this module is the pure-jnp reference path
used for CPU tests and as the kernel oracle.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.optim.optimizers import Optimizer, apply_updates


@dataclasses.dataclass(frozen=True)
class SGNSConfig:
    vocab: int
    dim: int = 128
    negatives: int = 5
    param_dtype: Any = jnp.float32


def init_params(cfg: SGNSConfig, key: jax.Array) -> Dict[str, jnp.ndarray]:
    k1, _ = jax.random.split(key)
    scale = 1.0 / jnp.sqrt(cfg.dim)
    return {
        "emb_in": (jax.random.uniform(k1, (cfg.vocab, cfg.dim),
                                      cfg.param_dtype) - 0.5) * 2 * scale,
        "emb_out": jnp.zeros((cfg.vocab, cfg.dim), cfg.param_dtype),
    }


def log_sigmoid(x):
    return -jnp.logaddexp(0.0, -x)


def sgns_loss(params, center: jnp.ndarray, pos: jnp.ndarray,
              negs: jnp.ndarray, valid: Optional[jnp.ndarray] = None):
    """Batch SGNS loss. center/pos: [B]; negs: [B, K]; valid: [B] mask."""
    ci = params["emb_in"][center]            # [B, D]
    po = params["emb_out"][pos]              # [B, D]
    no = params["emb_out"][negs]             # [B, K, D]
    pos_score = jnp.sum(ci * po, axis=-1)
    neg_score = jnp.einsum("bd,bkd->bk", ci, no)
    per = -(log_sigmoid(pos_score) + jnp.sum(log_sigmoid(-neg_score), -1))
    if valid is None:
        return jnp.mean(per)
    denom = jnp.maximum(jnp.sum(valid), 1.0)
    return jnp.sum(per * valid) / denom


SGNS_BACKENDS = ("jnp", "fused")


def sgns_grads(params, batch, backend: str = "jnp"):
    """Loss + parameter gradients for one SGNS batch.

    ``backend="jnp"``   — autodiff through the gathered-rows loss (reference).
    ``backend="fused"`` — gather rows, run the Pallas fused loss+grad kernel
    (``repro.kernels.sgns``: ci/po/no read once, three grads written once),
    scatter-add the row grads back into the tables. Same math as autodiff
    (the kernel-vs-autodiff contract is tested in tests/test_kernels.py);
    interpret mode on the CPU backend.
    """
    center, pos, negs = batch["center"], batch["pos"], batch["neg"]
    valid = batch.get("valid")
    if backend == "jnp":
        def loss_fn(p):
            return sgns_loss(p, center, pos, negs, valid)

        return jax.value_and_grad(loss_fn)(params)
    if backend != "fused":
        raise ValueError(
            f"sgns backend must be one of {SGNS_BACKENDS}, got {backend!r}")
    from repro.kernels.ops import sgns_fused_op
    v = jnp.ones(center.shape[0], jnp.float32) if valid is None else \
        valid.astype(jnp.float32)
    ci = params["emb_in"][center]
    po = params["emb_out"][pos]
    no = params["emb_out"][negs]
    loss_sum, g_ci, g_po, g_no = sgns_fused_op(ci, po, no, v)
    # the kernel returns the masked *sum*; the jnp path trains on the masked
    # mean — scale by the same denominator so both backends see one gradient
    denom = jnp.maximum(jnp.sum(v), 1.0)
    g_in = jnp.zeros_like(params["emb_in"]).at[center].add(g_ci / denom)
    g_out = (jnp.zeros_like(params["emb_out"])
             .at[pos].add(g_po / denom)
             .at[negs].add(g_no / denom))
    return loss_sum / denom, {"emb_in": g_in, "emb_out": g_out}


@functools.partial(jax.jit, static_argnames=("opt", "backend"),
                   donate_argnums=(0, 1))
def train_step(params, opt_state, batch, opt: Optimizer,
               backend: str = "jnp"):
    loss, grads = sgns_grads(params, batch, backend)
    updates, opt_state = opt.update(grads, opt_state, params)
    params = apply_updates(params, updates)
    return params, opt_state, loss


def normalize_embeddings(params) -> jnp.ndarray:
    e = params["emb_in"].astype(jnp.float32)
    return e / (jnp.linalg.norm(e, axis=-1, keepdims=True) + 1e-8)


def serving_table(params) -> np.ndarray:
    """The train->serve handoff: host f32 unit-norm ``[V, D]`` table in the
    layout ``repro.serve.EmbeddingService`` holds resident. One call site
    owns the normalization convention, so trainer and server cannot drift
    (the service also accepts a raw SGNS params dict and calls this)."""
    return np.asarray(jax.device_get(normalize_embeddings(params)))
