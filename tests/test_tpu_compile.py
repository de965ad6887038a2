"""Compile the main path's kernels and jitted programs for a TPU v5e that is
described, not attached — at the chip smoke's real widths.

Nothing here runs: the TPU compiler refuses what Pallas interpret mode and
the CPU backend accept (unlowerable primitives, scalar stores to VMEM, more
fast memory than a kernel may use). The topology is described inside a
module-scoped fixture, so collecting this file never loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from repro.core.walk_distributed import ShardedGraph
from repro.engine import WalkEngine, WalkPlan
from repro.engine.sampler import exact_slots
from repro.kernels import node2vec_step as step_kernel
from repro.kernels import ops
from repro.kernels import sgns as sgns_kernel
from repro.optim.optimizers import adam
from repro.train.stream import _train_epoch

# wec:k=16,deg=20: 65,536 vertices, max degree 165 (padded width 256); the
# chip smoke runs wec:k=14,deg=20: max degree 115 (padded width 128)
N, MAX_DEG = 1 << 16, 165
B, D, K = 1024, 128, 5


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def sds(topo):
    """ShapeDtypeStruct factory placed on one described v5e chip."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """A described-chip compile is written to the persistent cache but can
    never be read back here; keep the cache out of these tests."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("width", [128, 256])
def test_node2vec_step_kernel_compiles_for_v5e(sds, width):
    w = 4 * step_kernel.block_rows(width)
    ids = sds((w, width), jnp.int32)
    col = lambda dt: sds((w,), dt)
    compiled = jax.jit(
        lambda c, cw, u, pr, r: step_kernel.node2vec_step(
            c, cw, u, pr, r, 1.0, 0.5,
            block_w=step_kernel.block_rows(width))
    ).lower(ids, sds((w, width), jnp.float32), col(jnp.int32), ids,
            col(jnp.float32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("w,width", [(32768, 28), (1024, 5945)])
def test_exact_slots_membership_stays_fused_for_v5e(sds, w, width):
    """The jnp exact draw at the ER-20 walk cell's shape and at a Skew-S
    hub's row width: the membership compare of every candidate against
    every prev lane must stay inside one reduce fusion, so the compiled
    program never holds a [W, D, Dp] temporary, nor even one [W, D] i32
    buffer."""
    ids = sds((w, width), jnp.int32)
    col = lambda dt: sds((w,), dt)
    compiled = jax.jit(
        lambda c, cw, u, pr, r: exact_slots(c, cw, u, pr, r, 1.0, 0.5)
    ).lower(ids, sds((w, width), jnp.float32), col(jnp.int32), ids,
            col(jnp.float32)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < w * width * 4


def test_sgns_kernel_compiles_for_v5e(sds):
    rows = sds((B, D), jnp.float32)
    compiled = jax.jit(
        lambda ci, po, no, v: sgns_kernel.sgns_fused(ci, po, no, v)
    ).lower(rows, rows, sds((B, K, D), jnp.float32),
            sds((B,), jnp.float32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("backend", ["jnp", "fused"])
def test_train_epoch_compiles_for_v5e(sds, backend, monkeypatch):
    """The dense one-device SGNS epoch program at V=65536, D=128 over a
    short step grid. The kernel wrapper asks the default backend (the CPU
    here) whether to interpret; the chip would compile, so the test does."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    steps = 4
    opt = adam(0.025)
    params = {name: sds((N, D), jnp.float32)
              for name in ("emb_in", "emb_out")}
    opt_state = jax.tree.map(lambda a: sds(a.shape, a.dtype),
                             jax.eval_shape(opt.init, params))
    pairs = sds((steps * B,), jnp.int32)
    compiled = _train_epoch.lower(
        params, opt_state, pairs, pairs, sds((steps * B,), jnp.bool_),
        sds((steps, B), jnp.int32), sds((N,), jnp.float32),
        sds((N,), jnp.int32), sds((2,), jnp.uint32),
        opt=opt, negatives=K, backend=backend,
        n_pairs=steps * B).compile()
    assert ("tpu_custom_call" in compiled.as_text()) == (backend == "fused")


def test_sharded_walk_compiles_for_v5e_2x2(topo):
    """The sharded Pregel walk over the described 2x2 mesh, through the
    abstract-graph path of ``WalkEngine.analyze``: the NEIG exchange lowers
    to an all-to-all on the chip."""
    shards = len(topo.devices)
    s = jax.ShapeDtypeStruct

    def hot(*shape, dt=jnp.int32):
        return s((1,) + shape, dt)

    graph = ShardedGraph(
        n=N, n_orig=N, num_shards=shards, cap=MAX_DEG, hot_cap=MAX_DEG,
        adj=s((N, MAX_DEG), jnp.int32), wgt=s((N, MAX_DEG), jnp.float32),
        alias_p=s((N, MAX_DEG), jnp.float32),
        alias_i=s((N, MAX_DEG), jnp.int32), deg=s((N,), jnp.int32),
        hot_ids=hot(), hot_adj=hot(MAX_DEG),
        hot_wgt=hot(MAX_DEG, dt=jnp.float32),
        hot_alias_p=hot(MAX_DEG, dt=jnp.float32),
        hot_alias_i=hot(MAX_DEG), hot_deg=hot(),
        hot_wmin=hot(dt=jnp.float32), hot_wmax=hot(dt=jnp.float32))
    engine = WalkEngine.build(
        graph, WalkPlan(p=1.0, q=0.5, length=80, backend="sharded"),
        mesh=Mesh(topo.devices, ("rw",)))
    art = engine.analyze(num_walkers=N)
    assert art["shards"] == shards
    assert art["coll_counts"].get("all-to-all", 0) > 0


def test_sharded_train_epoch_compiles_for_v5e_2x2(topo):
    """The range-sharded SGNS epoch (lazy row-Adam, owner-masked psum
    gathers) over the described 2x2 mesh at V=16384, D=128."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.optim.optimizers import AdamState, adam_rows
    from repro.train.shard import (pow2_bucket, table_rows,
                                   train_epoch_sharded)
    mesh = Mesh(topo.devices, ("rw",))
    rows, rep = NamedSharding(mesh, P("rw")), NamedSharding(mesh, P())
    s = jax.ShapeDtypeStruct
    v, steps = 1 << 14, 4
    n = steps * B
    tables = {name: s((table_rows(v, len(topo.devices)), D), jnp.float32,
                      sharding=rows) for name in ("emb_in", "emb_out")}
    state = AdamState(s((), jnp.int32, sharding=rep), tables, tables)
    pairs = s((n,), jnp.int32, sharding=rep)
    compiled = train_epoch_sharded.lower(
        tables, state, pairs, pairs, s((n,), jnp.bool_, sharding=rep),
        s((steps, B), jnp.int32, sharding=rep),
        s((v,), jnp.float32, sharding=rep), s((v,), jnp.int32, sharding=rep),
        s((2,), jnp.uint32, sharding=rep), mesh=mesh, opt=adam_rows(0.0025),
        negatives=K, backend="jnp", n_pairs=n, u_in=pow2_bucket(B),
        u_out=pow2_bucket(B * (1 + K))).compile()
    assert "all-reduce" in compiled.as_text()
