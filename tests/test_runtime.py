"""Fault tolerance: round resume bit-equality (barrier and pipelined);
balance diagnostics; end-to-end node2vec quality."""
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.checkpoint.checkpointer import Checkpointer
from repro.core import rmat
from repro.core.node2vec import Node2VecConfig, node2vec
from repro.runtime.balance import shard_balance
from repro.runtime.fault_tolerance import WalkRoundRunner


def _cfg(rounds=3):
    return Node2VecConfig(p=0.5, q=2.0, walk_length=8, num_walks=rounds,
                          dim=16, seed=11)


def test_rounds_resume_bit_identical(tmp_path, small_graph):
    g = small_graph
    cfg = _cfg()
    # uninterrupted run
    r_full = list(WalkRoundRunner(g, cfg).rounds())
    # interrupted run: complete 2 rounds, "crash", resume with a NEW runner
    ck = Checkpointer(str(tmp_path))
    runner = WalkRoundRunner(g, cfg, checkpointer=ck)
    it = runner.rounds()
    got = [next(it), next(it)]
    del it, runner      # crash
    ck.wait()
    resumed = WalkRoundRunner(g, cfg, checkpointer=Checkpointer(
        str(tmp_path)))
    r_resumed = list(resumed.rounds())
    assert len(r_resumed) == cfg.num_walks
    for a, b in zip(r_full, r_resumed):
        assert np.array_equal(a, b)


def test_rounds_resume_pipelined_fused(tmp_path, small_graph):
    """Resume with the pipeline flag on the fused backend: bit-identical
    rounds and clean dropped accounting."""
    cfg = Node2VecConfig(p=0.5, q=2.0, walk_length=6, num_walks=3,
                         backend="fused", pipeline=True, seed=7)
    full = WalkRoundRunner(small_graph, cfg)
    r_full = list(full.rounds())
    ck = Checkpointer(str(tmp_path))
    runner = WalkRoundRunner(small_graph, cfg, checkpointer=ck)
    it = runner.rounds()
    next(it), next(it)
    del it, runner      # crash after 2 rounds
    ck.wait()
    resumed = WalkRoundRunner(small_graph, cfg,
                              checkpointer=Checkpointer(str(tmp_path)))
    r_resumed = list(resumed.rounds())
    for a, b in zip(r_full, r_resumed):
        assert np.array_equal(a, b)
    assert resumed.stats_summary()["dropped"] == 0


PIPELINE_RESUME_SCRIPT = textwrap.dedent("""
    import os, sys, warnings
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import numpy as np
    from repro.checkpoint.checkpointer import Checkpointer
    from repro.core import rmat
    from repro.core.node2vec import Node2VecConfig
    from repro.runtime.fault_tolerance import WalkRoundRunner

    tmp = sys.argv[1]
    warnings.simplefilter("ignore", RuntimeWarning)
    g = rmat.skew(4, k=8, avg_degree=16, seed=3)
    # starved capacity so drops are non-zero: the resume must preserve the
    # cumulative dropped accounting, not just the walks
    cfg = Node2VecConfig(p=0.5, q=2.0, walk_length=8, num_walks=3,
                         mode="approx_always", approx_eps=5e-2, cap=24,
                         capacity=2, backend="sharded", pipeline=True,
                         seed=11)
    full = WalkRoundRunner(g, cfg)
    r_full = list(full.rounds())
    assert full.total_dropped > 0, full.total_dropped
    runner = WalkRoundRunner(g, cfg, checkpointer=Checkpointer(tmp))
    it = runner.rounds()
    next(it), next(it)
    runner.ckpt.wait()
    del it, runner      # crash mid-pipeline, after 2 of 3 rounds
    resumed = WalkRoundRunner(g, cfg, checkpointer=Checkpointer(tmp))
    r_resumed = list(resumed.rounds())
    assert len(r_resumed) == cfg.num_walks
    for a, b in zip(r_full, r_resumed):
        assert np.array_equal(a, b)
    # rounds 0-1 drops come back from the checkpoint meta, round 2 reruns
    assert resumed.total_dropped == full.total_dropped, (
        resumed.total_dropped, full.total_dropped)
    assert resumed.stats_summary()["dropped"] == full.total_dropped
    print("OK", full.total_dropped)
""")


@pytest.mark.slow
def test_rounds_resume_pipelined_sharded(tmp_path):
    """Kill a pipelined sharded run (2 fake devices) between rounds; the
    resumed runner reproduces the same walks AND the same cumulative
    WalkStats.dropped accounting (carried in checkpoint meta)."""
    r = subprocess.run(
        [sys.executable, "-c", PIPELINE_RESUME_SCRIPT, str(tmp_path)],
        capture_output=True, text=True, timeout=600,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "HOME": "/root",
             "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout


def test_balance_capped_work_bounded(skewed_graph):
    rep = shard_balance(skewed_graph, num_shards=8, cap=24)
    assert rep.capped_imbalance <= rep.edge_imbalance + 1e-9
    assert rep.capped_imbalance < 1.6  # bounded post-cap imbalance


def test_node2vec_end_to_end_quality():
    """Fig. 6 proxy at test scale: embeddings linearly separate SBM
    communities far above chance."""
    g, labels = rmat.sbm_labeled(n=240, num_communities=3, p_in=0.09,
                                 p_out=0.004, seed=2)
    cfg = Node2VecConfig(p=1.0, q=0.5, walk_length=16, num_walks=3, window=4,
                         dim=24, epochs=2, batch_size=2048, seed=0)
    emb = node2vec(g, cfg)
    rng = np.random.default_rng(0)
    idx = rng.permutation(g.n)
    tr, te = idx[:g.n // 2], idx[g.n // 2:]
    y = np.eye(3)[labels]
    w, *_ = np.linalg.lstsq(emb[tr], y[tr], rcond=None)
    acc = ((emb[te] @ w).argmax(1) == labels[te]).mean()
    assert acc > 0.65, acc
