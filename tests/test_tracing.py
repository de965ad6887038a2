"""The program names its phases in the profiler trace (``repro.obs``).

* Every device scope rides in the op metadata of the compiled walk and
  SGNS programs, and no other ``walk.*`` / ``sgns.*`` scope does.
* Under a profiler trace, one ``WalkEngine.run`` writes the walk spans and
  one ``StreamingSGNSTrainer.consume`` the trainer's; the ``bytes`` of
  ``train.upload`` is what ``TrainStats.h2d_bytes`` counts for the round.
* Without a profiler the spans record nothing and cost little.
"""
import glob
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro import obs
from repro.core.walk import _simulate
from repro.engine import WalkEngine, WalkPlan
from repro.optim.optimizers import adam
from repro.train import StreamingSGNSTrainer
from repro.train.stream import _gen_pairs, _perm_batches, _train_epoch

SCOPE_RE = re.compile(r"^(walk|sgns)\.\w+$")
SPAN_RE = re.compile(r"^(walk|train)\.\w+$")
WALK_SCOPES = [s for s in obs.SCOPES if s.startswith("walk.")]
SGNS_SCOPES = [s for s in obs.SCOPES if s.startswith("sgns.")]


def _op_scopes(hlo_text: str) -> set:
    """The ``walk.*`` / ``sgns.*`` components of every op_name."""
    names = re.findall(r'op_name="([^"]*)"', hlo_text)
    return {c for n in names for c in n.split("/") if SCOPE_RE.match(c)}


@pytest.fixture(scope="module")
def engine(small_graph):
    return WalkEngine.build(small_graph, WalkPlan(p=0.5, q=2.0, length=8))


def _walk_hlo(engine, mode: str) -> str:
    sampler = WalkPlan(p=0.5, q=2.0, length=8, mode=mode,
                       cap=8).sampler()
    pg = engine.pg if mode == "exact" else WalkEngine.build(
        engine.store.graph, WalkPlan(length=8, mode=mode, cap=8)).pg
    starts = jnp.arange(16, dtype=jnp.int32)
    return _simulate.lower(pg, starts, starts, jax.random.PRNGKey(0),
                           sampler=sampler, length=8).compile().as_text()


def _train_hlo(vocab=64, dim=8, batch=32, steps=3, negatives=2) -> list:
    opt = adam(0.01)
    params = {k: jnp.zeros((vocab, dim)) for k in ("emb_in", "emb_out")}
    n = steps * batch
    pairs = jnp.zeros(n, jnp.int32)
    epoch = _train_epoch.lower(
        params, opt.init(params), pairs, pairs, jnp.ones(n, bool),
        jnp.zeros((steps, batch), jnp.int32), jnp.ones(vocab),
        jnp.zeros(vocab, jnp.int32), jax.random.PRNGKey(0), opt=opt,
        negatives=negatives, backend="jnp", n_pairs=n)
    gen = _gen_pairs.lower(jnp.zeros((4, 6), jnp.int32), window=2)
    perm = _perm_batches.lower(jax.random.PRNGKey(0), n=n, steps=steps,
                               batch=batch)
    return [p.compile().as_text() for p in (epoch, gen, perm)]


@pytest.fixture(scope="module")
def walk_hlo(engine):
    return _walk_hlo(engine, "exact")


@pytest.fixture(scope="module")
def train_hlo():
    return _train_hlo()


@pytest.mark.parametrize("scope", WALK_SCOPES)
def test_simulate_carries_walk_scope(walk_hlo, scope):
    assert scope in _op_scopes(walk_hlo)


@pytest.mark.parametrize("scope", SGNS_SCOPES)
def test_train_epoch_carries_sgns_scope(train_hlo, scope):
    assert scope in _op_scopes(train_hlo[0])


def test_pair_programs_are_scoped(train_hlo):
    """Pair generation and the shuffle are ``sgns.pairs`` throughout."""
    for text in train_hlo[1:]:
        assert _op_scopes(text) == {"sgns.pairs"}


def test_scopes_are_exactly_those_compiled(engine, walk_hlo, train_hlo):
    """``obs.SCOPES`` lists every scope the walk (exact and approx) and SGNS
    programs use, and no other."""
    used = _op_scopes(walk_hlo) | _op_scopes(_walk_hlo(engine, "approx"))
    for text in train_hlo:
        used |= _op_scopes(text)
    assert used == set(obs.SCOPES)
    assert len(obs.SCOPES) == len(set(obs.SCOPES))


# ------------------------------------------------------------- spans --
def _host_events(trace_dir: str) -> list:
    """[(name, {stat: value})] of the program spans on the host planes."""
    (path,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            out += [(e.name, dict(e.stats)) for line in plane.lines
                    for e in line.events if SPAN_RE.match(e.name)]
    return out


@pytest.fixture(scope="module")
def traced_round(engine, tmp_path_factory):
    """One walk round and one trained round, warmed up, then traced."""
    trainer = StreamingSGNSTrainer(engine.n, dim=8, window=3, negatives=2,
                                   batch_size=64, seed=0)
    starts = np.arange(32, dtype=np.int32)
    trainer.consume(engine.run(starts, seed=1).walks)     # compile
    jax.block_until_ready(trainer.params)
    h2d0 = trainer.recorder.h2d_bytes
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(trace_dir)
    try:
        walks = engine.run(starts, seed=2).walks
        trainer.consume(walks)
        jax.block_until_ready(trainer.params)
    finally:
        jax.profiler.stop_trace()
    return {"events": _host_events(trace_dir), "walks": walks,
            "vocab": engine.n,
            "h2d_step": trainer.recorder.h2d_bytes - h2d0}


@pytest.mark.parametrize("name", obs.SPANS)
def test_round_writes_each_span_once(traced_round, name):
    assert [n for n, _ in traced_round["events"]].count(name) == 1


def test_spans_are_exactly_those_written(traced_round):
    assert {n for n, _ in traced_round["events"]} == set(obs.SPANS)
    assert len(obs.SPANS) == len(set(obs.SPANS))


def test_upload_bytes_match_stats(traced_round):
    (stats,) = [s for n, s in traced_round["events"] if n == "train.upload"]
    # walks (int32) + alias prob (f32) + alias companion (i32)
    want = traced_round["walks"].astype(np.int32).nbytes + \
        traced_round["vocab"] * 8
    assert stats == {"bytes": want}
    assert traced_round["h2d_step"] == want
    for n, s in traced_round["events"]:
        if n != "train.upload":
            assert s == {}, n


def test_span_without_profiler_is_cheap():
    """A span is one TraceMe check while no profiler runs: well under the
    tens of milliseconds of the cheapest round."""
    t0 = time.perf_counter()
    for _ in range(1000):
        with obs.span("train.upload", bytes=1):
            pass
    assert (time.perf_counter() - t0) / 1000 < 1e-3
