"""The scope reducer (``chipbench/core/scopes.py``) on two traces recorded
on a TPU v5e: ``small.xplane.pb`` (a program with no scopes, as in
``test_trace.py``) and ``scoped.xplane.pb`` (one tiny round walked and
trained by the program, ``record_scoped.py``)."""
import os

import pytest
from jax.profiler import ProfileData

from chipbench.core import scopes, trace as tr, xplane
from chipbench.tests import record_scoped as rec

DATA = os.path.join(os.path.dirname(__file__), "data")
SMALL = os.path.join(DATA, "small.xplane.pb")
SCOPED = os.path.join(DATA, "scoped.xplane.pb")
SMALL_SPANS = {"window", "walk.run", "host.next"}
HARNESS = {"window", "walk.run", "train.consume"}
NAMES = scopes.program_names()


@pytest.fixture(scope="module")
def scoped():
    return scopes.reduce(SCOPED, [0], HARNESS, *NAMES)


@pytest.mark.parametrize("path", [SMALL, SCOPED])
def test_reader_gives_profile_data_times(path):
    """The device ops and their times are those ``ProfileData`` reads."""
    pd_ops = sorted(
        (e.name, e.start_ns, e.start_ns + e.duration_ns)
        for p in ProfileData.from_file(path).planes
        if p.name == "/device:TPU:0" for ln in p.lines
        if ln.name == scopes.OPS for e in ln.events)
    ours = []
    for plane in xplane.read(path).planes:
        meta, _ = xplane.tables(plane)
        if plane.name == "/device:TPU:0":
            ours += [(meta[e.metadata_id].name, *scopes._ns(ln, e))
                     for ln in plane.lines if ln.name == scopes.OPS
                     for e in ln.events]
    assert pd_ops and sorted(ours) == pd_ops


def test_scope_of_takes_innermost():
    s = set(NAMES[0])
    assert scopes.scope_of("jit(<lambda>)/dot_general:", s) == "unscoped"
    assert scopes.scope_of(
        "jit(_train_epoch)/sgns.grads/while/body/closed_call", s) == \
        "sgns.grads"
    assert scopes.scope_of("jit(_train_epoch)/sgns.grads/while/body/"
                           "closed_call/sgns.optimizer/mul:", s) == \
        "sgns.optimizer"


def test_small_trace_is_unscoped():
    """A program with no scopes or spans: every op unscoped, the idle time
    as ``trace.py`` attributes it."""
    old = tr.reduce(SMALL, [0], SMALL_SPANS)
    s = scopes.reduce(SMALL, [0], SMALL_SPANS, *NAMES)
    assert s.busy_s == old.busy_s and s.window_s == old.window_s
    assert s.scopes == {"unscoped": pytest.approx(old.busy_s, abs=1e-12)}
    assert s.spans == {}
    assert s.idle == [[n, pytest.approx(t, abs=1e-12)]
                      for n, t in tr.idle_by_span(old)]
    assert s.top_ops == [["jit__lambda/fusion", "unscoped",
                          pytest.approx(old.busy_s, abs=1e-12)]]


def test_scopes_sum_to_busy(scoped):
    assert scoped.busy_s == tr.reduce(SCOPED, [0], HARNESS).busy_s
    assert sum(scoped.scopes.values()) == pytest.approx(scoped.busy_s,
                                                        abs=1e-9)


@pytest.mark.parametrize("scope", NAMES[0])
def test_every_scope_has_device_time(scoped, scope):
    assert scoped.scopes.get(scope, 0.0) > 0.0


@pytest.mark.parametrize("span", NAMES[1])
def test_each_span_once(scoped, span):
    assert scoped.spans[span]["count"] == 1
    assert scoped.spans[span]["seconds"] > 0.0


def test_upload_bytes(scoped):
    want = rec.WALKERS * rec.LENGTH * 4 + 2 * 4 * (1 << 14)
    assert scoped.spans["train.upload"]["bytes"] == want
    assert scopes.per_layer(scoped, {})["sgns_h2d_bytes_per_round"] == want


def test_alias_refresh_takes_the_idle_gap(scoped):
    """The gap between the walk and the training lies inside the host alias
    refresh: with the program's spans it goes there, not to the harness's
    ``train.consume``."""
    idle = dict(scoped.idle)
    assert scoped.idle[0][0] == "train.alias_refresh"
    assert "train.consume" not in idle
    harness_only = dict(tr.idle_by_span(tr.reduce(SCOPED, [0], HARNESS)))
    assert harness_only["train.consume"] >= idle["train.alias_refresh"]
    assert sum(idle.values()) == pytest.approx(
        scoped.window_s - scoped.busy_s, abs=1e-9)


def test_program_without_names_reads_nothing():
    """A program that predates ``repro.obs``: all unscoped, no spans, and
    the per-layer numbers left out."""
    s = scopes.reduce(SCOPED, [0], HARNESS)
    assert list(s.scopes) == ["unscoped"] and s.spans == {}
    assert scopes.per_layer(s, {"walk_steps": 1, "sgns_steps": 1}) == {}


def test_per_layer_numbers(scoped):
    counts = {"walk_steps": rec.WALKERS * rec.LENGTH, "sgns_steps": 15}
    got = scopes.per_layer(scoped, counts)
    assert set(got) == set(scopes.SCOPE_METRICS) | {
        "sgns_alias_idle_share", "sgns_h2d_bytes_per_round"}
    assert got["walk_probs_ns_per_step"] == pytest.approx(
        scoped.scopes["walk.probs"] * 1e9 / counts["walk_steps"])
    assert 0.0 < got["sgns_alias_idle_share"] < 100.0
