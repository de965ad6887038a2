"""The harness's ``correct`` at a tiny size on the CPU: true for the program
as it is, false for the control (the program with q = 1, a first-order walk
that breaks the configured law) and for the timed path broken underneath.
The look for a chip is skipped; the rest of a run is driven as on the chip.
"""
import time

import jax
import numpy as np
import pytest
import faults
from conftest import TINY, tiny_cell

from chipbench.run import run_cell

SEED = 2**31 + 11


def _run(name, **kw):
    return run_cell(tiny_cell(name), SEED, 0.3, False, jax.devices(),
                    t_process=time.perf_counter(), **kw)


@pytest.mark.parametrize("name", sorted(TINY))
def test_sound_run_is_correct(name):
    r = _run(name)
    assert r["correct"], r["compared"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert list(r)[-1] == "compared"


@pytest.mark.parametrize("name", sorted(TINY))
def test_control_is_not_correct(name):
    r = _run(name, control=True)
    assert not r["correct"], r["compared"]
    failed = {k for k, c in r["compared"].items() if c["value"] > c["limit"]}
    assert failed == ({"loss_gap", "mu_gap", "delta_gap"} if name == "er20.sgns"
                      else {"law_z"})


@pytest.mark.parametrize("name", sorted(TINY))
@pytest.mark.parametrize("fault", sorted(faults.WALK))
def test_broken_timed_path_is_not_correct(monkeypatch, name, fault):
    faults.WALK[fault](monkeypatch)
    r = _run(name)
    assert not r["correct"], r["compared"]
    assert r["failed"] > 0
    assert r["compared"]["edge_faults"]["value"] > 0


def test_window_runs_whole_rounds():
    r = _run("er20.walk")
    rounds = r["attempted"] // r["setup"]["walkers_per_round"]
    assert rounds >= 1 and r["attempted"] % r["setup"]["walkers_per_round"] == 0
    assert r["setup"]["window_compiles"] == 0
    steps = r["metrics"]["walk_steps_per_s"]["value"] * r["setup"]["window_s"]
    assert steps == pytest.approx(r["attempted"] * 80)
    assert np.isfinite(r["metrics"]["setup_s"]["value"])


@pytest.mark.parametrize("fault", sorted(faults.TRAIN))
def test_broken_training_is_not_correct(monkeypatch, fault):
    faults.TRAIN[fault](monkeypatch)
    r = _run("er20.sgns")
    assert not r["correct"], r["compared"]
