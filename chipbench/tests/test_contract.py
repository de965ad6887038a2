"""BENCHMARK.json against the benchmark's contract, every piece findable by
its name, and the command's refusals: no TPU, and a checkout that holds
only the benchmark."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest
from conftest import ROOT

from chipbench.core import spec

BENCH = spec.load_json(os.path.join(ROOT, "BENCHMARK.json"))
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.-]{1,16}")


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "chipbench/run.py"]
    assert BENCH["paths"] == ["chipbench"]


def test_run_seconds_fits_a_full_check_of_24_cells():
    s = BENCH["run_seconds"]
    assert 1 <= s <= 51
    assert (2 + 14 * 24) * (s + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_names_units_and_entries():
    names = [e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in BENCH[k]]
    assert all(NAME.fullmatch(n) for n in names)
    assert len({e["name"] for e in BENCH["end_to_end"] + BENCH["per_layer"]}
               ) == len(BENCH["end_to_end"]) + len(BENCH["per_layer"])
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.fullmatch(m["unit"]) and m["better"] in ("lower",
                                                               "higher")
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert "setup_s" in {m["name"] for m in BENCH["end_to_end"]}


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_each_cell_loads_by_name(cell):
    c = spec.load_cell(cell)
    assert c.chips in (1, 4)
    kind = spec.kind_module(c.traffic["kind"])
    assert callable(kind.prepare)
    assert set(kind.GROUPS) <= set(c.config["limits"])
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c.per_layer and all(m["moves"] in e2e for m in c.per_layer)
    for m in c.per_layer:
        assert callable(spec.metric_reader(m["name"]))


def test_configs_are_files_under_paths():
    files = [c["file"] for c in BENCH["configs"]]
    assert len(set(files)) == len(files)
    for c in BENCH["configs"]:
        assert c["file"].startswith("chipbench/configs/")
        cfg = spec.load_json(os.path.join(ROOT, c["file"]))
        assert set(c["reduced"]) <= set(cfg["reduced"])


def test_unknown_kind_is_an_error():
    with pytest.raises(KeyError):
        spec.kind_module("no_such_kind")


def test_unknown_device_has_no_peaks():
    assert spec.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        spec.peaks_for("cpu")


def _run(cwd, env_extra):
    env = {**os.environ, **env_extra}
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "er20.walk",
         "--seed", "5", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(out: str) -> bool:
    return not any(line.startswith("{") for line in out.splitlines())


def test_refuses_without_a_tpu():
    r = _run(ROOT, {"JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0 and _no_result(r.stdout), r.stderr[-2000:]
    assert "needs a TPU" in r.stderr


def test_refuses_with_only_the_benchmark(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    r = _run(tmp_path, {"JAX_PLATFORMS": "cpu", "PYTHONPATH": ""})
    assert r.returncode != 0 and _no_result(r.stdout), r.stderr[-2000:]
