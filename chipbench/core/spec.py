"""Finds a cell's pieces by the names in ``BENCHMARK.json``: its
configuration file, its traffic file ``chipbench/traffic/<traffic>.json``,
the driver of the traffic's ``kind`` in ``chipbench/kinds/<kind>.py``, its
metrics, and one reader per per-layer metric in
``chipbench/metrics/<metric>.py``. Adding a cell, a traffic kind or a
metric adds files and entries; no file here changes."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import re

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list      # the BENCHMARK.json entries this cell reports
    per_layer: list


def _in_cell(metric: dict, name: str) -> bool:
    return "workloads" not in metric or name in metric["workloads"]


def load_cell(name: str, root: str = ROOT) -> Cell:
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"there are {sorted(cells)}")
    w = cells[name]
    cfg = {c["name"]: c for c in bench["configs"]}[w["config"]]
    return Cell(
        name=name, chips=int(w["chips"]),
        config=load_json(os.path.join(root, cfg["file"])),
        traffic=load_json(os.path.join(BENCH_DIR, "traffic",
                                       w["traffic"] + ".json")),
        end_to_end=[m for m in bench["end_to_end"] if _in_cell(m, name)],
        per_layer=[m for m in bench["per_layer"] if _in_cell(m, name)])


def _module(folder: str, name: str):
    path = os.path.join(BENCH_DIR, folder, name + ".py")
    if not os.path.isfile(path):
        raise KeyError(f"no {path}")
    mod_spec = importlib.util.spec_from_file_location(
        f"chipbench_{folder}_" + re.sub(r"\W", "_", name), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def kind_module(kind: str):
    """``chipbench/kinds/<kind>.py``: its ``prepare(cell, seed, seconds,
    span, control=...)`` sets a cell of that traffic kind up and returns a
    ``chipbench.core.walker.Prepared``; ``GROUPS`` names the groups of the
    configuration's ``limits`` that its check compares."""
    return _module("kinds", kind)


def metric_reader(name: str):
    """``read(ctx)`` of ``chipbench/metrics/<name>.py``: the metric's value,
    or None where the run gives it nothing to read."""
    return _module("metrics", name).read


def peaks_for(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; a device not in the table is
    an error, not a default."""
    table = load_json(os.path.join(BENCH_DIR, "peaks.json"))
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (known: {sorted(table['devices'])})")
    return table["devices"][device_kind]
