"""Shared helpers of the benchmark's CPU tests: cells at a tiny scale, built
from the real configuration and traffic files."""
from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from chipbench.core import spec  # noqa: E402

TINY = {"er20.walk": {"scale": 10}, "er20.sgns": {"scale": 10},
        "wec16.walk": {"scale": 10, "cap": 24}}
TINY_WALKERS = {"er20.walk": 128, "er20.sgns": 16, "wec16.walk": 128}


def tiny_cell(name: str):
    """The cell ``name`` with its graph cut to 1,024 vertices and fewer
    walkers a round; everything else as its files state."""
    cell = spec.load_cell(name)
    cell.config = {**cell.config, **TINY[name]}
    cell.traffic = {**cell.traffic, "walkers_per_round": TINY_WALKERS[name]}
    return cell
