"""The deployment's graph: an RMAT graph made from the configuration's own
``graph_seed``, symmetrised and deduplicated, as CSR with sorted rows.

A copy of the generator the program ships (RMAT per Chakrabarti et al., as
Fast-Node2Vec section 4.1 uses it, and the CSR build), kept here so the data
stays fixed while the program changes. The CSR is cached under
``chipbench/.cache/`` (git-ignored), keyed by the generator's parameters, so
only the first run in a checkout pays for generation.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os

import numpy as np

CACHE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), ".cache")


@dataclasses.dataclass
class Csr:
    """Host CSR: ``col[row_ptr[v]:row_ptr[v+1]]`` are v's neighbours, sorted."""
    n: int
    row_ptr: np.ndarray   # [n+1] int64
    col: np.ndarray       # [m] int32
    wgt: np.ndarray       # [m] float32

    @property
    def deg(self) -> np.ndarray:
        return np.diff(self.row_ptr).astype(np.int64)

    def neighbors(self, v: int) -> np.ndarray:
        return self.col[self.row_ptr[v]:self.row_ptr[v + 1]]

    def weights(self, v: int) -> np.ndarray:
        return self.wgt[self.row_ptr[v]:self.row_ptr[v + 1]]


def rmat_edges(k: int, num_edges: int, a: float, b: float, c: float,
               d: float, seed: int) -> tuple[np.ndarray, np.ndarray]:
    """``num_edges`` directed RMAT edges over 2^k vertices: per level, one
    quadrant bit pair, P(row=1) = c+d, then P(col=1 | row)."""
    if abs(a + b + c + d - 1.0) > 1e-6:
        raise ValueError(f"RMAT probabilities sum to {a + b + c + d}, not 1")
    rng = np.random.default_rng(seed)
    p_col1_row0 = b / max(a + b, 1e-12)
    p_col1_row1 = d / max(c + d, 1e-12)
    src = np.zeros(num_edges, dtype=np.int64)
    dst = np.zeros(num_edges, dtype=np.int64)
    for _ in range(k):
        row = rng.random(num_edges) < c + d
        col = rng.random(num_edges) < np.where(row, p_col1_row1, p_col1_row0)
        src = (src << 1) | row
        dst = (dst << 1) | col
    return src, dst


def csr_from_edges(n: int, src: np.ndarray, dst: np.ndarray) -> Csr:
    """Undirected, unit-weight CSR: self loops dropped, reverse edges added,
    duplicates removed, rows sorted ascending."""
    keep = src != dst
    src, dst = src[keep], dst[keep]
    src, dst = np.concatenate([src, dst]), np.concatenate([dst, src])
    key = np.unique(src * n + dst)
    src, dst = key // n, key % n
    row_ptr = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(np.bincount(src, minlength=n), out=row_ptr[1:])
    return Csr(n=n, row_ptr=row_ptr, col=dst.astype(np.int32),
               wgt=np.ones(dst.size, np.float32))


def rmat_graph(spec: dict) -> Csr:
    """``spec``: ``scale`` (k), ``avg_degree``, ``rmat`` [a, b, c, d],
    ``graph_seed``. Draws n * avg_degree / 2 edges, as the paper's
    undirected treatment doubles each."""
    k = int(spec["scale"])
    n = 1 << k
    src, dst = rmat_edges(k, int(n * spec["avg_degree"] / 2),
                          *spec["rmat"], seed=int(spec["graph_seed"]))
    return csr_from_edges(n, src, dst)


def load_graph(spec: dict) -> Csr:
    """The graph of ``spec``, from the cache where it is there."""
    fields = {k: spec[k] for k in ("scale", "avg_degree", "rmat",
                                   "graph_seed")}
    tag = hashlib.sha256(json.dumps(fields, sort_keys=True).encode())
    path = os.path.join(CACHE_DIR, f"rmat-{tag.hexdigest()[:16]}.npz")
    if os.path.exists(path):
        with np.load(path) as z:
            return Csr(n=int(z["n"]), row_ptr=z["row_ptr"], col=z["col"],
                       wgt=z["wgt"])
    g = rmat_graph(spec)
    os.makedirs(CACHE_DIR, exist_ok=True)
    tmp = path + ".part.npz"
    np.savez(tmp, n=g.n, row_ptr=g.row_ptr, col=g.col, wgt=g.wgt)
    os.replace(tmp, path)
    return g
