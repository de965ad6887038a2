"""Roofline analysis from compiled dry-run artifacts (no hardware needed).

Per (arch x shape x mesh) cell, three terms in seconds:

    compute    = HLO_FLOPs / (chips * PEAK_FLOPS)
    memory     = HLO_bytes / (chips * HBM_BW)
    collective = collective_operand_bytes / (chips * LINK_BW)

FLOPs/bytes come from ``compiled.cost_analysis()``; collective bytes are NOT
in cost_analysis, so we parse the optimized HLO text and sum operand sizes of
every all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute.

Because ``cost_analysis`` does NOT multiply through ``while`` loops (verified
empirically: scan length does not change reported flops), the dry-run compiles
the model at 1 and 2 superblocks *unrolled* and extrapolates linearly —
exact for a homogeneous stack:  cost(N) = c1 + (N-1) * (c2 - c1).

Hardware model (TPU v5e-class, per chip): 197 TFLOP/s bf16, 819 GB/s HBM,
~50 GB/s/link ICI.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Optional

PEAK_FLOPS = 197e12      # bf16 FLOP/s per chip
HBM_BW = 819e9           # bytes/s per chip
LINK_BW = 50e9           # bytes/s per ICI link

COLLECTIVE_OPS = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                  "collective-permute")

# "%name = TYPE[dims]{layout} opcode(...), replica_groups=[g,k]<=[n] ..."
_LINE_RE = re.compile(
    r"=\s+(.*?)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start|-done)?\(")
_SHAPE_RE = re.compile(r"(f64|f32|bf16|f16|s64|s32|s16|s8|u64|u32|u16|u8|"
                       r"pred)\[([\d,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_BYTES = {"f64": 8, "s64": 8, "u64": 8, "f32": 4, "s32": 4, "u32": 4,
          "f16": 2, "bf16": 2, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
          "pred": 1}


def cost_dict(ca) -> Dict[str, float]:
    """``compiled.cost_analysis()`` as a plain dict (empty where the backend
    reports nothing)."""
    return dict(ca or {})


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _BYTES[dtype]


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum per-device *operand* bytes per collective opcode.

    HLO text prints only the result shape, so operand bytes are recovered
    from the result + the op semantics: all-gather result = group_size x
    operand; reduce-scatter operand = group_size x result; all-reduce /
    all-to-all / collective-permute result == operand. Async -start/-done
    pairs are counted once.
    """
    out: Dict[str, int] = {k: 0 for k in COLLECTIVE_OPS}
    counts: Dict[str, int] = {k: 0 for k in COLLECTIVE_OPS}
    for line in hlo_text.splitlines():
        m = _LINE_RE.search(line)
        if not m or m.group(3) == "-done":
            continue
        result, op = m.group(1), m.group(2)
        rbytes = sum(_shape_bytes(d, s) for d, s in _SHAPE_RE.findall(result))
        gm = _GROUPS_RE.search(line)
        gsize = int(gm.group(2)) if gm else 1
        if op == "all-gather" and gsize:
            rbytes = rbytes // max(gsize, 1)
        elif op == "reduce-scatter":
            rbytes = rbytes * gsize
        out[op] += rbytes
        counts[op] += 1
    out["_counts"] = counts  # type: ignore[assignment]
    return out


@dataclasses.dataclass
class Roofline:
    """Cost-analysis numbers are PER-DEVICE (verified: an SPMD module's
    cost_analysis reports the per-device program), so:

        HLO_FLOPs_total = hlo_flops * chips, and
        t_compute = HLO_FLOPs_total / (chips * peak) = hlo_flops / peak.
    """
    arch: str
    shape: str
    mesh: str
    chips: int
    hlo_flops: float           # per device
    hlo_bytes: float           # per device
    coll_bytes: float          # per device
    coll_by_op: Dict[str, int]
    model_flops: float         # global (6*N*D)
    per_device_mem: Optional[float] = None

    @property
    def t_compute(self) -> float:
        return self.hlo_flops / PEAK_FLOPS

    @property
    def t_memory(self) -> float:
        return self.hlo_bytes / HBM_BW

    @property
    def t_collective(self) -> float:
        return self.coll_bytes / LINK_BW

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def useful_ratio(self) -> float:
        """MODEL_FLOPS / HLO_FLOPs_total — how much compiled compute is
        'useful' (catches remat recompute / replication / routing waste)."""
        total = self.hlo_flops * self.chips
        return self.model_flops / total if total else 0.0

    @property
    def roofline_fraction(self) -> float:
        """ideal compute-only time vs the max roofline term (the score)."""
        t = max(self.t_compute, self.t_memory, self.t_collective)
        ideal = self.model_flops / (self.chips * PEAK_FLOPS)
        return ideal / t if t > 0 else 0.0

    def to_dict(self) -> dict:
        return {
            "arch": self.arch, "shape": self.shape, "mesh": self.mesh,
            "chips": self.chips, "hlo_flops": self.hlo_flops,
            "hlo_bytes": self.hlo_bytes, "coll_bytes": self.coll_bytes,
            "coll_by_op": self.coll_by_op, "model_flops": self.model_flops,
            "t_compute": self.t_compute, "t_memory": self.t_memory,
            "t_collective": self.t_collective, "bottleneck": self.bottleneck,
            "useful_ratio": self.useful_ratio,
            "roofline_fraction": self.roofline_fraction,
            "per_device_mem": self.per_device_mem,
        }


def extrapolate(c1: dict, c2: dict, n: int) -> dict:
    """cost(N) = c1 + (N-1)*(c2 - c1), per numeric key (homogeneous stack)."""
    out = {}
    for k in c1:
        v1 = c1.get(k, 0)
        v2 = c2.get(k, 0)
        if isinstance(v1, dict):
            out[k] = extrapolate(v1, v2 if isinstance(v2, dict) else {}, n)
        else:
            out[k] = (v1 or 0) + (n - 1) * ((v2 or 0) - (v1 or 0))
    return out


def model_flops_for(cfg, kind: str, seq: int, global_batch: int) -> float:
    """6*N*D (dense) / 6*N_active*D for training; 2*N*D forward-only.
    D = processed tokens. Decode processes one token per call."""
    n_active = cfg.active_param_count()
    if kind == "train":
        tokens = seq * global_batch
        return 6.0 * n_active * tokens
    if kind == "prefill":
        tokens = seq * global_batch
        return 2.0 * n_active * tokens
    tokens = global_batch  # decode: one new token per sequence
    return 2.0 * n_active * tokens
