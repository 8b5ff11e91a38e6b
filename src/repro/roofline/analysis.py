"""Roofline terms from a compiled dry-run artifact.

    compute term    = HLO_FLOPs / (chips × peak_FLOP/s)
    memory term     = HLO_bytes / (chips × HBM_bw)
    collective term = collective_bytes / (chips × link_bw)

HLO_FLOPs / HLO_bytes come from ``compiled.cost_analysis()``;
collective_bytes is parsed from the optimized HLO text: for every
all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute op we sum the *output* tensor bytes, with the wire
model  all-reduce → 2× (reduce + broadcast phases),  others → 1×.
MODEL_FLOPS = 6·N·D (dense) / 6·N_active·D (MoE) per train step; the
ratio MODEL_FLOPS / HLO_FLOPs exposes remat/redundancy waste.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Optional

from repro.config import ArchConfig, ShapeConfig
from repro.roofline.hw import HBM_BW, ICI_BW, PEAK_FLOPS_BF16

__all__ = [
    "CollectiveStats",
    "parse_collectives",
    "RooflineTerms",
    "roofline_terms",
    "model_flops",
    "cost_analysis_dict",
]


def cost_analysis_dict(compiled) -> Dict[str, float]:
    """``compiled.cost_analysis()``, with ``{}`` where the backend
    reports nothing."""
    return compiled.cost_analysis() or {}

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLLECTIVES = (
    "all-reduce",
    "all-gather",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

# `%op = bf16[8,128]{1,0} all-gather(...)` or tuple outputs
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_LINE_RE = re.compile(
    r"=\s*(\([^)]*\)|\S+)\s+(all-reduce-start|all-reduce|all-gather-start|all-gather|"
    r"reduce-scatter|all-to-all|collective-permute-start|collective-permute)\("
)


@dataclasses.dataclass
class CollectiveStats:
    bytes_by_op: Dict[str, float]
    count_by_op: Dict[str, int]

    @property
    def wire_bytes(self) -> float:
        """Modeled bytes on the wire: all-reduce counts double."""
        total = 0.0
        for op, b in self.bytes_by_op.items():
            total += 2.0 * b if op.startswith("all-reduce") else b
        return total

    @property
    def total_count(self) -> int:
        return sum(self.count_by_op.values())


def _shape_bytes(txt: str) -> float:
    total = 0.0
    for dt, dims in _SHAPE_RE.findall(txt):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def parse_collectives(hlo_text: str) -> CollectiveStats:
    bytes_by_op: Dict[str, float] = {}
    count_by_op: Dict[str, int] = {}
    for line in hlo_text.splitlines():
        m = _LINE_RE.search(line)
        if not m:
            continue
        shape_txt, op = m.group(1), m.group(2)
        op = op.replace("-start", "")
        b = _shape_bytes(shape_txt)
        bytes_by_op[op] = bytes_by_op.get(op, 0.0) + b
        count_by_op[op] = count_by_op.get(op, 0) + 1
    return CollectiveStats(bytes_by_op, count_by_op)


def model_flops(cfg: ArchConfig, shape: ShapeConfig) -> float:
    """6·N·D (dense) / 6·N_active·D (MoE); decode uses D = new tokens and
    2·N (forward only)."""
    n = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens
    tokens = shape.global_batch  # one new token per slot
    return 2.0 * n * tokens


@dataclasses.dataclass
class RooflineTerms:
    compute_s: float
    memory_s: float
    collective_s: float
    hlo_flops: float
    hlo_bytes: float
    collective_bytes: float
    model_flops: float
    chips: int

    @property
    def dominant(self) -> str:
        terms = {
            "compute": self.compute_s,
            "memory": self.memory_s,
            "collective": self.collective_s,
        }
        return max(terms, key=terms.get)

    @property
    def step_time_s(self) -> float:
        """Optimistic (perfect overlap): max of the three terms."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_flop_ratio(self) -> float:
        return self.model_flops / self.hlo_flops if self.hlo_flops else 0.0

    @property
    def mfu(self) -> float:
        """MODEL_FLOPS / (chips × peak × step_time) under the optimistic
        overlap model — the roofline fraction reported in §Perf."""
        t = self.step_time_s
        if t <= 0:
            return 0.0
        return self.model_flops / (self.chips * PEAK_FLOPS_BF16 * t)


def roofline_terms(
    *,
    hlo_flops: float,
    hlo_bytes: float,
    collective_bytes: float,
    chips: int,
    cfg: Optional[ArchConfig] = None,
    shape: Optional[ShapeConfig] = None,
    mflops: Optional[float] = None,
) -> RooflineTerms:
    if mflops is None:
        mflops = model_flops(cfg, shape) if cfg and shape else 0.0
    return RooflineTerms(
        compute_s=hlo_flops / (chips * PEAK_FLOPS_BF16),
        memory_s=hlo_bytes / (chips * HBM_BW),
        collective_s=collective_bytes / (chips * ICI_BW),
        hlo_flops=hlo_flops,
        hlo_bytes=hlo_bytes,
        collective_bytes=collective_bytes,
        model_flops=mflops,
        chips=chips,
    )
