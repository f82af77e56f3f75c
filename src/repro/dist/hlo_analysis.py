"""Collective-traffic accounting + XLA analysis extraction + roofline.

Hardware model (one v5e-class chip; see DESIGN notes in
benchmarks/roofline.py):
  PEAK_FLOPS_BF16  197 TFLOP/s
  HBM_BW           819 GB/s
  ICI_BW           50 GB/s per link

`collective_bytes(text)` is a ONE-PASS text scan (no trip-count
multiplication -- use dist.hlo_cost.analyze for that); it exists so the
dry-run can record the per-program collective mix cheaply and so tests can
pin the opcode accounting (-start counted once, -done never).
"""
from __future__ import annotations

import dataclasses
import re

from repro.dist.hlo_cost import (is_collective, leaf_bytes,
                                 normalize_collective, parse_shape)

PEAK_FLOPS_BF16 = 197e12   # flop/s
HBM_BW = 819e9             # byte/s
ICI_BW = 50e9              # byte/s per link


# ---------------------------------------------------------------------------
# Collective traffic (single-pass, text level)
# ---------------------------------------------------------------------------

_INSTR = re.compile(
    r"^\s*(?:ROOT\s+)?%[\w.\-]+\s*=\s*(\([^=]*?\)|\w+\[[^\]]*\](?:\{[^}]*\})?)"
    r"\s+([\w\-]+)\(")


def collective_bytes(text: str) -> dict:
    """Sum output bytes of every collective instruction in `text`.

    Returns {"by_op": {base_opcode: bytes}, "count": n, "total_bytes": b}.
    Async pairs count once: `-start` carries the shape, `-done` is skipped.
    """
    by_op: dict[str, float] = {}
    count = 0
    for line in text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        type_str, opcode = m.group(1), m.group(2)
        if not is_collective(opcode):
            continue
        base = normalize_collective(opcode)
        nbytes = leaf_bytes(parse_shape(type_str))
        by_op[base] = by_op.get(base, 0.0) + nbytes
        count += 1
    return {"by_op": by_op, "count": count,
            "total_bytes": sum(by_op.values())}


# ---------------------------------------------------------------------------
# XLA compiled-module analyses
# ---------------------------------------------------------------------------

def cost_analysis_terms(compiled) -> tuple[float, float]:
    """(flops, bytes_accessed) from compiled.cost_analysis(); 0.0 when the
    backend does not report a term.  NOTE: XLA counts loop bodies ONCE --
    use dist.hlo_cost for trip-count-aware totals."""
    ca = compiled.cost_analysis()
    return float(ca.get("flops", 0.0)), float(ca.get("bytes accessed", 0.0))


_MEM_FIELDS = ("argument_size_in_bytes", "output_size_in_bytes",
               "temp_size_in_bytes", "alias_size_in_bytes",
               "generated_code_size_in_bytes")


def memory_analysis_dict(compiled) -> dict:
    """compiled.memory_analysis() flattened to a plain dict."""
    ma = compiled.memory_analysis()
    return {f: int(getattr(ma, f)) for f in _MEM_FIELDS}


# ---------------------------------------------------------------------------
# Roofline
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Roofline:
    """Three-term per-device roofline: compute vs HBM vs interconnect."""
    flops: float
    hbm_bytes: float
    collective_bytes: float = 0.0
    peak_flops: float = PEAK_FLOPS_BF16
    hbm_bw: float = HBM_BW
    ici_bw: float = ICI_BW

    @property
    def t_compute_s(self) -> float:
        return self.flops / self.peak_flops

    @property
    def t_memory_s(self) -> float:
        return self.hbm_bytes / self.hbm_bw

    @property
    def t_collective_s(self) -> float:
        return self.collective_bytes / self.ici_bw

    @property
    def bound_s(self) -> float:
        return max(self.t_compute_s, self.t_memory_s, self.t_collective_s)

    @property
    def dominant(self) -> str:
        terms = (("compute", self.t_compute_s), ("memory", self.t_memory_s),
                 ("collective", self.t_collective_s))
        return max(terms, key=lambda kv: kv[1])[0]

    @property
    def arithmetic_intensity(self) -> float:
        return self.flops / max(self.hbm_bytes, 1e-9)

    def as_dict(self) -> dict:
        return {
            "flops": self.flops,
            "hbm_bytes": self.hbm_bytes,
            "collective_bytes": self.collective_bytes,
            "t_compute_s": self.t_compute_s,
            "t_memory_s": self.t_memory_s,
            "t_collective_s": self.t_collective_s,
            "bound_s": self.bound_s,
            "dominant": self.dominant,
            "arithmetic_intensity": self.arithmetic_intensity,
        }
