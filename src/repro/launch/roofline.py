"""Roofline term extraction from compiled dry-run artifacts.

    compute term    = HLO_FLOPs / (chips * PEAK_FLOPS_BF16)
    memory term     = HLO_bytes / (chips * HBM_BW)
    collective term = collective_bytes / (chips * ICI_BW_PER_LINK)

cost_analysis() provides flops/bytes. collective_bytes is NOT there: we
parse the compiled (post-SPMD-partitioning) HLO text and sum operand sizes
of all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute
ops. Sizes come from the result-shape string on each op line; HLO is
per-device after partitioning, so the sum is per-device collective traffic
(matching the per-chip link-bandwidth denominator).
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict

from repro.launch.mesh import HBM_BW, ICI_BW_PER_LINK, PEAK_FLOPS_BF16

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLLECTIVES = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
)

_SHAPE_RE = re.compile(r"(pred|bf16|f16|f32|f64|s8|u8|s16|u16|s32|u32|s64|u64|c64|c128)\[([\d,]*)\]")


def _shape_bytes(text: str) -> int:
    """Sum byte sizes of every shape literal in an HLO result-type string."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes_from_hlo(hlo_text: str) -> Dict[str, int]:
    """Per-collective-kind operand/result bytes from compiled HLO text."""
    out: Dict[str, int] = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        ls = line.strip()
        # match op lines: `%name = <shape> all-reduce(...)`, also fusion-free starts
        m = re.match(r"^(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(.+?)\s+([\w\-]+)\(", ls)
        if not m:
            continue
        result_type, opname = m.group(1), m.group(2)
        for coll in _COLLECTIVES:
            if opname == coll or opname.startswith(coll + "-start"):
                out[coll] += _shape_bytes(result_type)
                break
    return out


@dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    chips: int
    flops: float                      # total HLO FLOPs = per-device * chips
    hbm_bytes: float                  # per-device bytes accessed (cost_analysis)
    collective_bytes: float           # per-device collective bytes
    collectives: Dict[str, int] = field(default_factory=dict)
    model_flops: float = 0.0          # 6*N*D (or 6*N_active*D)
    peak_memory_bytes: float = 0.0    # per-device, from memory_analysis

    @property
    def t_compute(self) -> float:
        return self.flops / (self.chips * PEAK_FLOPS_BF16)

    @property
    def t_memory(self) -> float:
        return self.hbm_bytes / HBM_BW

    @property
    def t_collective(self) -> float:
        return self.collective_bytes / ICI_BW_PER_LINK

    @property
    def bottleneck(self) -> str:
        terms = {
            "compute": self.t_compute,
            "memory": self.t_memory,
            "collective": self.t_collective,
        }
        return max(terms, key=terms.get)

    @property
    def useful_flops_ratio(self) -> float:
        return self.model_flops / self.flops if self.flops else 0.0

    def to_dict(self) -> dict:
        return {
            "arch": self.arch, "shape": self.shape, "mesh": self.mesh,
            "chips": self.chips, "flops": self.flops,
            "hbm_bytes": self.hbm_bytes,
            "collective_bytes": self.collective_bytes,
            "collectives": self.collectives,
            "model_flops": self.model_flops,
            "peak_memory_bytes": self.peak_memory_bytes,
            "t_compute": self.t_compute, "t_memory": self.t_memory,
            "t_collective": self.t_collective, "bottleneck": self.bottleneck,
            "useful_flops_ratio": self.useful_flops_ratio,
        }


def analyze(compiled, *, arch: str, shape: str, mesh_name: str, chips: int,
            model_flops: float = 0.0) -> Roofline:
    cost = compiled.cost_analysis()
    # cost_analysis reports the per-device (post-SPMD-partitioning) module;
    # scale FLOPs to the global total (uniform across devices). bytes and
    # collective bytes stay per-device to match per-chip bandwidth terms.
    flops = float(cost.get("flops", 0.0)) * chips
    hbm = float(cost.get("bytes accessed", 0.0))
    try:
        mem = compiled.memory_analysis()
        peak = float(
            getattr(mem, "temp_size_in_bytes", 0)
            + getattr(mem, "argument_size_in_bytes", 0)
            + getattr(mem, "output_size_in_bytes", 0)
            - getattr(mem, "alias_size_in_bytes", 0)
        )
    except Exception:
        peak = 0.0
    colls = collective_bytes_from_hlo(compiled.as_text())
    return Roofline(
        arch=arch, shape=shape, mesh=mesh_name, chips=chips, flops=flops,
        hbm_bytes=hbm, collective_bytes=float(sum(colls.values())),
        collectives=colls, model_flops=model_flops, peak_memory_bytes=peak,
    )
