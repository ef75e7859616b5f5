"""Three-term roofline from a compiled dry-run artifact.

    compute    = FLOPs_global   / (chips * peak_FLOP/s)
    memory     = bytes_global   / (chips * HBM_bw)
    collective = wire_bytes_global / (chips * link_bw)

Per-device quantities come from the parsed post-SPMD HLO (trip-count
corrected — see analysis.hlo); global = per-device * chips. We report the
raw ``cost_analysis()`` numbers alongside for comparison (they undercount
loop bodies). MODEL_FLOPS = 6*N*D (N = active params for MoE) gives the
"useful fraction" ratio that catches remat/redundancy waste.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

from ..core.cost_model import TPU_V5E, Hardware
from .hlo import parse_hlo

__all__ = ["RooflineReport", "analyze_compiled", "model_flops"]


@dataclasses.dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    chips: int
    # per-device, trip-corrected
    dot_flops_dev: float
    dot_bytes_dev: float
    wire_bytes_dev: float
    wire_by_family: dict
    collective_counts: dict
    # raw cost_analysis (per device, loop bodies counted once)
    xla_flops_dev: float
    xla_bytes_dev: float
    # memory analysis
    bytes_per_device: float
    # model-level
    model_flops_total: float
    unknown_trips: int

    hw: Hardware = TPU_V5E

    # ---- terms (seconds) ----
    @property
    def t_compute(self) -> float:
        return self.dot_flops_dev / self.hw.peak_flops

    @property
    def t_memory(self) -> float:
        return self.dot_bytes_dev / self.hw.hbm_bw

    @property
    def t_collective(self) -> float:
        return self.wire_bytes_dev / self.hw.link_bw

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
        total = self.dot_flops_dev * self.chips
        return self.model_flops_total / total if total else 0.0

    def row(self) -> dict:
        return {
            "arch": self.arch,
            "shape": self.shape,
            "mesh": self.mesh,
            "chips": self.chips,
            "t_compute_s": self.t_compute,
            "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "bottleneck": self.bottleneck,
            "model_flops": self.model_flops_total,
            "hlo_flops_global": self.dot_flops_dev * self.chips,
            "useful_flops_ratio": self.useful_flops_ratio,
            "hbm_bytes_global": self.dot_bytes_dev * self.chips,
            "wire_bytes_global": self.wire_bytes_dev * self.chips,
            "wire_by_family": self.wire_by_family,
            "collective_counts": self.collective_counts,
            "bytes_per_device": self.bytes_per_device,
            "xla_flops_dev": self.xla_flops_dev,
            "xla_bytes_dev": self.xla_bytes_dev,
            "unknown_trips": self.unknown_trips,
        }


def model_flops(cfg, shape, run_cfg=None) -> float:
    """6*N*D model FLOPs for the step being lowered."""
    n_active = cfg.param_count(active_only=True)
    if shape.mode == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens
    if shape.mode == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens
    # decode: one token per sequence
    return 2.0 * n_active * shape.global_batch


def analyze_compiled(
    compiled,
    *,
    arch: str,
    shape,
    mesh_name: str,
    chips: int,
    cfg=None,
    hw: Hardware = TPU_V5E,
) -> RooflineReport:
    txt = compiled.as_text()
    mod = parse_hlo(txt)
    cost = compiled.cost_analysis() or {}
    ma = compiled.memory_analysis()
    mem_bytes = float(
        ma.argument_size_in_bytes + ma.output_size_in_bytes + ma.temp_size_in_bytes
    )
    wire = mod.collective_wire_bytes()
    return RooflineReport(
        arch=arch,
        shape=shape.name,
        mesh=mesh_name,
        chips=chips,
        dot_flops_dev=mod.dot_flops(),
        dot_bytes_dev=mod.dot_bytes(),
        wire_bytes_dev=sum(wire.values()),
        wire_by_family=wire,
        collective_counts=mod.collective_count(),
        xla_flops_dev=float(cost.get("flops", 0.0)),
        xla_bytes_dev=float(cost.get("bytes accessed", 0.0)),
        bytes_per_device=mem_bytes,
        model_flops_total=model_flops(cfg, shape) if cfg else 0.0,
        unknown_trips=len(mod.unknown_trip),
        hw=hw,
    )
