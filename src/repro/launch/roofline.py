"""Roofline-term extraction from compiled dry-run artifacts.

Three terms per (arch x shape x mesh), in seconds:

  compute    = HLO_FLOPs_per_device / peak_FLOP/s
  memory     = HLO_bytes_per_device / HBM_bw
  collective = collective_bytes_per_device / link_bw

FLOPs/bytes/collective-bytes come from ``launch.hlo_analysis`` — a
loop-aware analysis of the optimized post-SPMD HLO (XLA's own
``cost_analysis()`` counts while bodies once, so a scanned 95-layer model
would be undercounted ~95x; see hlo_analysis docstring). Post-SPMD shapes
are per-device, so terms are per-chip directly. Raw ``cost_analysis()``
numbers are retained in the dry-run JSON for reference.
"""
from __future__ import annotations

import dataclasses

from repro.launch.hlo_analysis import analyze
from repro.launch.mesh import chip_peaks


@dataclasses.dataclass
class RooflineTerms:
    flops: float  # per-device HLO FLOPs
    hbm_bytes: float  # per-device bytes moved
    coll_bytes: float  # per-device collective payload bytes
    n_devices: int
    device_kind: str  # keys the peak table; an unknown chip raises
    coll_breakdown: dict = dataclasses.field(default_factory=dict)

    @property
    def t_compute(self) -> float:
        return self.flops / chip_peaks(self.device_kind).flops_bf16

    @property
    def t_memory(self) -> float:
        return self.hbm_bytes / chip_peaks(self.device_kind).hbm_bw

    @property
    def t_collective(self) -> float:
        return self.coll_bytes / chip_peaks(self.device_kind).ici_bw

    @property
    def bottleneck(self) -> str:
        terms = {
            "compute": self.t_compute,
            "memory": self.t_memory,
            "collective": self.t_collective,
        }
        return max(terms, key=terms.get)

    @property
    def t_bound(self) -> float:
        return max(self.t_compute, self.t_memory, self.t_collective)

    def as_dict(self) -> dict:
        return {
            "flops_per_device": self.flops,
            "hbm_bytes_per_device": self.hbm_bytes,
            "coll_bytes_per_device": self.coll_bytes,
            "n_devices": self.n_devices,
            "device_kind": self.device_kind,
            "t_compute_s": self.t_compute,
            "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "bottleneck": self.bottleneck,
            "coll_breakdown": self.coll_breakdown,
        }


def extract_terms(compiled, n_devices: int, device_kind: str) -> RooflineTerms:
    """Pull per-device roofline terms from a compiled artifact's HLO,
    against the peaks of ``device_kind``."""
    stats = analyze(compiled.as_text())
    return RooflineTerms(
        flops=stats["flops"],
        hbm_bytes=stats["bytes"],
        coll_bytes=stats["coll_bytes"],
        n_devices=n_devices,
        device_kind=device_kind,
        coll_breakdown=stats["coll_breakdown"],
    )


def model_flops(
    param_count: int,
    tokens: int,
    active_param_count: int | None = None,
    kind: str = "train",
) -> float:
    """MODEL_FLOPS: 6*N*D for training (fwd+bwd), 2*N*D for inference.
    MoE uses N_active."""
    n = active_param_count if active_param_count is not None else param_count
    factor = 6.0 if kind == "train" else 2.0
    return factor * n * tokens
