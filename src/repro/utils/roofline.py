"""Three-term roofline model from compiled dry-run artifacts.

Peaks live in one table keyed by ``jax.Device.device_kind``; a device that
is not in the table is an error, never a default.

Terms (assignment formulas; all reduce to per-chip quantities because the
compiled module is the per-device program):
  compute    = flops_per_device / peak
  memory     = bytes_per_device / hbm_bw
  collective = collective_bytes_per_device / ici_bw
"""

from __future__ import annotations

from dataclasses import asdict, dataclass, field
from typing import Dict, Optional


@dataclass(frozen=True)
class ChipPeaks:
    flops: float  # bf16 FLOP/s per chip
    hbm_bw: float  # HBM bytes/s per chip
    ici_bw: float  # ICI bytes/s per link per chip
    hbm_bytes: int  # HBM capacity per chip


#: Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB of
#: HBM at 819 GB/s, 1,600 Gbit/s of interconnect per chip over 4 links.
PEAKS: Dict[str, ChipPeaks] = {
    "TPU v5 lite": ChipPeaks(flops=197e12, hbm_bw=819e9, ici_bw=50e9,
                             hbm_bytes=16 * 10**9),
}

#: the chip the production meshes and the dry run describe (v5e pods)
DRYRUN_DEVICE_KIND = "TPU v5 lite"


def peaks_for(device_kind: str) -> ChipPeaks:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


@dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    kind: str  # train | prefill | decode
    flops_per_device: float
    bytes_per_device: float
    collective_bytes_per_device: float
    model_flops: float  # 6*N*D (dense) or 6*N_active*D (MoE), global
    n_devices: int
    device_kind: str = DRYRUN_DEVICE_KIND
    peak_memory_per_device: Optional[float] = None
    collectives: Dict[str, Dict[str, float]] = field(default_factory=dict)
    notes: str = ""

    @property
    def peaks(self) -> ChipPeaks:
        return peaks_for(self.device_kind)

    @property
    def t_compute(self) -> float:
        return self.flops_per_device / self.peaks.flops

    @property
    def t_memory(self) -> float:
        return self.bytes_per_device / self.peaks.hbm_bw

    @property
    def t_collective(self) -> float:
        return self.collective_bytes_per_device / self.peaks.ici_bw

    @property
    def bottleneck(self) -> str:
        terms = {
            "compute": self.t_compute,
            "memory": self.t_memory,
            "collective": self.t_collective,
        }
        return max(terms, key=terms.get)

    @property
    def step_time_lower_bound(self) -> float:
        """No-overlap lower bound: the max term (perfect overlap of others)."""
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def useful_flops_fraction(self) -> float:
        """MODEL_FLOPS / HLO_FLOPS(global) — remat/recompute/waste detector."""
        hlo_global = self.flops_per_device * self.n_devices
        return self.model_flops / hlo_global if hlo_global else 0.0

    @property
    def roofline_fraction(self) -> float:
        """How close the step would run to the compute roofline if it achieved
        the no-overlap lower bound: useful-compute-time / bound."""
        t_useful = (self.model_flops / self.n_devices) / self.peaks.flops
        lb = self.step_time_lower_bound
        return t_useful / lb if lb else 0.0

    def to_dict(self) -> dict:
        d = asdict(self)
        d.update(
            t_compute=self.t_compute,
            t_memory=self.t_memory,
            t_collective=self.t_collective,
            bottleneck=self.bottleneck,
            useful_flops_fraction=self.useful_flops_fraction,
            roofline_fraction=self.roofline_fraction,
        )
        return d


def model_flops(cfg, shape_kind: str, seq_len: int, global_batch: int) -> float:
    """6*N*D (training) / 2*N*D (inference fwd) with N = active params."""
    n = getattr(cfg, "n_active_params", None) or cfg.n_params
    tokens = seq_len * global_batch
    if shape_kind == "train":
        return 6.0 * n * tokens
    if shape_kind == "prefill":
        return 2.0 * n * tokens
    return 2.0 * n * global_batch  # decode: one token per sequence
