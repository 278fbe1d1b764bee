"""Three-term roofline model, with per-chip peaks keyed by device kind.

Per compiled (arch × shape × mesh) step::

    compute    = HLO_FLOPs   / (chips × peak_FLOP/s)
    memory     = HLO_bytes   / (chips × HBM_bw)
    collective = coll_bytes  /  link_bw           (per-device bytes)

``cost_analysis()`` already reports *per-device* FLOPs/bytes when the
program is SPMD-partitioned, so the chips factor is only applied when
explicitly requested (``per_device=False``).  Collective bytes come from
the HLO parse (``analysis.hlo``) and are per-device by the output-bytes
convention documented there.

The peaks come from :data:`PEAKS`, keyed by ``jax.Device.device_kind``;
a kind missing from the table is an error, never a default.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

from repro.analysis import hlo as hlo_mod


@dataclasses.dataclass(frozen=True)
class HW:
    """Published per-chip constants."""

    peak_flops: float                # bf16 FLOP/s
    hbm_bw: float                    # bytes/s
    ici_bw: float                    # bytes/s per link
    hbm_bytes: float                 # capacity, for fits-check commentary


#: Per-chip peaks by ``device_kind``.  TPU v5e (JAX reports it as
#: "TPU v5 lite"), from the Google Cloud documentation, "TPU v5e":
#: 197 TFLOP/s bf16, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of
#: chip-to-chip interconnect (200 GB/s over 4 links = 50 GB/s a link).
PEAKS: Dict[str, HW] = {
    "TPU v5 lite": HW(peak_flops=197e12, hbm_bw=819e9, ici_bw=50e9,
                      hbm_bytes=16e9),
}

#: The v5e entry (the dry-run's target chip).
V5E_KIND = "TPU v5 lite"
V5E = PEAKS[V5E_KIND]


def peaks_for(device_kind: str) -> HW:
    """The published peaks of ``device_kind``; raises on a kind the
    table does not hold."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; add "
            f"them to roofline.PEAKS with their source") from None


def model_flops(param_count: int, tokens: int, *,
                active_param_count: Optional[int] = None) -> float:
    """The 6·N·D convention (6·N_active·D for MoE)."""
    n = active_param_count if active_param_count is not None else param_count
    return 6.0 * float(n) * float(tokens)


@dataclasses.dataclass(frozen=True)
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    chips: int
    hlo_flops: float                  # per device
    hlo_bytes: float                  # per device (HBM traffic)
    collective_bytes: float           # per device
    collective_detail: Dict[str, int]
    model_flops_total: float          # 6·N·D for the global step
    peak_memory_bytes: float          # per device, from memory_analysis
    device_kind: str                  # key into PEAKS
    bytes_detail: Dict[str, float] = dataclasses.field(default_factory=dict)

    @property
    def hw(self) -> HW:
        return peaks_for(self.device_kind)

    # -- the three terms, in seconds -------------------------------------
    @property
    def t_compute(self) -> float:
        return self.hlo_flops / self.hw.peak_flops

    @property
    def t_memory(self) -> float:
        return self.hlo_bytes / self.hw.hbm_bw

    @property
    def t_collective(self) -> float:
        return self.collective_bytes / self.hw.ici_bw

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def t_bound(self) -> float:
        """Lower-bound step time: terms overlap perfectly ⇒ max()."""
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def useful_flops_frac(self) -> float:
        """MODEL_FLOPS / (chips × HLO_FLOPs): remat/redundancy waste."""
        tot = self.hlo_flops * self.chips
        return self.model_flops_total / tot if tot else 0.0

    @property
    def mfu_bound(self) -> float:
        """Model-FLOPs utilization at the roofline bound (the score)."""
        denom = self.t_bound * self.chips * self.hw.peak_flops
        return self.model_flops_total / denom if denom else 0.0

    def row(self) -> Dict[str, Any]:
        return {
            "arch": self.arch, "shape": self.shape, "mesh": self.mesh,
            "chips": self.chips,
            "t_compute_s": self.t_compute, "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "bottleneck": self.bottleneck, "t_bound_s": self.t_bound,
            "useful_flops_frac": self.useful_flops_frac,
            "mfu_bound": self.mfu_bound,
            "hlo_flops_per_dev": self.hlo_flops,
            "hlo_bytes_per_dev": self.hlo_bytes,
            "coll_bytes_per_dev": self.collective_bytes,
            "peak_mem_bytes_per_dev": self.peak_memory_bytes,
            "bytes_detail": self.bytes_detail,
        }


def roofline_report(*, arch: str, shape: str, mesh_name: str, chips: int,
                    hlo_text: str, model_flops_total: float,
                    device_kind: str,
                    peak_memory_bytes: float = 0.0,
                    arch_cfg=None, shape_cfg=None,
                    n_micro: int = 1) -> RooflineReport:
    """Assemble the report from the trip-count-aware HLO walk
    (``analysis.hlo_cost``) over the compiled module text.

    Notes:
      - ``compiled.cost_analysis()`` counts each ``while`` body once —
        meaningless for scan-structured programs — so the roofline terms
        come from our own analyzer (validated against XLA's numbers on
        loop-free programs).
      - when ``arch_cfg``/``shape_cfg`` are given, the memory term is
        *kernel-adjusted* (``analysis.attn_adjust``): the chunked-twin's
        HBM-materialized score blocks are swapped for the Pallas
        kernels' true DMA traffic.  Both raw and adjusted numbers are
        kept.
    """
    from repro.analysis import hlo_cost
    c = hlo_cost.analyze(hlo_text)
    bytes_final = c.hbm_bytes
    adj_detail: Dict[str, float] = {}
    if arch_cfg is not None and shape_cfg is not None:
        from repro.analysis import attn_adjust
        adj_detail = attn_adjust.adjust(c.hbm_bytes, c.by_shape, arch_cfg,
                                        shape_cfg, n_micro, chips)
        bytes_final = adj_detail["bytes_adjusted"]
    return RooflineReport(
        arch=arch, shape=shape, mesh=mesh_name, chips=chips,
        hlo_flops=c.flops, hlo_bytes=bytes_final,
        collective_bytes=c.collective_bytes,
        collective_detail={k: int(v) for k, v in c.collectives.items()},
        model_flops_total=model_flops_total,
        peak_memory_bytes=peak_memory_bytes,
        device_kind=device_kind,
        bytes_detail=adj_detail)
