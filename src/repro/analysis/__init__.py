"""Analysis substrate: HLO collective accounting + the 3-term roofline."""

from repro.analysis.hlo import (collective_bytes, count_ops, parse_shape_bytes)
from repro.analysis.roofline import (HW, PEAKS, RooflineReport,
                                     model_flops, peaks_for, roofline_report)

__all__ = ["collective_bytes", "count_ops", "parse_shape_bytes", "HW",
           "PEAKS", "RooflineReport", "peaks_for", "model_flops", "roofline_report"]
