"""The whole step's share of the chip's peak: the operations the real
vertices of the traced steps require, forward and backward
(``costs.train_flops``), over the traced window's seconds times the
chips times the published bf16 peak."""

import costs
import peaks


def read(rec):
    t = rec.get("trace")
    if not t or "step_levels" not in rec or t["window_s"] <= 0:
        return None
    flops = sum(costs.train_flops(rec["kind"], rec["input_dim"],
                                  rec["hidden"], c)
                for c in rec["step_levels"])
    peak = peaks.peaks_for(rec["device_kind"])["flops"]
    return 100.0 * flops / (t["window_s"] * rec["chips"] * peak)
