"""The Mamba-2 one-token kernel's share of its roofline over the
profiled phase of a traced serving run: least time of its launches
(``costs_hybrid``: the state rows read and written, ``xBC``, ``dt`` and
``y`` of the lanes the engine fired, at v5e's 819 GB/s) over the
kernel's device time.  The chip's trace names the launches after the
``pallas_call`` (``%ssm_step.<n>``); with none in the trace the metric
is left out."""

import re

import costs_hybrid
import peaks as peaks_mod

NAME = re.compile(r"^%ssm_step(\.\d+)? = ")


def read(rec):
    t = rec.get("trace")
    if not t or not rec.get("profile_ticks"):
        return None
    hits = [v for k, v in t["ops"].items() if NAME.match(k)]
    launches = sum(c for c, _s in hits)
    seconds = sum(s for _c, s in hits)
    if not launches or seconds <= 0:
        return None
    least = costs_hybrid.ssm_step_least_seconds(
        rec["ssm"], launches, rec["profile_lanes"], rec["profile_starts"],
        rec["profile_ticks"], peaks_mod.peaks_for(rec["device_kind"]))
    return 100.0 * least / seconds
