"""Shared arithmetic of the per-layer metric readers in ``metrics/``."""

from __future__ import annotations

import costs
import peaks as peaks_mod
import trace_reduce

#: Operation names the chip's trace gives the fused megastep kernels
#: (Pallas names a kernel after its kernel function).
KERNELS = {"fwd": "_megastep_kernel", "bwd": "_bwd_megastep_kernel"}


def idle_share(rec: dict):
    t = rec.get("trace")
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def roofline(rec: dict, which: str):
    """Summed least time of a megastep kernel's launches over the
    traced steps, over its summed device time, in percent."""
    t = rec.get("trace")
    if not t or "step_levels" not in rec:
        return None
    hit = trace_reduce.kernel_seconds(t, KERNELS[which])
    if hit is None or hit[1] <= 0:
        return None
    kernel = costs.fwd_kernel if which == "fwd" else costs.bwd_kernel
    least, _bound = costs.kernel_least_seconds(
        kernel, rec["kind"], rec["hidden"], rec["step_levels"],
        peaks_mod.peaks_for(rec["device_kind"]))
    return 100.0 * least / hit[1]


def self_times(spans) -> dict:
    """Per span name, summed self time in seconds: a span's duration
    less the part its directly nested spans (same thread) cover."""
    out, by_tid = {}, {}
    for s in spans:
        if s.ph == "X":
            by_tid.setdefault(s.tid, []).append(s)
    for group in by_tid.values():
        group.sort(key=lambda s: (s.ts, -s.dur))
        stack = []                      # [span, covered_ns]
        for s in group:
            while stack and stack[-1][0].ts + stack[-1][0].dur <= s.ts:
                top, cov = stack.pop()
                out[top.name] = out.get(top.name, 0.0) + (top.dur - cov) / 1e9
            if stack:
                stack[-1][1] += s.dur
            stack.append([s, 0])
        while stack:
            top, cov = stack.pop()
            out[top.name] = out.get(top.name, 0.0) + (top.dur - cov) / 1e9
    return out
