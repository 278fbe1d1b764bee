"""Runtime launch/HBM profiler.

Promotes the jaxpr launch census that previously lived only in
``tests/test_megastep_bwd.py`` into a runtime surface: given a vertex
function and a packed schedule, :func:`profile_step` traces the forward
and gradient programs, counts the pallas launches inside each
``lax.scan`` body (one scan body = one batching-task level, so the
in-scan count IS launches/level) and outside any scan, and reports the
modeled HBM bytes per step from the roofline model in
``kernels/level_megastep.py`` — emitted as a ``profile.step`` span and
``profile.*`` gauges on the global metrics registry, so the
fused-vs-unfused claim is auditable at runtime, not just in tests.

Heavy imports (jax, the scheduler) happen inside the functions — this
module is importable from anywhere in the obs layer without cycles.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional

from repro.obs import trace
from repro.obs.registry import get_registry

__all__ = ["walk_jaxpr", "launch_census", "LaunchCensus", "profile_step"]


def walk_jaxpr(jx, scans: List[int], outside: List[int]) -> None:
    """Collect (pallas_call count inside each scan body) and the count
    outside any scan, recursing through nested jaxprs.  ``scans`` grows
    one entry per scan encountered; ``outside`` is a 1-element
    accumulator.  A kernel's own body is not walked: its in-kernel
    loops are not launches."""
    for eqn in jx.eqns:
        if eqn.primitive.name == "pallas_call":
            outside[0] += 1
            continue
        if eqn.primitive.name == "scan":
            body = eqn.params["jaxpr"].jaxpr
            inner_scans, inner = [], [0]
            walk_jaxpr(body, inner_scans, inner)
            scans.append(inner[0])
            scans.extend(inner_scans)
            continue
        for v in eqn.params.values():
            sub = getattr(v, "jaxpr", None)
            if sub is not None and hasattr(sub, "eqns"):
                walk_jaxpr(sub, scans, outside)
            elif hasattr(v, "eqns"):
                walk_jaxpr(v, scans, outside)


@dataclasses.dataclass
class LaunchCensus:
    """Pallas launches of one traced program: per-scan-body counts (=
    launches per level for the level scans) and the count outside any
    scan."""

    scan_launches: List[int]
    outside: int

    @property
    def total_per_sweep(self) -> int:
        """Launches per full sweep, counting each scan body once."""
        return sum(self.scan_launches) + self.outside

    @property
    def per_level(self) -> int:
        """Max launches in any single scan body (the fused contract is
        exactly 1 in both sweep directions; op-by-op is 0)."""
        return max(self.scan_launches, default=0)


def launch_census(fn, *args, **kwargs) -> LaunchCensus:
    """Trace ``fn(*args, **kwargs)`` and census its pallas launches."""
    import jax
    jaxpr = jax.make_jaxpr(fn)(*args, **kwargs)
    scans: List[int] = []
    outside = [0]
    walk_jaxpr(jaxpr.jaxpr, scans, outside)
    return LaunchCensus(scans, outside[0])


def profile_step(fn, params, sched, ext, *, dev=None,
                 fusion_mode: str = "auto",
                 registry=None) -> Dict[str, Any]:
    """Profile one training step's program structure and memory model.

    Traces the forward (``execute_lazy``) and the gradient of a
    sum-of-roots loss, censusing pallas launches per level in each, and
    — for GateSpec-declaring cells — reports the modeled HBM bytes per
    step (fused and unfused, forward and backward) from the
    ``level_traffic_bytes`` roofline model.  Emits everything as
    ``profile.*`` gauges on ``registry`` (default: the global one) and
    brackets the trace work in a ``profile.step`` span.

    ``sched`` is the host :class:`~repro.core.structure.LevelSchedule`;
    ``dev`` its device twin (``sched.to_device()`` when omitted).
    """
    import jax
    import jax.numpy as jnp
    from repro.core.scheduler import (execute_lazy, readout_roots,
                                      resolve_fusion)

    reg = registry if registry is not None else get_registry()
    if dev is None:
        dev = sched.to_device()

    with trace.span("profile.step", fusion_mode=fusion_mode):
        spec = resolve_fusion(fn, fusion_mode, sched_arity=sched.A)
        fused = spec is not None

        def loss(p, e):
            buf = execute_lazy(fn, p, e, dev, fusion_mode=fusion_mode)
            return jnp.sum(readout_roots(buf, dev) ** 2)

        fwd = launch_census(
            lambda p, e: execute_lazy(fn, p, e, dev,
                                      fusion_mode=fusion_mode),
            params, ext)
        grad = launch_census(jax.grad(loss, argnums=(0, 1)), params, ext)

        out: Dict[str, Any] = {
            "fusion_mode": fusion_mode,
            "fused": fused,
            "levels": int(sched.T),
            "slots_per_level": int(sched.M),
            "arity": int(sched.A),
            "occupancy": float(sched.occupancy),
            "fwd_launches_per_level": fwd.per_level,
            "fwd_launches_outside": fwd.outside,
            "fwd_scan_launches": list(fwd.scan_launches),
            "grad_launches_per_level": grad.per_level,
            "grad_launches_outside": grad.outside,
            "grad_scan_launches": list(grad.scan_launches),
        }
        if spec is not None:
            out.update(_hbm_model(spec, sched))
        for k, v in out.items():
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                reg.set_gauge(f"profile.{k}", float(v))
    return out


def _hbm_model(spec, sched) -> Dict[str, Any]:
    """Modeled whole-step HBM bytes (per the roofline accounting in
    ``kernels/level_megastep.py``: one batching task per level)."""
    from repro.kernels.level_megastep import (level_bwd_traffic_bytes,
                                              level_traffic_bytes)
    T, M, A = sched.T, sched.M, sched.A
    S, H = spec.state_dim, spec.hidden
    out: Dict[str, Any] = {"gate_kind": spec.kind}
    for direction, per_level in (("fwd", level_traffic_bytes),
                                 ("bwd", level_bwd_traffic_bytes)):
        fused_b = T * per_level(spec.kind, M, A, S, H, fused=True)
        unfused_b = T * per_level(spec.kind, M, A, S, H, fused=False)
        out[f"hbm_{direction}_fused_bytes"] = fused_b
        out[f"hbm_{direction}_unfused_bytes"] = unfused_b
        out[f"hbm_{direction}_reduction"] = unfused_b / max(fused_b, 1)
    return out
