"""What JAX reports of the device a run measured on."""

from __future__ import annotations

import jax


def info() -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peak_bytes(chips: int) -> int:
    """Peak bytes in use on the fullest of the first ``chips`` devices
    (0 where the backend keeps no count)."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices()[:chips])
