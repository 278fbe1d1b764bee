"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

Copied from the program's ``analysis/roofline.py`` so that no change to
the program can move the yardstick.  Source: Google Cloud
documentation, "TPU v5e": 197 TFLOP/s in bf16, 393 TOP/s in int8,
16 GB of HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect.
JAX reports a v5e chip as "TPU v5 lite".  No float32 peak is
published; the bf16 peak is the one every share here is taken of.
A device kind that is not in the table is an error, never a default.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks_for(kind: str) -> dict:
    try:
        return PEAKS[kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind {kind!r}; "
                         f"add them to bench/peaks.py with their source"
                         ) from None
