"""Bytes and operations that the Mamba-2 one-token update (the
``ssm_step`` kernel) requires, from the configuration's widths alone.

Per live lane and Mamba layer: the chain's state row read (the
``[H, P, N]`` SSM state and the conv's ``K - 1`` raw ``xBC`` rows its
predecessor left; not for a chain's first token, which reads none) and
written back, the token's raw ``xBC`` (``d_inner + 2N``) and ``dt`` (one a
head) read, and ``y`` (``d_inner``) written, float32.  Padding of the
arena's layout is not counted, nor the conv weights (read once a
launch, 70 KB against megabytes of state).  Operations: per state
element the decay, the input term and the output's product (two each),
per ``xBC`` element the conv's ``K`` multiply-adds; the update is bound
by bandwidth by a factor of hundreds.
"""

from __future__ import annotations

F32 = 4


def row_floats(ssm: dict) -> int:
    """Floats of one layer's share of a state row."""
    d_inner = ssm["heads"] * ssm["head_dim"]
    conv = d_inner + 2 * ssm["d_state"]
    return d_inner * ssm["d_state"] + (ssm["d_conv"] - 1) * conv


def lane_bytes(ssm: dict, reads_row: bool) -> float:
    d_inner = ssm["heads"] * ssm["head_dim"]
    conv = d_inner + 2 * ssm["d_state"]
    moved = (row_floats(ssm) * (2 if reads_row else 1)
             + conv + ssm["heads"] + d_inner)
    return F32 * moved


def lane_flops(ssm: dict) -> float:
    d_inner = ssm["heads"] * ssm["head_dim"]
    conv = d_inner + 2 * ssm["d_state"]
    return 6.0 * d_inner * ssm["d_state"] + 2.0 * ssm["d_conv"] * conv


def ssm_step_least_seconds(ssm: dict, launches: float, lanes: int,
                           starts: int, ticks: int, peaks: dict) -> float:
    """Least time of ``launches`` kernel launches (one a tick and Mamba
    layer) at the lanes a tick the engine fired: ``lanes`` live lanes,
    ``starts`` of them chains' first tokens, over ``ticks`` ticks."""
    per_tick = ((lanes - starts) * lane_bytes(ssm, True)
                + starts * lane_bytes(ssm, False)) / ticks
    flops = lanes * lane_flops(ssm) / ticks
    return launches * max(per_tick / peaks["hbm_bytes_per_s"],
                          flops / peaks["flops"])
