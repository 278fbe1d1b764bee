"""The numbers that decide ``correct``: gaps between what the timed path
produced and what the plain reference gives, each with its limit.

Training (per the first three steps the window's own call ran):

  * ``loss_gap``   — worst step's ``|loss - ref| / |ref|``;
  * ``grad_gap``   — the first clipped gradient as the optimizer holds
    it, worst leaf's ``| |g| - |g_ref| |`` over the larger of
    ``|g_ref|`` and the median leaf's ``|g_ref|``;
  * ``update_gap`` — the same for the parameters' change over the
    three steps, over the leaves whose reference gradient is at least
    a thousandth of the median leaf's (a leaf below that moves under
    Adam by round-off alone).

  * ``batch_shortfall`` — samples missing from those steps' batches
    (beyond an epoch's one short batch) or repeated in them.

Serving, over every request of the window that ended ``ok``:
``root_rms_gap`` — the RMS of the root states' differences over the RMS
of the reference's; ``root_gap`` — the worst request's
``max |state - ref| / max |ref|`` (printed, not compared: see PERF.md).
"""

from __future__ import annotations

import numpy as np


def norm_gap(prog: dict, ref: dict, keep=None) -> float:
    """Worst leaf's gap of norms, against the larger of the leaf's
    reference norm and the median leaf's."""
    keys = sorted(ref) if keep is None else sorted(keep)
    med = float(np.median([ref[k] for k in sorted(ref)]))
    return max(abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30)
               for k in keys)


def moving_leaves(grad_ref: dict) -> list:
    med = float(np.median(list(grad_ref.values())))
    return [k for k, v in grad_ref.items() if v >= 1e-3 * med]


def train_gaps(prog_losses, prog_grad1: dict, prog_delta: dict,
               ref: dict) -> dict:
    """``prog_*`` and ``ref[...]`` as leaf-norm dicts (losses a list)."""
    losses = [abs(a - b) / abs(b) for a, b in
              zip(prog_losses, ref["losses"])]
    g_ref = ref["grad1"]
    return {"loss_gap": max(losses),
            "grad_gap": norm_gap(prog_grad1, g_ref),
            "update_gap": norm_gap(prog_delta, ref["delta"],
                                   keep=moving_leaves(g_ref))}


def batch_shortfall(step_ids, batch: int, corpus: int) -> int:
    """Samples missing from (or repeated in) the first steps, beyond the
    one short batch an epoch of ``corpus`` samples ends with: an exact
    count, whose limit is 0."""
    ids = np.concatenate(step_ids)
    tail = (batch - corpus % batch) % batch
    short = sum(batch - len(s) for s in step_ids)
    return int(max(0, short - tail) + len(ids) - len(np.unique(ids)))


def root_gap(prog, ref) -> float:
    gaps = [float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))
            for a, b in zip(prog, ref)]
    return max(gaps)


def root_rms_gap(prog, ref) -> float:
    """RMS of the difference over RMS of the reference, over every
    compared root state."""
    d = sum(float(np.sum((np.asarray(a, np.float64) - b) ** 2))
            for a, b in zip(prog, ref))
    r = sum(float(np.sum(np.asarray(b, np.float64) ** 2)) for b in ref)
    return float(np.sqrt(d / max(r, 1e-30)))


def judge(values: dict, limits: dict) -> list:
    """``[(name, value, limit, ok)]`` for every number with a limit."""
    return [(k, float(values[k]), float(limits[k]),
             bool(np.isfinite(values[k]) and values[k] <= limits[k]))
            for k in sorted(limits)]
