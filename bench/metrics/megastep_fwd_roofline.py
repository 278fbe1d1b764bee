"""The forward megastep's share of its roofline over the traced steps:
least time (``costs.fwd_kernel`` on real vertices, v5e peaks) over
the kernel's device time in the trace."""

import readers


def read(rec):
    return readers.roofline(rec, "fwd")
