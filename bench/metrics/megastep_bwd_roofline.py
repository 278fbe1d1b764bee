"""The backward megastep's share of its roofline over the traced steps,
its scatter-add walk included: least time (``costs.bwd_kernel``) over
the kernel's device time in the trace."""

import readers


def read(rec):
    return readers.roofline(rec, "bwd")
