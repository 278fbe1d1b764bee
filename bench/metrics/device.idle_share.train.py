"""Share of the traced training window in which no operation ran on
the device."""

import readers


def read(rec):
    return readers.idle_share(rec)
