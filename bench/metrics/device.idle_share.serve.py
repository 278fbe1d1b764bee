"""Share of the profiled phase after a traced serving window in which
no operation ran on the device; the program's span tracer is off
then."""

import readers


def read(rec):
    return readers.idle_share(rec)
