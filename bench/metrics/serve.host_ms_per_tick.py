"""Host time of the engine per frontier tick: the self time of its
planning, stacking, admission, read-back and retirement spans
(``cb.*`` in the program's tracer) over the ticks of the span phase
of a traced run.  The tracer syncs with the device inside those spans
and slows the engine until a queue builds: the number is of that
regime, device waits included."""

import readers

SPANS = ("cb.plan", "cb.stack", "cb.admit", "cb.readback", "cb.retire")


def read(rec):
    if "spans" not in rec or not rec.get("span_ticks"):
        return None
    own = readers.self_times(rec["spans"])
    return 1e3 * sum(own.get(n, 0.0) for n in SPANS) / rec["span_ticks"]
