"""The serving engine's own waits on the device per frontier tick: the
self time of ``cb.wait`` (a fused window's ``block_until_ready``),
``cb.project`` (an admission's input projection, a round trip) and
``cb.readback`` (the arena's host copy at retirement) over the ticks of
the span phase of a traced run, the base of ``serve.host_ms_per_tick``.
``None`` where the program names no window wait (``cb.wait``)."""

import readers

SPANS = ("cb.wait", "cb.project", "cb.readback")


def read(rec):
    spans = rec.get("spans")
    if not spans or not rec.get("span_ticks"):
        return None
    own = readers.self_times(spans)
    if "cb.wait" not in own:
        return None
    return 1e3 * sum(own.get(n, 0.0) for n in SPANS) / rec["span_ticks"]
