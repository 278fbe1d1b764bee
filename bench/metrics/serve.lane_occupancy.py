"""Real vertices of the requests answered in the window over the
frontier lanes the window's ticks offered (ticks times
``frontier_width``): plain counters of the untraced window."""


def read(rec):
    if not rec.get("ticks") or "answered_vertices" not in rec:
        return None
    return 100.0 * rec["answered_vertices"] / (rec["ticks"]
                                               * rec["frontier_width"])
