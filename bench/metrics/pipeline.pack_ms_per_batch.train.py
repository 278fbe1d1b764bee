"""Producer-side work per packed batch: the summed duration of the
background packer's ``prefetch.source`` (drawing the next item, where a
composed epoch is built) and ``prefetch.pack`` spans (threads other
than the main one) over the ``prefetch.pack`` spans ended in the traced
window.  ``None`` where the program names no ``prefetch.source``."""

import threading

SPANS = ("prefetch.source", "prefetch.pack")


def read(rec):
    spans = rec.get("spans")
    if not spans:
        return None
    main = threading.main_thread().ident
    mine = [s for s in spans if s.name in SPANS and s.tid != main]
    packs = sum(1 for s in mine if s.name == "prefetch.pack")
    if not packs or not any(s.name == "prefetch.source" for s in mine):
        return None
    return sum(s.dur for s in mine) / 1e6 / packs
