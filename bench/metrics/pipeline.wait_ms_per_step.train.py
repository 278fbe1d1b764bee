"""The trainer's wait for its next packed batch per training step: the
summed duration of the consumer's ``prefetch.wait`` spans (main thread;
each is the take of one batch from the pipeline's background packer)
over the ``train.step`` spans of the traced window.  ``None`` where the
program names no such wait."""

import threading


def read(rec):
    spans = rec.get("spans")
    if not spans:
        return None
    main = threading.main_thread().ident
    steps = sum(1 for s in spans if s.name == "train.step")
    waits = [s.dur for s in spans
             if s.name == "prefetch.wait" and s.tid == main]
    if not steps or not waits:
        return None
    return sum(waits) / 1e6 / steps
