"""The window's surroundings: the traced part of a ``--trace 1`` window,
and the heap frozen before every window.

The profiler runs with its Python tracer off (it slows the host several
times over, and serving is host work).  In training the program's own
span tracer (``repro.obs.trace``) runs beside it; serving takes the two
one after the other (see ``loops/serve.py``).  A ``bench.window``
annotation marks the traced part; its start, read on both clocks, puts
the program's spans (``time.perf_counter_ns``) on the profiler's clock,
so that an idle gap of the device can be attributed to the program's
span that the main thread was in.
"""

from __future__ import annotations

import gc
import shutil
import threading
import time

import jax

import trace_reduce


def freeze_heap() -> None:
    """Move everything set-up made (the corpus or the window's requests,
    built ahead; the program's caches) out of reach of Python's cyclic
    collector for the window, as a long-running job does once it has
    loaded its data: without it, each full collection walks millions of
    set-up objects and stalls the host at random.  Objects the window
    makes are collected as usual; the loop unfreezes after it."""
    gc.collect()
    gc.freeze()


class SpanWindow:
    """The program's own span tracer, installed until :meth:`stop`.
    While it is installed the program syncs with the device inside
    some spans (``repro.obs.trace.maybe_block``), so that a span times
    execution rather than dispatch: what runs under it runs slower."""

    def __init__(self):
        from repro.obs import trace as obs
        self.obs = obs
        self.tracer = obs.Tracer(max_spans=1_000_000)
        obs.set_tracer(self.tracer)
        self.stopped = False

    def stop(self) -> None:
        if not self.stopped:
            self.obs.set_tracer(None)
            self.stopped = True

    def spans(self):
        self.stop()
        return self.tracer.snapshot()


class TracedWindow:
    """A profiler trace until :meth:`stop`, with the program's span
    tracer beside it where ``spans`` is true."""

    def __init__(self, trace_dir: str, spans: bool = True):
        self.dir = trace_dir
        self.program = SpanWindow() if spans else None
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        self.window = jax.profiler.TraceAnnotation(trace_reduce.WINDOW)
        self.window.__enter__()
        self.perf_ns = time.perf_counter_ns()
        self.seconds = None

    def stop(self) -> None:
        if self.seconds is not None:
            return
        self.window.__exit__(None, None, None)
        self.seconds = (time.perf_counter_ns() - self.perf_ns) / 1e9
        jax.profiler.stop_trace()
        if self.program:
            self.program.stop()

    def record(self) -> dict:
        """The reduced trace, and the program's spans where they were
        taken."""
        self.stop()
        out = {"traced_s": self.seconds}
        host = []
        if self.program:
            out["spans"] = self.program.spans()
            main = threading.main_thread().ident
            host = [(s.name, s.ts, s.ts + s.dur) for s in out["spans"]
                    if s.ph == "X" and s.tid == main]
        out["trace"] = trace_reduce.reduce_dir(
            self.dir, host_spans=host, window_perf_ns=self.perf_ns)
        shutil.rmtree(self.dir, ignore_errors=True)
        return out
