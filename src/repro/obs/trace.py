"""Process-global tracing: nested spans, correlation ids, near-zero
cost when disabled.

The paper's headline ablations decompose wall-clock into graph
construction/preprocessing vs. computation vs. batching overhead — this
module is what lets the repo produce that breakdown end-to-end: one
request or one training batch can be followed through compose →
fingerprint → cache/persist → pack → H2D → fused megastep fwd/bwd →
grad-reduce/retire as a single span timeline.

The tracer is deliberately process-global (module-level ``_TRACER`` +
``span()``/``instant()``/``correlate()`` free functions at each
instrumented site) rather than threaded through every constructor —
the same pattern as ``dist/fault.py``'s chaos hook, and for the same
reason: the hot paths it instruments span six modules whose signatures
should not all grow a ``tracer=`` parameter.  With no tracer installed
every site is one global load + ``is None`` check (the overhead test in
``tests/test_obs.py`` holds the disabled cost under 2% of a megastep).

Three span APIs:

  - ``with span("pipeline.pack", graphs=8):`` — the common case; strict
    nesting by construction, exception-safe.
  - ``h = begin("prefetch.pack"); ...; end(h, retries=n)`` — explicit
    begin/end for code where a ``with`` block is awkward (retry loops,
    callbacks); the handle may be ended with extra attributes.
  - ``instant("sched.cache_hit", tier="memory")`` — zero-duration
    events (cache hits, chaos injections, retirements).

Correlation ids ride a thread-local context: ``with correlate(step=n)``
stamps every span/instant begun inside the block (on that thread) with
``step=n``.  Conventions: ``step`` = trainer optimizer step, ``batch``
= pipeline pack sequence number, ``request`` = serving request id.

Spans time host work and never wait on the device themselves.  Where
the program waits (the trainer's non-finite guard, a serving window's
``block_until_ready``, a host read-back), that wait has a span of its
own (``train.sync``, ``cb.wait``, ``cb.project``, ``cb.readback``); a
span around an asynchronous dispatch times the dispatch.  Installing a
tracer therefore adds no device sync: what runs traced runs as it runs
untraced.

One clock with the device trace: while a tracer is installed, every
span also opens a ``jax.profiler.TraceAnnotation`` of the same name on
its thread, so a ``jax.profiler`` capture shows the program's spans,
nested as here, beside the device's operations.  An annotation must
end on the thread that began it: a ``begin`` handle ended on another
thread keeps its span here but gets no annotation (its annotation is
dropped unrecorded).  Outside a capture an annotation records nothing.
``Span.ts`` stays on ``time.perf_counter_ns``.

Activation: ``REPRO_TRACE=<path>`` (or ``=1`` for ``trace.json``) in
the environment installs a tracer at ``import repro`` time and
registers an atexit flush to Chrome trace-event JSON — open the file in
``ui.perfetto.dev``.  Programmatic: ``install_tracer(Tracer())``.
"""

from __future__ import annotations

import collections
import contextlib
import os
import sys
import threading
import time
from typing import Any, Dict, Iterable, List, Optional

__all__ = [
    "Span", "SpanHandle", "Tracer",
    "span", "instant", "correlate", "begin", "end",
    "enabled", "get_tracer", "set_tracer", "install_tracer",
    "maybe_install_from_env", "validate_spans",
]


class Span:
    """One finished trace event.  ``ts``/``dur`` are perf_counter
    nanoseconds (monotonic; only relative placement matters).  ``ph``
    follows the Chrome trace-event phase: "X" complete, "i" instant."""

    __slots__ = ("name", "ts", "dur", "tid", "cid", "attrs", "ph")

    def __init__(self, name: str, ts: int, dur: int, tid: int,
                 cid: Optional[Dict[str, Any]],
                 attrs: Optional[Dict[str, Any]], ph: str = "X"):
        self.name = name
        self.ts = ts
        self.dur = dur
        self.tid = tid
        self.cid = cid
        self.attrs = attrs
        self.ph = ph

    @property
    def dur_ms(self) -> float:
        return self.dur / 1e6

    def as_dict(self) -> Dict[str, Any]:
        d: Dict[str, Any] = {"name": self.name, "ms": round(self.dur_ms, 4)}
        if self.cid:
            d.update(self.cid)
        return d

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return (f"Span({self.name!r}, {self.dur_ms:.3f}ms, "
                f"cid={self.cid}, attrs={self.attrs})")


class SpanHandle:
    """An open span (explicit begin/end API; also the context manager
    ``span()`` returns).  ``end()`` is idempotent — a double end is
    counted, not raised — and may run on a different thread than
    ``begin`` (the span stays on its begin thread's lane, and its
    profiler annotation is dropped: see the module docstring)."""

    __slots__ = ("_tracer", "name", "t0", "tid", "cid", "attrs", "_open",
                 "_ann")

    def __init__(self, tracer: "Tracer", name: str, t0: int, tid: int,
                 cid: Optional[Dict[str, Any]],
                 attrs: Optional[Dict[str, Any]], ann: Any = None):
        self._tracer = tracer
        self.name = name
        self.t0 = t0
        self.tid = tid
        self.cid = cid
        self.attrs = attrs
        self._open = True
        self._ann = ann

    def __enter__(self) -> "SpanHandle":
        return self

    def __exit__(self, *exc) -> bool:
        self.end()
        return False

    def set(self, **attrs: Any) -> None:
        """Attach attributes known only inside the span; they are
        recorded when it ends."""
        self.attrs = {**(self.attrs or {}), **attrs}

    def end(self, **extra: Any) -> None:
        if not self._open:
            self._tracer.double_ends += 1
            return
        self._open = False
        ann, self._ann = self._ann, None
        if ann is not None:
            if threading.get_ident() == self.tid:
                ann.__exit__(None, None, None)
            else:
                self._tracer._strand(ann)
        t1 = time.perf_counter_ns()
        attrs = self.attrs
        if extra:
            attrs = {**(attrs or {}), **extra}
        self._tracer._commit(Span(self.name, self.t0, t1 - self.t0,
                                  self.tid, self.cid, attrs))


class _Tls(threading.local):
    def __init__(self):
        self.cid: Dict[str, Any] = {}


class Tracer:
    """Collects spans into a bounded deque; optionally feeds each span's
    duration into a :class:`~repro.obs.registry.MetricsRegistry`
    histogram (``span.<name>``, milliseconds) so stage timings are
    queryable without walking the raw span list."""

    def __init__(self, path: Optional[str] = None,
                 max_spans: int = 100_000, registry=None):
        self.path = path
        self.max_spans = max_spans
        self.registry = registry
        self.spans: "collections.deque[Span]" = collections.deque(
            maxlen=max_spans)
        self.finished = 0        # spans ever completed (incl. dropped)
        self.open_spans = 0      # begun, not yet ended
        self.double_ends = 0     # idempotent-end violations observed
        self.thread_names: Dict[int, str] = {}
        self._lock = threading.Lock()
        self._tls = _Tls()
        from jax.profiler import TraceAnnotation
        self._annotation = TraceAnnotation
        #: Annotations of handles ended off their begin thread: never
        #: ended (that would record them on the wrong thread), released
        #: once no profiler capture is running, when they record nothing.
        self._stranded: List[Any] = []

    # -- core -------------------------------------------------------------
    def begin(self, name: str, **attrs: Any) -> SpanHandle:
        tid = threading.get_ident()
        if tid not in self.thread_names:
            self.thread_names[tid] = threading.current_thread().name
        cid = self._tls.cid
        t0 = time.perf_counter_ns()
        if self._stranded and not self._annotation.is_enabled():
            with self._lock:
                self._stranded.clear()
        h = SpanHandle(self, name, t0, tid, dict(cid) if cid else None,
                       attrs or None, self._annotation(name))
        with self._lock:
            self.open_spans += 1
        return h

    def _strand(self, ann: Any) -> None:
        with self._lock:
            self._stranded.append(ann)

    def end(self, handle: SpanHandle, **extra: Any) -> None:
        handle.end(**extra)

    def _commit(self, sp: Span) -> None:
        with self._lock:
            self.spans.append(sp)
            self.finished += 1
            self.open_spans -= 1
        if self.registry is not None:
            self.registry.observe(f"span.{sp.name}", sp.dur_ms)

    def span(self, name: str, **attrs: Any) -> SpanHandle:
        """A span as a context manager: begun here, ended on exit."""
        return self.begin(name, **attrs)

    def instant(self, name: str, **attrs: Any) -> None:
        tid = threading.get_ident()
        if tid not in self.thread_names:
            self.thread_names[tid] = threading.current_thread().name
        cid = self._tls.cid
        sp = Span(name, time.perf_counter_ns(), 0, tid,
                  dict(cid) if cid else None, attrs or None, ph="i")
        with self._lock:
            self.spans.append(sp)

    @contextlib.contextmanager
    def correlate(self, **ids: Any):
        tls = self._tls
        prev = tls.cid
        tls.cid = {**prev, **{k: v for k, v in ids.items()
                              if v is not None}}
        try:
            yield
        finally:
            tls.cid = prev

    # -- introspection ----------------------------------------------------
    @property
    def dropped(self) -> int:
        """Finished spans evicted by the bounded deque."""
        return max(0, self.finished - len(self.spans))

    def current_correlation(self) -> Dict[str, Any]:
        return dict(self._tls.cid)

    def summary(self, last_n: int = 10) -> List[Dict[str, Any]]:
        """The last ``last_n`` completed spans, newest last — the
        serving ``health()`` surface."""
        with self._lock:
            tail = list(self.spans)[-last_n:]
        return [sp.as_dict() for sp in tail]

    def snapshot(self) -> List[Span]:
        with self._lock:
            return list(self.spans)


def validate_spans(spans: Iterable[Span]) -> List[str]:
    """Well-formedness check over finished spans: on each thread lane,
    complete spans must STRICTLY nest (two spans are disjoint or one
    contains the other — a partial overlap means a begin/end pairing
    went wrong).  Returns human-readable violations (empty = valid)."""
    errors: List[str] = []
    lanes: Dict[int, List[Span]] = {}
    for sp in spans:
        if sp.ph == "X":
            lanes.setdefault(sp.tid, []).append(sp)
    for tid, sps in lanes.items():
        sps.sort(key=lambda s: (s.ts, -(s.ts + s.dur)))
        stack: List[Span] = []
        for s in sps:
            while stack and s.ts >= stack[-1].ts + stack[-1].dur:
                stack.pop()
            if stack and s.ts + s.dur > stack[-1].ts + stack[-1].dur:
                errors.append(
                    f"tid {tid}: span {s.name!r} overlaps "
                    f"{stack[-1].name!r} without nesting")
            stack.append(s)
    return errors


# ---------------------------------------------------------------------------
# The process-global instance + free-function call sites
# ---------------------------------------------------------------------------

_TRACER: Optional[Tracer] = None


class _NullCtx:
    """Reusable no-op context manager: the disabled-span fast path."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NULL = _NullCtx()


def enabled() -> bool:
    """True when a tracer is installed — guard EXPENSIVE attribute
    computations at call sites (plain attrs may be passed directly)."""
    return _TRACER is not None


def get_tracer() -> Optional[Tracer]:
    return _TRACER


def set_tracer(tracer: Optional[Tracer]) -> Optional[Tracer]:
    """Install (or, with ``None``, remove) the process-global tracer."""
    global _TRACER
    _TRACER = tracer
    return tracer


@contextlib.contextmanager
def install_tracer(tracer: Optional[Tracer]):
    """Install ``tracer`` process-wide for the duration of the block
    (nested installs restore the previous tracer on exit; ``None``
    force-disables tracing inside the block)."""
    global _TRACER
    prev = _TRACER
    _TRACER = tracer
    try:
        yield tracer
    finally:
        _TRACER = prev


def span(name: str, **attrs: Any):
    """A (possibly no-op) context manager timing the block as ``name``."""
    t = _TRACER
    if t is None:
        return _NULL
    return t.span(name, **attrs)


def instant(name: str, **attrs: Any) -> None:
    t = _TRACER
    if t is not None:
        t.instant(name, **attrs)


def correlate(**ids: Any):
    """Stamp spans begun inside the block (this thread) with ``ids``."""
    t = _TRACER
    if t is None:
        return _NULL
    return t.correlate(**ids)


def begin(name: str, **attrs: Any) -> Optional[SpanHandle]:
    """Explicit-begin span; returns ``None`` when tracing is off (pass
    it to :func:`end`, which accepts ``None``)."""
    t = _TRACER
    return None if t is None else t.begin(name, **attrs)


def end(handle: Optional[SpanHandle], **extra: Any) -> None:
    if handle is not None:
        handle.end(**extra)


# ---------------------------------------------------------------------------
# Environment activation (REPRO_TRACE) + atexit flush
# ---------------------------------------------------------------------------

_ATEXIT_ARMED = False


def _flush_at_exit() -> None:  # pragma: no cover - exercised via CLI runs
    t = _TRACER
    if t is None or not t.path:
        return
    try:
        from repro.obs.export import write_chrome_trace
        n = write_chrome_trace(t, t.path)
        print(f"[obs] wrote {n} trace events to {t.path} "
              f"({t.dropped} dropped)", file=sys.stderr)
    except Exception as e:  # noqa: BLE001 - exit path must not raise
        print(f"[obs] trace flush failed: {e}", file=sys.stderr)


def maybe_install_from_env() -> Optional[Tracer]:
    """Install a tracer if ``REPRO_TRACE`` asks for one (idempotent).

    ``REPRO_TRACE=<path>`` writes Chrome trace-event JSON to ``path`` at
    process exit; ``REPRO_TRACE=1`` uses ``trace.json``; unset/``0`` is
    off.  ``REPRO_TRACE_CAP`` bounds retained spans (default 100000 —
    oldest are dropped, and the export notes the count)."""
    global _ATEXIT_ARMED
    if _TRACER is not None:
        return _TRACER
    val = os.environ.get("REPRO_TRACE", "")
    if not val or val == "0":
        return None
    path = "trace.json" if val == "1" else val
    cap = int(os.environ.get("REPRO_TRACE_CAP", "100000"))
    from repro.obs.registry import get_registry
    t = Tracer(path=path, max_spans=cap, registry=get_registry())
    set_tracer(t)
    if not _ATEXIT_ARMED:
        import atexit
        atexit.register(_flush_at_exit)
        _ATEXIT_ARMED = True
    return t
