"""Observability: process-global tracing, the unified metrics registry,
Chrome/Perfetto export, and the runtime launch/HBM profiler.

Import surface::

    from repro.obs import trace                 # span()/instant()/correlate()
    from repro.obs.registry import get_registry
    from repro.obs.export import write_chrome_trace, flamegraph
    from repro.obs.profile import profile_step, launch_census

See ``docs/observability.md`` for the span taxonomy and correlation-id
conventions, and ``REPRO_TRACE=<path>`` for one-command timelines.
"""

from repro.obs.registry import (MetricsRegistry, fresh_registry,
                                get_registry, set_registry)
from repro.obs.trace import (Span, SpanHandle, Tracer, begin, correlate,
                             enabled, end, get_tracer, install_tracer,
                             instant, maybe_install_from_env, set_tracer,
                             span, validate_spans)

__all__ = [
    "MetricsRegistry", "fresh_registry", "get_registry", "set_registry",
    "Span", "SpanHandle", "Tracer", "begin", "correlate", "enabled",
    "end", "get_tracer", "install_tracer", "instant",
    "maybe_install_from_env", "set_tracer", "span", "validate_spans",
]
