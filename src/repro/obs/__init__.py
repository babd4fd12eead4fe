"""repro.obs — observability for the serving stack.

Three pieces (see ``docs/observability.md``):

* :mod:`~repro.obs.histogram` — :class:`LogHistogram`, the bounded-memory
  mergeable sketch behind every latency/convergence distribution.
* :mod:`~repro.obs.registry` — :class:`MetricsRegistry` of typed counters,
  gauges, and histograms with JSON + Prometheus exporters.
* :mod:`~repro.obs.trace` — :class:`TraceRecorder`, request-scoped span
  trees exported as Chrome-trace/Perfetto JSON, plus the
  :func:`~repro.obs.jaxbridge.device_annotation` bridge to
  ``jax.profiler``.

:class:`Observability` bundles all three for threading through
``AnnIndex.serve(..., obs=...)`` / ``serve_async(..., obs=...)``.  The
shared :data:`NULL_OBS` singleton is the default: every probe point
degrades to a constant-time no-op, so an uninstrumented engine pays
nothing.
"""
from __future__ import annotations

from typing import Optional

from .histogram import LogHistogram
from .jaxbridge import device_annotation
from .registry import Counter, Gauge, Histogram, MetricsRegistry
from .trace import NULL_TRACER, SpanHandle, TraceRecorder

__all__ = [
    "LogHistogram",
    "MetricsRegistry", "Counter", "Gauge", "Histogram",
    "TraceRecorder", "SpanHandle", "NULL_TRACER",
    "device_annotation",
    "Observability", "NULL_OBS",
]


class Observability:
    """Tracer + metrics registry + profiler flag, as one handle.

    * ``tracing`` — record span trees (:class:`TraceRecorder`); off means
      the shared :data:`NULL_TRACER` (no-ops, no allocation).
    * ``metrics`` — write convergence/serving histograms into
      ``registry``.  The engines guard every registry write on this flag,
      which is what the zero-overhead test pins down.
    * ``profile`` — additionally wrap device dispatches in
      ``jax.profiler.TraceAnnotation`` so host spans line up with device
      timelines under ``jax.profiler.trace()``.
    """

    __slots__ = ("tracer", "registry", "metrics", "profile")

    def __init__(self, *, tracing: bool = True, metrics: bool = True,
                 profile: bool = False, max_trace_events: int = 200_000,
                 registry: Optional[MetricsRegistry] = None):
        self.tracer = (TraceRecorder(enabled=True,
                                     max_events=max_trace_events)
                       if tracing else NULL_TRACER)
        self.registry = registry if registry is not None else MetricsRegistry()
        self.metrics = bool(metrics)
        self.profile = bool(profile)

    @property
    def enabled(self) -> bool:
        return self.tracer.enabled or self.metrics

    def write_trace(self, path: str) -> None:
        """Dump the Chrome-trace JSON collected so far to ``path``."""
        self.tracer.write(path)


#: Shared all-off bundle — the default ``obs`` everywhere.
NULL_OBS = Observability(tracing=False, metrics=False, profile=False)
