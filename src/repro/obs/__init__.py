"""repro.obs — observability for the serving stack.

Three pieces (see ``docs/observability.md``):

* :mod:`~repro.obs.histogram` — :class:`LogHistogram`, the bounded-memory
  mergeable sketch behind every latency/convergence distribution.
* :mod:`~repro.obs.registry` — :class:`MetricsRegistry` of typed counters,
  gauges, and histograms with JSON + Prometheus exporters.
* :mod:`~repro.obs.trace` — :class:`TraceRecorder`, request-scoped span
  trees exported as Chrome-trace/Perfetto JSON.

:class:`Observability` bundles them for threading through
``AnnIndex.serve(..., obs=...)`` / ``serve_async(..., obs=...)``, and owns
the one span API, :meth:`Observability.span`, whose two sinks are the
``TraceRecorder`` and the ``jax.profiler`` host timeline.  The shared
:data:`NULL_OBS` singleton is the default: every probe point degrades to a
constant-time no-op, so an uninstrumented engine pays nothing.
"""
from __future__ import annotations

import threading
from contextlib import nullcontext
from typing import Optional

from jax.profiler import TraceAnnotation

from .histogram import LogHistogram
from .registry import Counter, Gauge, Histogram, MetricsRegistry
from .trace import _NULL_SPAN, NULL_TRACER, SpanHandle, TraceRecorder

__all__ = [
    "LogHistogram",
    "MetricsRegistry", "Counter", "Gauge", "Histogram",
    "TraceRecorder", "SpanHandle", "NULL_TRACER",
    "Observability", "NULL_OBS",
]

_NULL_CONTEXT = nullcontext()


class _Span:
    """One open span in one or both sinks: a :class:`TraceRecorder` span
    and a ``jax.profiler.TraceAnnotation``."""

    __slots__ = ("_rec", "_ann", "_handle")

    def __init__(self, rec, ann):
        self._rec = rec
        self._ann = ann
        self._handle = None

    def __enter__(self):
        if self._ann is not None:
            self._ann.__enter__()
        if self._rec is not None:
            self._handle = self._rec.__enter__()
        return self

    def __exit__(self, *exc):
        if self._rec is not None:
            self._rec.__exit__(*exc)
        if self._ann is not None:
            self._ann.__exit__(*exc)
        return False

    def add_args(self, **kw) -> None:
        if self._handle is not None:
            self._handle.add_args(**kw)
        if self._ann is not None:
            self._ann.set_metadata(**kw)

    def event(self, name: str, args: Optional[dict] = None) -> None:
        """Instant event inside the span (trace-recorder sink only)."""
        if self._handle is not None:
            self._handle.event(name, args)


class _Tags:
    """Context manager that sets this thread's span tags."""

    __slots__ = ("_local", "_tags", "_saved")

    def __init__(self, local: threading.local, tags: dict):
        self._local = local
        self._tags = tags

    def __enter__(self):
        self._saved = getattr(self._local, "tags", None)
        self._local.tags = dict(self._saved or {}, **self._tags)
        return self

    def __exit__(self, *exc):
        self._local.tags = self._saved
        return False


class Observability:
    """Tracer + metrics registry + profiler flag, as one handle.

    * ``tracing`` — record span trees (:class:`TraceRecorder`); off means
      the shared :data:`NULL_TRACER` (no-ops, no allocation).
    * ``metrics`` — write convergence/serving histograms into
      ``registry``.  The engines guard every registry write on this flag,
      which is what the zero-overhead test pins down.
    * ``profile`` — every span also opens a
      ``jax.profiler.TraceAnnotation`` while a profiler session runs, so
      it lands in the profiler's host plane, on the clock the device
      planes are aligned to.
    """

    __slots__ = ("tracer", "registry", "metrics", "profile", "_local")

    def __init__(self, *, tracing: bool = True, metrics: bool = True,
                 profile: bool = False, max_trace_events: int = 200_000,
                 registry: Optional[MetricsRegistry] = None):
        self.tracer = (TraceRecorder(enabled=True,
                                     max_events=max_trace_events)
                       if tracing else NULL_TRACER)
        self.registry = registry if registry is not None else MetricsRegistry()
        self.metrics = bool(metrics)
        self.profile = bool(profile)
        self._local = threading.local()

    def span(self, name: str, cat: str = "serve", **args):
        """``with obs.span("engine.sync", cat="engine", bucket=8) as sp:``

        A span in each sink that is on: the :class:`TraceRecorder` when
        ``tracing``, a ``jax.profiler.TraceAnnotation(name, **args)`` when
        ``profile`` and a profiler session is running.  With neither, the
        shared null span: no allocation.  ``args`` are merged over this
        thread's :meth:`tags`; ``sp.add_args(...)`` adds more before the
        span closes."""
        tracing = self.tracer.enabled
        profiling = self.profile and TraceAnnotation.is_enabled()
        if not (tracing or profiling):
            return _NULL_SPAN
        tags = getattr(self._local, "tags", None)
        if tags:
            args = dict(tags, **args)
        return _Span(self.tracer.span(name, cat, args) if tracing else None,
                     TraceAnnotation(name, **args) if profiling else None)

    def tags(self, **tags):
        """``with obs.tags(batch=7):`` — args added to every span this
        thread opens inside the block (e.g. one batch's sequence number
        on the engine's spans as well as the coalescer's)."""
        if not (self.tracer.enabled or self.profile):
            return _NULL_CONTEXT
        return _Tags(self._local, tags)

    @property
    def enabled(self) -> bool:
        return self.tracer.enabled or self.metrics

    def write_trace(self, path: str) -> None:
        """Dump the Chrome-trace JSON collected so far to ``path``."""
        self.tracer.write(path)


#: Shared all-off bundle — the default ``obs`` everywhere.
NULL_OBS = Observability(tracing=False, metrics=False, profile=False)
