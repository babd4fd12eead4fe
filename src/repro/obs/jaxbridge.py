"""Opt-in bridge from host spans to the JAX device profiler.

When a ``jax.profiler.trace()`` capture is running, host-side Python has
no natural representation on the device timeline.  ``TraceAnnotation``
fixes that: any code executed under one shows up as a named slice in the
profiler's host rows, letting you line up "the coalescer dispatched bucket
8 here" with the XLA ops it launched.

:func:`device_annotation` is the single entry point.  It is a no-op
(shared ``nullcontext``) unless explicitly enabled, so the serving hot
path never touches the profiler machinery by default:

    with device_annotation("ann_dispatch/bucket8", enabled=obs.profile):
        out = compiled(queries)
"""
from __future__ import annotations

from contextlib import nullcontext

from jax.profiler import TraceAnnotation

__all__ = ["device_annotation"]


def device_annotation(name: str, enabled: bool = False):
    """Context manager: ``jax.profiler.TraceAnnotation(name)`` when
    ``enabled``, else a no-op."""
    if not enabled:
        return nullcontext()
    return TraceAnnotation(name)
