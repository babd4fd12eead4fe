"""Pallas calls whose execution mode follows the lowering platform.

A kernel lowers through Mosaic when the computation is compiled for a TPU
and runs in Pallas interpret mode when it is compiled for the CPU (the
test path).  The choice is made by ``jax.lax.platform_dependent`` at
lowering time, so an ahead-of-time compile against a described TPU
topology takes the Mosaic branch even on a CPU-only host.  There is no
switch to flip and no fallback: a Mosaic compile error on a TPU raises,
and any other platform fails to lower.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
# A gather kernel keeps its scalar-prefetched ids and its (B, C) f32 output
# in SMEM (1 MiB on a v5e): one word per (query, candidate) pair each, and
# up to four words per pair for int8 dedup (sorted ids, order, scales,
# output).  One launch holds at most this many pairs — 512 KiB at four
# words — and larger batches run as several launches along B.
MAX_PAIRS = 32768


def pallas_call(kernel, **kw):
    """``pl.pallas_call(kernel, **kw)``: Mosaic on TPU, interpreted on
    CPU."""
    compiled = pl.pallas_call(kernel, **kw)
    interpreted = pl.pallas_call(kernel, interpret=True, **kw)

    def call(*args):
        return jax.lax.platform_dependent(*args, cpu=interpreted,
                                          tpu=compiled)
    return call


def split_batch(kernel):
    """Wrap ``kernel(*tables, ids (B, C), queries (B, d), **kw) -> (B, C)``
    so that a batch of more than :data:`MAX_PAIRS` pairs runs as
    consecutive launches over slices of B, each within the SMEM budget.
    Rows are independent, so the result is the same; a deduplicating
    kernel dedups within each launch."""
    @functools.wraps(kernel)
    def call(*args, **kw):
        *tables, ids, queries = args
        bsz, c = ids.shape
        step = max(1, MAX_PAIRS // max(c, 1))
        if bsz <= step:
            return kernel(*args, **kw)
        return jnp.concatenate(
            [kernel(*tables, ids[i:i + step], queries[i:i + step], **kw)
             for i in range(0, bsz, step)], axis=0)
    return call


def interprets() -> bool:
    """Whether Pallas kernels run in interpret mode on the default backend
    (the CPU) — the value benchmark rows record."""
    return jax.default_backend() == "cpu"


def row_tiles(x: jax.Array) -> jax.Array:
    """(..., N, d) -> (..., N, ceil(d / 128), 128), zero-padded along d.

    The layout every gather kernel reads: one table row is one
    (k, 128) block whose trailing dims are the array's own, so a single
    row gathers as an aligned DMA at any d, and the device's default
    layout for this shape is the one Mosaic reads (no relayout copy of the
    table per call).  Zero padding leaves l2 and inner-product distances
    unchanged."""
    d = x.shape[-1]
    k = -(-d // LANES)
    if k * LANES != d:
        x = jnp.pad(x, ((0, 0),) * (x.ndim - 1) + ((0, k * LANES - d),))
    return x.reshape(x.shape[:-1] + (k, LANES))
