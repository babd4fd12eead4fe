"""Fused gather + distance Pallas TPU kernels.

This is the paper's compute hot spot (Challenges II & IV): the neighbor
expansion gathers ≤ B·M·R feature vectors at data-dependent addresses and
reduces each against its query.  On CPU the paper attacks it with neighbor
grouping + prefetch; the TPU-native form is a *fused dynamic-gather +
distance* kernel so gathered rows never round-trip through HBM.  The
batch-major traversal engine launches each kernel ONCE per global step over
the whole (B, C) candidate grid.

Both kernels read the table in the row-tiled layout
(:func:`repro.kernels.platform.row_tiles`, (N, k, 128)) that the index
stores next to its (N, d) vectors, so one row is one aligned block at any
d.  Queries are tiled the same way (a small in-call pad), the whole query
tile stays resident in VMEM, and distances are written as scalars into an
SMEM (B, C) output:

* ``rowgather`` variant — scalar-prefetched candidate ids drive the
  ``BlockSpec`` index_map of the table, so the pipeline streams exactly the
  needed row blocks HBM→VMEM (double-buffered) while the VPU reduces the
  previous row.  One (query, candidate) pair per grid step.
* ``dma`` variant — the table stays unblocked in HBM (``pl.ANY``); each
  grid step issues G explicit row DMAs into a VMEM scratch tile, then
  reduces the G rows.  G rows per step amortize grid overhead.

Every (row, query) pair is reduced with the same f32 ops as
``ref.dist_ref`` (diff-and-square, or negative dot), so the kernels agree
with the reference; padded ids (>= N) return +inf.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import pallas_call, row_tiles, split_batch


def pair_dist(row: jax.Array, q: jax.Array, metric: str, d: int
              ) -> jax.Array:
    """One (row, query) distance over matching (k, 128) f32 tiles: squared
    L2 for "l2", negative inner product for "ip".  Shared by every f32
    kernel so their per-pair arithmetic is identical.  A single-tile row
    (d <= 128) reduces over its d real lanes only, which keeps the sum's
    association equal to the reference's (d,) reduction."""
    if metric == "ip":
        terms = row * q
    else:
        diff = row - q
        terms = diff * diff
    if row.shape[0] == 1:
        terms = terms[:, :d]
    total = jnp.sum(terms)
    return -total if metric == "ip" else total


def query_tiles(queries: jax.Array, k: int) -> jax.Array:
    """(B, d) queries -> (B, k, 128) f32, zero-padded like the table."""
    q = row_tiles(queries.astype(jnp.float32))
    assert q.shape[1] == k, (q.shape, k)
    return q


# ---------------------------------------------------------------------------
# Variant 1: scalar-prefetch row gather
# ---------------------------------------------------------------------------

def _rowgather_kernel(ids_ref, row_ref, q_ref, out_ref, *, n_nodes: int,
                      d: int, metric: str):
    b = pl.program_id(0)
    c = pl.program_id(1)
    dist = pair_dist(row_ref[...].astype(jnp.float32), q_ref[b], metric, d)
    out_ref[b, c] = jnp.where(ids_ref[b, c] < n_nodes, dist,
                              jnp.float32(jnp.inf))


@split_batch
def l2dist_rowgather(
    tiles: jax.Array, ids: jax.Array, queries: jax.Array,
    *, metric: str = "l2",
) -> jax.Array:
    """(N, k, 128) row-tiled table, (B, C) ids, (B, d) queries -> (B, C)
    f32 distances.

    ``metric="l2"`` -> squared L2; ``"ip"`` -> negative inner product
    (smaller = closer either way; padded ids >= N report +inf).
    """
    n, k, lanes = tiles.shape
    bsz, c = ids.shape
    q = query_tiles(queries, k)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bsz, c),
        in_specs=[
            # one gathered table row per grid step, addressed by the
            # prefetched candidate id (clamped; padding masked in-kernel)
            pl.BlockSpec(
                (None, k, lanes), lambda b, cc, ids_ref: (jnp.minimum(
                    ids_ref[b, cc], n - 1), 0, 0)),
            pl.BlockSpec((bsz, k, lanes), lambda b, cc, ids_ref: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
    )
    kernel = functools.partial(_rowgather_kernel, n_nodes=n,
                               d=queries.shape[1], metric=metric)
    return pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bsz, c), jnp.float32),
    )(ids, tiles, q)


# ---------------------------------------------------------------------------
# Variant 2: explicit-DMA tile gather
# ---------------------------------------------------------------------------

def _dma_kernel(ids_ref, table_ref, q_ref, out_ref, rows, sem,
                *, n_nodes: int, g: int, d: int, metric: str):
    b = pl.program_id(0)
    cb = pl.program_id(1)
    # issue G row DMAs HBM->VMEM (Mosaic overlaps them; interpret mode runs
    # them synchronously), then wait for all of them
    copies = [
        pltpu.make_async_copy(
            table_ref.at[jnp.minimum(ids_ref[b, cb * g + i], n_nodes - 1)],
            rows.at[i], sem)
        for i in range(g)]
    for cp in copies:
        cp.start()
    for cp in copies:
        cp.wait()
    q = q_ref[b]
    for i in range(g):
        c = cb * g + i
        dist = pair_dist(rows[i].astype(jnp.float32), q, metric, d)
        out_ref[b, c] = jnp.where(ids_ref[b, c] < n_nodes, dist,
                                  jnp.float32(jnp.inf))


@split_batch
def l2dist_dma(
    tiles: jax.Array, ids: jax.Array, queries: jax.Array,
    *, g: int = 8, metric: str = "l2",
) -> jax.Array:
    """DMA-tile variant; requires C % g == 0 (pad ids with N to align).
    Same contract as :func:`l2dist_rowgather`."""
    n, k, lanes = tiles.shape
    bsz, c = ids.shape
    assert c % g == 0, f"candidate count {c} not divisible by tile {g}"
    q = query_tiles(queries, k)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bsz, c // g),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),   # table stays in HBM
            pl.BlockSpec((bsz, k, lanes), lambda b, cb, ids_ref: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
        scratch_shapes=[
            pltpu.VMEM((g, k, lanes), tiles.dtype),
            pltpu.SemaphoreType.DMA,
        ],
    )
    kernel = functools.partial(_dma_kernel, n_nodes=n, g=g,
                               d=queries.shape[1], metric=metric)
    return pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bsz, c), jnp.float32),
    )(ids, tiles, q)
