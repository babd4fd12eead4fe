"""Batch-deduplicating gather + distance Pallas backends.

PR 5 made every global step ONE (B, C) distance launch, but a hot vertex
sitting on several queries' frontiers is still gathered once PER LANE that
expands it.  NDSEARCH's observation (PAPERS.md) is that the gather — not
the reduction — bounds expansion throughput, so the right unit of work is
the UNIQUE row set of the whole batch step:

  1. **sort** — stable-sort the flattened (B·C,) candidate ids (padding
     folded onto the ``n_nodes`` sentinel) so equal ids sit next to each
     other; ``order`` remembers which lane each sorted slot came from.
     Static shapes — the pass jits cleanly inside the traversal loop.
  2. **gather+reduce** — a scalar-prefetch Pallas kernel (the ``rowgather``
     idiom: prefetched ids drive the row-tiled table's BlockSpec
     index_map) walks the B·C (row, lane) pairs in sorted order.  The
     pipeline skips the HBM fetch when a step's block index repeats, so
     each distinct row crosses HBM→VMEM once; the pair's distance is
     written straight to its lane's output slot (SMEM scatter).

Row reductions use the same f32 op order as ``ref``/``rowgather``, and
every (row, query) pair is still reduced exactly once, so results are
BIT-IDENTICAL to the non-dedup backends — the sort only changes how many
times a row crosses the memory hierarchy.  The counters
``SearchStats.uniq_comps`` / ``batch_dup_comps`` (first-toucher
attribution, ``core.metrics.batch_unique_counts``) measure exactly the
gather traffic this backend saves, as long as the step fits one launch
(``kernels.platform.MAX_PAIRS`` pairs; larger batches dedup per launch).

``dedup_gather_int8`` composes with ``repro.quant``: the unique rows are
gathered from the row-tiled int8 codes (per-vector scales, int32-accumulated
integer dot, one f32 rescale — bit-identical to ``ref_int8``), so the 4x
payload shrink compounds with the dedup factor.

Both register with ``kernels.registry`` — selecting them is purely
``SearchConfig(dist_backend="dedup_gather")``; no search code changes.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.l2dist import pair_dist, query_tiles
from repro.kernels.platform import pallas_call, split_batch
from repro.kernels.registry import (kernel_table, pad_ids_to_tile,
                                     register_backend)
from repro.quant.kernels import (int8_pair_dist, int8_query_operands,
                                 require_codes)

# unique-buffer tile of :func:`unique_ids_inverse`
TILE = 8


def sort_candidates(ids: jax.Array, n_nodes: int
                    ) -> Tuple[jax.Array, jax.Array]:
    """Stable sort of a (B, C) candidate grid's flattened ids, every
    padding id folded onto the sentinel ``n_nodes`` first.  Returns
    (``sorted_ids`` (B·C,), ``order`` (B·C,)) with
    ``sorted_ids == flat[order]``: equal ids end up adjacent, which is what
    lets a gather pipeline walking this order fetch each distinct row
    once."""
    flat = jnp.where(ids < n_nodes, ids, jnp.int32(n_nodes)).astype(
        jnp.int32).reshape(-1)
    order = jnp.argsort(flat, stable=True).astype(jnp.int32)
    return flat[order], order


def unique_ids_inverse(
    ids: jax.Array, n_nodes: int, tile: int = TILE,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Static-shape sort/unique pass over a (B, C) candidate grid.

    Every id >= ``n_nodes`` (padding) is folded onto the single sentinel
    ``n_nodes`` before deduplication.  Returns:

    * ``uniq`` (T,) int32 — the distinct ids packed at the front, the rest
      of the buffer filled with the sentinel; T = B·C rounded up to
      ``tile`` (see :func:`registry.pad_ids_to_tile`).
    * ``inv`` (B, C) int32 — ``uniq[inv[b, c]]`` folds back to
      ``min(ids[b, c], n_nodes)``; the scatter map of step 3.
    * ``n_uniq`` () int32 — how many REAL (non-sentinel) distinct ids the
      batch step touches: the rows a dedup backend actually gathers.
    """
    bsz, c = ids.shape
    t = bsz * c
    sent = jnp.int32(n_nodes)
    sorted_ids, order = sort_candidates(ids, n_nodes)
    first = jnp.concatenate(
        [jnp.ones((1,), bool), sorted_ids[1:] != sorted_ids[:-1]])
    rank = jnp.cumsum(first.astype(jnp.int32)) - 1         # uniq idx / elt
    uniq = jnp.full((t,), sent, jnp.int32).at[rank].set(sorted_ids)
    inv = jnp.zeros((t,), jnp.int32).at[order].set(rank).reshape(bsz, c)
    n_uniq = jnp.sum(first & (sorted_ids < n_nodes)).astype(jnp.int32)
    return pad_ids_to_tile(uniq, tile, n_nodes), inv, n_uniq


# ---------------------------------------------------------------------------
# f32 table kernel
# ---------------------------------------------------------------------------

def _dedup_kernel(sids_ref, order_ref, row_ref, q_ref, out_ref, *,
                  n_nodes: int, c: int, d: int, metric: str):
    # identical per-pair math to l2dist._rowgather_kernel, so results are
    # bit-identical to the non-dedup backends; only the visiting order
    # differs
    t = pl.program_id(0)
    lane = order_ref[t]
    b = lane // c
    dist = pair_dist(row_ref[...].astype(jnp.float32), q_ref[b], metric, d)
    out_ref[b, lane % c] = jnp.where(sids_ref[t] < n_nodes, dist,
                                     jnp.float32(jnp.inf))


@split_batch
def dedupdist(
    tiles: jax.Array, ids: jax.Array, queries: jax.Array,
    *, metric: str = "l2",
) -> jax.Array:
    """(N, k, 128) row-tiled table, (B, C) ids, (B, d) queries -> (B, C)
    f32 distances with each DISTINCT candidate row gathered once for the
    whole batch.

    The grid walks the B·C (row, query) pairs in sorted-id order
    (:func:`sort_candidates`); the row block's index_map follows that
    order, and the Pallas pipeline skips the HBM fetch whenever a step's
    block index equals the previous one — so a row shared by several lanes
    crosses HBM→VMEM once.  Same contract as
    :func:`l2dist.l2dist_rowgather` and bit-identical to it.
    """
    n, k, lanes = tiles.shape
    bsz, c = ids.shape
    sids, order = sort_candidates(ids, n)
    q = query_tiles(queries, k)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(bsz * c,),
        in_specs=[
            pl.BlockSpec(
                (None, k, lanes), lambda t, sids_ref, order_ref: (
                    jnp.minimum(sids_ref[t], n - 1), 0, 0)),
            pl.BlockSpec((bsz, k, lanes), lambda t, s_ref, o_ref: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
    )
    kernel = functools.partial(_dedup_kernel, n_nodes=n, c=c,
                               d=queries.shape[1], metric=metric)
    return pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bsz, c), jnp.float32),
    )(sids, order, tiles, q)


# ---------------------------------------------------------------------------
# int8 codes kernel (per-vector scales; composes with repro.quant)
# ---------------------------------------------------------------------------

def _dedup_int8_kernel(sids_ref, order_ref, row_ref, scale_ref, qc_ref,
                       qmeta_ref, out_ref, *, n_nodes: int, c: int,
                       metric: str):
    # per-pair math is quant.kernels.int8_pair_dist, shared with
    # rowgather_int8 — bit-identical to ref_int8 / rowgather_int8
    t = pl.program_id(0)
    lane = order_ref[t]
    b = lane // c
    dist = int8_pair_dist(row_ref[...], qc_ref[b], scale_ref[t],
                          qmeta_ref[b, 0], qmeta_ref[b, 1], metric)
    out_ref[b, lane % c] = jnp.where(sids_ref[t] < n_nodes, dist,
                                     jnp.float32(jnp.inf))


@split_batch
def dedupdist_int8(
    code_tiles: jax.Array, scales: jax.Array, ids: jax.Array,
    queries: jax.Array, *, metric: str = "l2",
) -> jax.Array:
    """int8 variant of :func:`dedupdist`: distinct rows gather once from
    the (N, k, 128) row-tiled int8 codes, with (N, 1) per-vector scales, so
    the 4x payload shrink compounds with the dedup factor.  Bit-identical
    to ``ref_int8`` (same int32-accumulate + single-f32-rescale op
    order)."""
    n, k, lanes = code_tiles.shape
    bsz, c = ids.shape
    if scales.shape != (n, 1):
        raise ValueError(
            f"dedupdist_int8 needs per-vector scales of shape ({n}, 1), "
            f"got {scales.shape}; per-dimension scales are served by the "
            f"'ref_int8' backend")
    sids, order = sort_candidates(ids, n)
    qc, qmeta = int8_query_operands(queries, k)
    # the per-candidate scale is one f32: gathered here in sorted order
    # (4 bytes per candidate) and read from SMEM in the kernel
    s_sorted = scales[jnp.minimum(sids, n - 1), 0]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(bsz * c,),
        in_specs=[
            pl.BlockSpec(
                (None, k, lanes), lambda t, sids_ref, order_ref: (
                    jnp.minimum(sids_ref[t], n - 1), 0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((bsz, k, lanes), lambda t, s_ref, o_ref: (0, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
    )
    kernel = functools.partial(_dedup_int8_kernel, n_nodes=n, c=c,
                               metric="ip" if metric in ("ip", "cosine")
                               else "l2")
    return pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bsz, c), jnp.float32),
    )(sids, order, code_tiles, s_sorted, qc, qmeta)


# ---------------------------------------------------------------------------
# registry entries — zero search-code changes
# ---------------------------------------------------------------------------

def make_dedup_dist_fn(metric: str = "l2"):
    """Batch-major dedup DistFn: the step's whole (B, M·R) candidate grid
    is ONE launch that gathers each distinct row once."""
    kmetric = "ip" if metric in ("ip", "cosine") else "l2"

    def dist_fn(graph, active_ids, nbr_ids, queries):
        b, m, r = nbr_ids.shape
        d = dedupdist(kernel_table(graph, "vector_tiles"),
                      nbr_ids.reshape(b, m * r),
                      queries, metric=kmetric)
        return d.reshape(b, m, r)
    return dist_fn


def make_dedup_int8_dist_fn(metric: str = "l2"):
    """Batch-major int8 dedup DistFn ((B, M, R) ids in, (B, M, R) f32 out;
    the batch's distinct code rows are gathered once).  Per-vector scales
    only, like ``rowgather_int8``."""
    def dist_fn(graph, active_ids, nbr_ids, queries):
        code_tiles, scales = require_codes(graph, "int8", tiled=True)
        b, m, r = nbr_ids.shape
        d = dedupdist_int8(code_tiles, scales, nbr_ids.reshape(b, m * r),
                           queries, metric=metric)
        return d.reshape(b, m, r)
    return dist_fn


def _cfg_metric(cfg) -> str:
    return getattr(cfg, "metric", "l2") or "l2"


@register_backend("dedup_gather", tiles="vector_tiles")
def _dedup_backend(cfg):
    return make_dedup_dist_fn(_cfg_metric(cfg))


@register_backend("dedup_gather_int8", tiles="code_tiles")
def _dedup_int8_backend(cfg):
    return make_dedup_int8_dist_fn(_cfg_metric(cfg))
