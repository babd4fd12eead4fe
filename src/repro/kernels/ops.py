"""jit'd public wrappers around the Pallas kernels + search integration.

Every kernel lowers through Mosaic when compiled for a TPU and runs in
Pallas interpret mode when compiled for the CPU
(:func:`repro.kernels.platform.pallas_call`); there is nothing to set.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels import bitonic as _bitonic
from repro.kernels import l2dist as _l2


@functools.partial(jax.jit, static_argnames=("impl", "g", "metric"))
def l2dist(
    tiles: jax.Array, ids: jax.Array, queries: jax.Array,
    impl: str = "rowgather", g: int = 8, metric: str = "l2",
) -> jax.Array:
    """Fused gather + distance: (N, k, 128) row-tiled table
    (:func:`repro.kernels.platform.row_tiles`), (B, C) ids, (B, d) queries
    -> (B, C) f32.

    This is the batch-major hot-path launch: the traversal engine calls it
    ONCE per global step with the whole query batch's flattened candidate
    grid (B queries × C = M·R candidates each).

    ``metric`` selects the reduction: "l2" (squared L2) or "ip"/"cosine"
    (negative inner product; cosine callers pre-normalize, so the kernels
    treat it as ip).  Smaller = closer for every metric.

    ``g`` is the DMA tile size ("dma" impl only; requires C % g == 0 —
    ``registry.pad_ids_to_tile`` handles ragged candidate counts).
    """
    kmetric = "ip" if metric in ("ip", "cosine") else "l2"
    if impl == "rowgather":
        return _l2.l2dist_rowgather(tiles, ids, queries, metric=kmetric)
    if impl == "dma":
        return _l2.l2dist_dma(tiles, ids, queries, g=g, metric=kmetric)
    raise ValueError(impl)


@jax.jit
def sort_pairs(keys, p0, p1):
    """Row-wise (B, n) ascending co-sort by (key, p0); n must be 2**k."""
    return _bitonic.sort_pairs(keys, p0, p1)


@jax.jit
def topl_merge(
    q_dists: jax.Array, q_ids: jax.Array, q_meta: jax.Array,
    c_dists: jax.Array, c_ids: jax.Array,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Frontier merge on the bitonic kernel (B-batched, mirrors queue.insert).

    Queue (B, L) sorted rows + candidates (B, C) -> top-L (dists, ids, meta)
    and per-row update positions.  L + C is padded to the next power of two.
    """
    invalid = jnp.int32(2**31 - 1)
    big = jnp.float32(jnp.inf)
    bsz, qlen = q_ids.shape
    c = c_ids.shape[1]
    n = 1
    while n < qlen + c:
        n *= 2
    pad = n - (qlen + c)

    ids = jnp.concatenate(
        [q_ids, c_ids, jnp.full((bsz, pad), invalid, jnp.int32)], axis=1)
    dists = jnp.concatenate(
        [q_dists, c_dists, jnp.full((bsz, pad), big, jnp.float32)], axis=1)
    is_new = jnp.concatenate(
        [jnp.zeros((bsz, qlen), jnp.int32), jnp.ones((bsz, c), jnp.int32),
         jnp.zeros((bsz, pad), jnp.int32)], axis=1)
    meta = jnp.concatenate(
        [q_meta.astype(jnp.int32), jnp.zeros((bsz, c + pad), jnp.int32)],
        axis=1)
    # pack (meta, is_new) into one payload so the 3-array kernel suffices
    packed = meta * 2 + is_new

    # pass 1: group by (id, is_new) so existing entries precede fresh dups.
    # Split the id into (high 23 bits as an exact f32 key, low 8 bits in the
    # int payload) — exact ordering for ids up to 2^31 without denormal
    # bitcasts; is_new rides in the payload's LSB.
    key_hi = (ids >> 8).astype(jnp.float32)
    p0 = ((ids & 0xFF) << 1) | (packed & 1)
    positions = jnp.broadcast_to(
        jnp.arange(n, dtype=jnp.int32)[None, :], (bsz, n))
    _, _, pos = sort_pairs(key_hi, p0, positions)
    # gather full rows by the returned original positions
    take = jax.vmap(lambda a, p: a[p])
    ids_g = take(ids, pos)
    dists_g = take(dists, pos)
    packed_g = take(packed, pos)
    dup = jnp.concatenate(
        [jnp.zeros((bsz, 1), bool),
         (ids_g[:, 1:] == ids_g[:, :-1]) & (ids_g[:, 1:] != invalid)], axis=1)
    ids_g = jnp.where(dup, invalid, ids_g)
    dists_g = jnp.where(dup, big, dists_g)

    # pass 2: by (dist, id)
    d2, i2, pk2 = sort_pairs(dists_g, ids_g, packed_g)
    rank = jnp.arange(n, dtype=jnp.int32)[None, :]
    surv = (pk2 & 1 == 1) & (i2 != invalid) & (rank < qlen)
    up = jnp.min(jnp.where(surv, rank, qlen), axis=1).astype(jnp.int32)
    return d2[:, :qlen], i2[:, :qlen], (pk2[:, :qlen] >> 1), up
