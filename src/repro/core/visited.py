"""Visited-set structures (§4.4, "loosely synchronized visiting map").

Three modes, trading exactness for scale, all with the paper's correctness
model: a false-negative lookup merely causes a duplicate distance computation
(benign — the queue merge dedups); a false *positive* is never produced.

* ``bitmap`` — exact dense bitmap over the N graph vertices, one bit per
  vertex in ceil(N/32) uint32 words: bit ``id & 31`` of word ``id >> 5``
  marks vertex ``id``.  Per walker, per query.  The paper's shared CPU
  bitvector with benign races maps to *per-walker* maps that are OR-merged
  only at global syncs ("eventual consistency"); between syncs walkers may
  duplicate each other's work, which we measure (paper claims <5%).  Bits,
  not bools, because every round passes over whole maps (the scatter, the
  walker broadcast and merge, the loop carry): a bit moves 1/8 of a bool's
  bytes.  A (…, N) bool view exists only where a caller needs one
  (:func:`unpack`: the build's prune pool, through
  ``bfis.search_topm_batch_visited``).
* ``hash``  — fixed 2**bits open-addressed set with bounded linear probing.
  Scales to billion-node graphs (memory independent of N).  Probe losses and
  in-batch scatter races cause duplicate computations only (benign, and the
  direct TPU analog of the paper's fence-free racy updates).
* ``loose`` — no structure at all; dedup happens only against the frontier
  at insert time.  Maximum duplicates, zero memory; useful as an ablation
  (the paper's "no visiting map" extreme).
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class Visited:
    # bitmap: (ceil(N/32),) uint32 words | hash: (2**bits,) int32 keys
    table: jax.Array
    mode_bitmap: bool = dataclasses.field(metadata=dict(static=True))
    mask: int = dataclasses.field(metadata=dict(static=True))  # hash: 2**b - 1

    def _replace(self, **kw) -> "Visited":
        return dataclasses.replace(self, **kw)


_EMPTY = jnp.int32(-1)
_PROBES = 8
_WORD = 32   # vertices per bitmap word


def _n_words(n_nodes: int) -> int:
    return -(-n_nodes // _WORD)


def make_visited(mode: str, n_nodes: int, hash_bits: int = 14) -> Visited:
    if mode == "bitmap":
        return Visited(jnp.zeros((_n_words(n_nodes),), jnp.uint32), True, 0)
    if mode == "hash":
        size = 1 << hash_bits
        return Visited(jnp.full((size,), _EMPTY, jnp.int32), False, size - 1)
    if mode == "loose":
        return Visited(jnp.full((1,), _EMPTY, jnp.int32), False, 0)
    raise ValueError(f"unknown visited mode {mode!r}")


def _hash(ids: jax.Array, mask: int) -> jax.Array:
    # Knuth multiplicative hash on int32 ids.
    h = (ids.astype(jnp.uint32) * jnp.uint32(2654435761)) >> jnp.uint32(16)
    return (h ^ ids.astype(jnp.uint32)).astype(jnp.int32) & mask


def check_and_insert(
    v: Visited, ids: jax.Array, valid: jax.Array
) -> Tuple[Visited, jax.Array]:
    """Batch test-and-set.  Returns (visited', fresh_mask).

    ``fresh_mask[i]`` is True when ids[i] was valid and *not* previously
    marked; those are the ids whose distances must be computed this step.
    """
    if v.mode_bitmap:
        # valid ids are < N (callers mask the graph's padding sentinel), so
        # no bit past N in the last word is ever set
        safe = jnp.clip(ids, 0, v.table.shape[0] * _WORD - 1)
        word = safe >> 5
        bit = (safe & (_WORD - 1)).astype(jnp.uint32)
        already = ((v.table[word] >> bit) & 1).astype(bool) & valid
        fresh = valid & ~already
        # in-batch duplicates: keep first occurrence only (exact dedup)
        fresh = fresh & _first_occurrence(ids, fresh)
        # scatter-ADD of the fresh bits is an exact OR: the fresh ids of one
        # call are distinct (first occurrence) and their bits were unset
        # (~already), so ids that share a word add disjoint bits and no
        # carry can occur; stale and invalid lanes add 0
        table = v.table.at[word].add(fresh.astype(jnp.uint32) << bit)
        return v._replace(table=table), fresh

    if v.mask == 0:  # loose mode: no memory; only in-batch dedup
        fresh = valid & _first_occurrence(ids, valid)
        return v, fresh

    # hash mode: bounded linear probing.
    table = v.table
    found = jnp.zeros(ids.shape, bool)
    inserted = jnp.zeros(ids.shape, bool)
    slot = _hash(ids, v.mask)
    for _ in range(_PROBES):
        cur = table[slot]
        # a lane that already claimed its slot must not read its own insert
        # back as a pre-existing hit
        hit = (cur == ids) & valid & ~inserted
        empty = (cur == _EMPTY) & valid & ~found & ~inserted
        # try to claim empty slots; duplicate-index scatter races are benign
        # (loser reads back a different key and retries next probe round)
        table = table.at[jnp.where(empty, slot, 0)].set(
            jnp.where(empty, ids, table[0]))
        claimed = empty & (table[slot] == ids)
        inserted = inserted | claimed
        found = found | hit
        done = found | inserted
        slot = jnp.where(done, slot, (slot + 1) & v.mask)
    # ids that neither hit nor found a slot are treated as fresh (duplicate
    # compute possible — benign)
    fresh = valid & ~found
    fresh = fresh & _first_occurrence(ids, fresh)
    return v._replace(table=table), fresh


def _first_occurrence(ids: jax.Array, valid: jax.Array) -> jax.Array:
    """Mask keeping only the first occurrence of each id among valid slots."""
    n = ids.shape[0]
    eq = ids[None, :] == ids[:, None]                 # (n, n)
    earlier = jnp.tril(jnp.ones((n, n), bool), k=-1)  # j < i
    dup_of_earlier = jnp.any(eq & earlier & valid[None, :], axis=1)
    return valid & ~dup_of_earlier


# ---------------------------------------------------------------------------
# Batch-major operations — leading (B,) query axis on the table
# ---------------------------------------------------------------------------

def make_visited_batch(mode: str, n_nodes: int, batch: int,
                       hash_bits: int = 14) -> Visited:
    """A stacked visited map: one :func:`make_visited` table per query on a
    leading (B,) axis (the batch-major engine's per-query visited state).

    Walker-stacked maps compose by passing ``batch=(B, W)``-style products
    through repeated broadcasting at the call site; this helper only adds
    the query axis."""
    if mode == "bitmap":
        return Visited(jnp.zeros((batch, _n_words(n_nodes)), jnp.uint32),
                       True, 0)
    if mode == "hash":
        size = 1 << hash_bits
        return Visited(jnp.full((batch, size), _EMPTY, jnp.int32), False,
                       size - 1)
    if mode == "loose":
        return Visited(jnp.full((batch, 1), _EMPTY, jnp.int32), False, 0)
    raise ValueError(f"unknown visited mode {mode!r}")


def check_and_insert_batch(
    v: Visited, ids: jax.Array, valid: jax.Array
) -> Tuple[Visited, jax.Array]:
    """:func:`check_and_insert` vmapped over the leading query axis:
    (B, ...) tables × (B, C) ids — bit-identical to the per-query path."""
    return jax.vmap(check_and_insert)(v, ids, valid)


def popcount(v: Visited) -> jax.Array:
    """Number of marked vertices in walker 0's table.

    On an OR-merged stacked map this is the exact union size (bitmap mode) or
    table occupancy (hash mode; slot losses undercount — benign).  Used to
    measure cross-walker duplicate computations:
    ``dups = sum(per-walker comps) - (union_after - union_before)``.
    """
    t0 = v.table[0] if v.table.ndim > 1 else v.table
    if v.mode_bitmap:
        return jnp.sum(jax.lax.population_count(t0)).astype(jnp.int32)
    if v.mask == 0:
        return jnp.int32(0)
    return jnp.sum(t0 != _EMPTY).astype(jnp.int32)


def merge_visited(vs: Visited) -> Visited:
    """OR-merge stacked walker visited maps (leading axis W) at a global sync.

    Bitmap: exact OR.  Hash: keep walker 0's table and re-insert others'
    non-empty keys (best effort; losses are benign).  Loose: no-op.
    """
    if vs.mode_bitmap:
        merged = or_reduce(vs.table)
        return Visited(jnp.broadcast_to(merged, vs.table.shape), True, 0)
    if vs.mask == 0:
        return vs
    # hash: fold tables together; occupied slots from any walker win.
    def fold(acc, t):
        take = (acc == _EMPTY) & (t != _EMPTY)
        return jnp.where(take, t, acc), None
    merged, _ = jax.lax.scan(fold, vs.table[0], vs.table[1:])
    return Visited(jnp.broadcast_to(merged, vs.table.shape), False, vs.mask)


def or_reduce(words: jax.Array) -> jax.Array:
    """Bitwise OR of stacked bitmap words over the leading axis: the union
    of the maps."""
    return jax.lax.reduce(words, jnp.uint32(0), jax.lax.bitwise_or, (0,))


def unpack(v: Visited, n_nodes: int) -> jax.Array:
    """A bitmap map as a (..., N) bool mask over the graph's vertices."""
    shifts = jnp.arange(_WORD, dtype=jnp.uint32)
    bits = (v.table[..., None] >> shifts) & 1           # (..., words, 32)
    flat = bits.reshape(v.table.shape[:-1] + (-1,))
    return flat[..., :n_nodes].astype(bool)
