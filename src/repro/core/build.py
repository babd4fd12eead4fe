"""Similarity-graph index construction.

The paper builds on NSG's construction ("not the focus of this work", §2.2).
The seed repo used a serial per-node Python loop with a host-side heap
search; this module replaces it with ParlayANN-style **batch insertion**:

* points are inserted in prefix-doubling rounds (1, 1, 2, 4, 8, ...); within
  a round every point runs its candidate search against the SAME frozen
  snapshot of the graph-so-far, so results cannot depend on intra-round
  ordering;
* all searches of a round go through the jit-compiled batch-major engine
  (``search_topm_batch`` — the exact hot path queries use at serve time,
  any registered distance backend), chunked into ``build_batch``-sized
  device calls.  ``build_batch`` is ONLY a compute tile: the final graph is
  bit-identical for every batch size and every within-batch permutation;
* the α-prune runs as a vectorized matrix form of :func:`_robust_prune`
  over the whole round (:func:`robust_prune_batch`), and reverse edges are
  applied from a (u, p)-lexsorted pair list with a fixed lowest-id-first
  conflict rule — deterministic, batch-invariant, bit-reproducible.

:func:`build_nsg_serial` is the per-point reference implementation (same
round schedule, scalar prune loops); ``build_nsg(build_batch=1)`` must match
it bit for bit — the parity gate ``tests/test_build_batch.py`` pins.

Incremental maintenance rides the same machinery: :func:`insert_points`
inserts new points into a live padded adjacency (``AnnIndex.add``), and
:func:`repair_deleted` re-prunes the in-neighborhood of tombstoned vertices
(``AnnIndex.delete``) so the graph stays navigable without a rebuild.

Construction remains offline-ish; host numpy orchestrates and the device
does the distance-heavy candidate searches.  Search-time code never calls
into this module.
"""
from __future__ import annotations

import functools
from typing import List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.config import SearchConfig
from repro.core.graph import PaddedCSR, compute_medoid, make_padded_csr
from repro.kernels.platform import row_tiles


# ---------------------------------------------------------------------------
# Exact kNN (blocked brute force) — ground truth + upper-level seeds
# ---------------------------------------------------------------------------

def normalize_rows(x: np.ndarray) -> np.ndarray:
    """Unit-normalize rows (cosine = inner product on normalized vectors)."""
    x = np.asarray(x, np.float32)
    return x / np.maximum(np.linalg.norm(x, axis=1, keepdims=True), 1e-12)


@functools.partial(jax.jit, static_argnames=("metric",))
def _dist_block(q: jax.Array, x: jax.Array, metric: str = "l2") -> jax.Array:
    """(B, N) distances between query block and data block; smaller =
    closer for every metric ("ip" = negative inner product).

    The product runs at full f32 precision: a TPU's default f32 matmul
    rounds its inputs to bf16, which reorders near neighbours and would
    make this "exact" ground truth approximate."""
    q = q.astype(jnp.float32)
    x = x.astype(jnp.float32)
    qx = jnp.matmul(q, x.T, precision=jax.lax.Precision.HIGHEST)
    if metric == "ip":
        return -qx
    q2 = jnp.sum(q * q, axis=1, keepdims=True)
    x2 = jnp.sum(x * x, axis=1)
    return q2 + x2[None, :] - 2.0 * qx


def exact_knn(
    data: np.ndarray, queries: np.ndarray, k: int, block: int = 2048,
    metric: str = "l2",
) -> Tuple[np.ndarray, np.ndarray]:
    """Exact k nearest neighbors of ``queries`` within ``data``.

    ``metric`` is "l2" (squared L2), "ip" (negative inner product), or
    "cosine" (ip after normalizing BOTH sides here).  Returns
    (ids (Q, k) int32, dists (Q, k) float32) sorted ascending.
    """
    if metric == "cosine":
        data, queries = normalize_rows(data), normalize_rows(queries)
        metric = "ip"
    data_j = jnp.asarray(data)
    out_ids, out_d = [], []
    for s in range(0, queries.shape[0], block):
        q = jnp.asarray(queries[s:s + block])
        d = _dist_block(q, data_j, metric=metric)     # (b, N)
        d_top, i_top = jax.lax.top_k(-d, k)
        out_ids.append(np.asarray(i_top, np.int32))
        out_d.append(np.asarray(-d_top, np.float32))
    return np.concatenate(out_ids), np.concatenate(out_d)


def knn_graph(data: np.ndarray, k: int, block: int = 2048,
              metric: str = "l2") -> np.ndarray:
    """(N, k) kNN graph excluding self-edges, padded with the sentinel N.

    One numpy pass: a stable argsort on the self-edge mask compacts each
    row's non-self entries to the front (preserving distance order), then
    slots past the per-row valid count become the sentinel.
    """
    ids, _ = exact_knn(data, data, k + 1, block, metric=metric)
    n = data.shape[0]
    valid = ids != np.arange(n, dtype=ids.dtype)[:, None]     # (N, k+1)
    order = np.argsort(~valid, axis=1, kind="stable")
    rows = np.take_along_axis(ids, order, axis=1)[:, :k]
    cnt = np.minimum(valid.sum(axis=1), k)
    rows = np.where(np.arange(k)[None, :] < cnt[:, None], rows, n)
    return rows.astype(np.int32)


# ---------------------------------------------------------------------------
# α-prune: scalar reference + vectorized batch form
# ---------------------------------------------------------------------------

def prune_dists(vecs: np.ndarray, point: np.ndarray,
                metric: str) -> np.ndarray:
    """Candidate-to-point distances on the builder's pruning scale.

    ``vecs`` is (..., C, d), ``point`` broadcasts as (..., d); returns
    (..., C).  Actual L2 for "l2" (NOT squared — the α-occlusion rule is
    stated on metric distances), negative inner product for "ip".  Both the
    scalar and the batch prune call THIS function, with einsum contractions
    whose elementwise accumulation order is identical for 2-D and 3-D
    inputs — that shared arithmetic is what makes ``build_batch=1``
    bit-identical to the serial reference.
    """
    if metric == "ip":
        return -np.einsum("...cd,...d->...c", vecs, point)
    diff = vecs - point[..., None, :]
    return np.sqrt(np.maximum(
        np.einsum("...cd,...cd->...c", diff, diff), 0.0))


def _prune_dists(data: np.ndarray, ids: np.ndarray, point: np.ndarray,
                 metric: str) -> np.ndarray:
    """Distances of data[ids] to ``point`` (scalar-path convenience)."""
    return prune_dists(data[ids], point, metric)


def _robust_prune(
    data: np.ndarray, node: int, cand_ids: np.ndarray, cand_d: np.ndarray,
    degree: int, alpha: float, metric: str = "l2",
) -> np.ndarray:
    """Monotonic-RNG α-prune: greedily keep the closest candidate c, then
    drop every remaining candidate c' with α·d(c, c') ≤ d(node, c').

    For "ip" the same occlusion rule runs on negative-inner-product
    distances (the ip-NSW heuristic) with α forced to 1: scaling negative
    distances would invert the α>1 "keep more" semantics."""
    order = np.argsort(cand_d, kind="stable")
    cand_ids = cand_ids[order]
    cand_d = cand_d[order]
    eff_alpha = 1.0 if metric == "ip" else alpha
    keep: List[int] = []
    alive = np.ones(cand_ids.shape[0], bool)
    alive &= cand_ids != node
    for i in range(cand_ids.shape[0]):
        if not alive[i]:
            continue
        c = int(cand_ids[i])
        keep.append(c)
        if len(keep) >= degree:
            break
        # occlusion rule: drop c' when c is much closer to c' than node is
        d_cc = _prune_dists(data, cand_ids, data[c], metric)
        alive = alive & ~(eff_alpha * d_cc <= cand_d)
        alive[i] = False
    return np.asarray(keep, np.int32)


def robust_prune_batch(
    data: np.ndarray, node_ids: np.ndarray, cand_ids: np.ndarray,
    degree: int, alpha: float, metric: str = "l2",
) -> np.ndarray:
    """Vectorized :func:`_robust_prune` over a whole batch of nodes.

    ``node_ids`` is (B,), ``cand_ids`` (B, C) int32 padded with the
    sentinel ``len(data)`` (rows need not be sorted; padding and self
    entries are masked).  Returns (B, degree) int32 kept neighbors, padded
    with the sentinel — row b bit-identical to
    ``_robust_prune(data, node_ids[b], ...)`` over the same candidates.

    The greedy loop runs over OUTPUT SLOTS (``degree`` iterations) instead
    of candidates: each iteration picks every row's first still-alive
    candidate at once and applies the α-occlusion mask as one (B, C)
    matrix update.
    """
    n = data.shape[0]
    bsz, _ = cand_ids.shape
    valid = cand_ids < n
    cvecs = data[np.minimum(cand_ids, n - 1)]             # (B, C, d)
    cand_d = prune_dists(cvecs, data[node_ids], metric)   # (B, C)
    cand_d = np.where(valid, cand_d, np.inf)
    order = np.argsort(cand_d, axis=1, kind="stable")
    cand_ids = np.take_along_axis(cand_ids, order, axis=1)
    cand_d = np.take_along_axis(cand_d, order, axis=1)
    cvecs = np.take_along_axis(cvecs, order[:, :, None], axis=1)
    eff_alpha = 1.0 if metric == "ip" else alpha
    alive = (cand_ids < n) & (cand_ids != node_ids[:, None])
    rows = np.arange(bsz)
    out = np.full((bsz, degree), n, np.int32)
    for slot in range(degree):
        has = alive.any(axis=1)
        if not has.any():
            break
        idx = np.argmax(alive, axis=1)                    # first alive
        out[:, slot] = np.where(has, cand_ids[rows, idx], n)
        if slot == degree - 1:
            break
        d_cc = prune_dists(cvecs, cvecs[rows, idx], metric)   # (B, C)
        alive = alive & ~(eff_alpha * d_cc <= cand_d)
        alive[rows, idx] = False
    return out


# ---------------------------------------------------------------------------
# Candidate search: the batch-major engine over the graph-so-far
# ---------------------------------------------------------------------------

def _build_search_config(ef: int, metric: str, backend: str) -> SearchConfig:
    """The builder's candidate-search beam: top-M staged traversal with an
    ``ef``-deep frontier, through any registered distance backend."""
    return SearchConfig(
        k=ef, metric=metric, queue_len=ef, m_max=4, staged=True,
        stage_every=1, max_steps=4 * ef, dist_backend=backend,
        visited_mode="bitmap")   # the (B, N) mask IS the prune pool


@functools.partial(jax.jit, static_argnames=("cfg", "pool"))
def _candidate_search_batch(nbrs: jax.Array, vectors: jax.Array,
                            vector_tiles: Optional[jax.Array],
                            entry: jax.Array, queries: jax.Array,
                            cfg: SearchConfig, pool: str) -> jax.Array:
    """Candidate beam search for a B-leading query batch over a graph
    snapshot.  ``queries`` is (B, d).  ``pool`` picks the candidate set:

    * ``"visited"`` — the (B, N) bool visited mask, Vamana's prune pool V
      (every vertex the traversal scored, including the far-out descent
      path whose pruned survivors become the long-range edges).  The
      INSERTION pool.
    * ``"results"`` — the (B, ef) top results only (the seed builder's
      refinement pool): a deliberately NARROW, local pool, so a
      refinement prune polishes the close neighborhood while the current
      row's long-range edges keep their slots.

    The snapshot is the full-shape (N, R) adjacency, so every round of a
    build reuses ONE trace per pool kind."""
    from repro.core.bfis import (search_topm_batch,
                                 search_topm_batch_visited)

    graph = PaddedCSR(
        nbrs=nbrs, vectors=vectors, medoid=entry, n_top=0,
        flat=jnp.zeros((0, nbrs.shape[1], vectors.shape[1]),
                       vectors.dtype), vector_tiles=vector_tiles)
    if pool == "visited":
        _, _, _, visited = search_topm_batch_visited(graph, queries, cfg)
        return visited
    if pool == "results":
        ids, _, _ = search_topm_batch(graph, queries, cfg)
        return ids
    raise ValueError(f"unknown candidate pool {pool!r}")


def _device_tables(data: np.ndarray, build_backend: str
                   ) -> Tuple[jax.Array, Optional[jax.Array]]:
    """The build's device tables: the (N, d) vectors, plus their row-tiled
    copy when ``build_backend`` is a Pallas kernel (None for ``ref``)."""
    vectors = jnp.asarray(data)
    return vectors, (None if build_backend == "ref"
                     else row_tiles(vectors))


def _visited_to_rows(vis: np.ndarray, n: int) -> np.ndarray:
    """(b, N) bool visited masks -> (b, C) int32 ascending visited ids,
    sentinel-padded, with C = the chunk's max visited count.  Converting
    per chunk keeps host memory at O(b · C) — the round never materializes
    a (round, N) mask."""
    counts = vis.sum(axis=1)
    width = max(int(counts.max()), 1)
    rows_idx, ids = np.nonzero(vis)
    pos = np.arange(ids.shape[0]) \
        - np.repeat(np.cumsum(counts) - counts, counts)
    out = np.full((vis.shape[0], width), n, np.int32)
    out[rows_idx, pos] = ids
    return out


def _search_candidates(
    nbrs_dev: jax.Array, vectors_dev: jax.Array,
    tiles_dev: Optional[jax.Array], entry_dev: jax.Array,
    queries: np.ndarray, cfg: SearchConfig, build_batch: int,
    batch_perm: Optional[int] = None, pool: str = "visited",
) -> np.ndarray:
    """Run all candidate searches for a round, ``build_batch`` at a time;
    returns per-point candidate pools as (B, C) sentinel-padded id rows
    (``pool`` as in :func:`_candidate_search_batch`).

    The last chunk is padded (repeating its first row) so every device call
    has the same (build_batch, d) shape — one jit trace per build.  With
    ``batch_perm`` set, each chunk is permuted before the device call and
    un-permuted after: a determinism audit knob proving lane results don't
    depend on batch position (the engine's per-lane independence contract).
    """
    n = int(nbrs_dev.shape[0])
    out = []
    total = queries.shape[0]
    for s in range(0, total, build_batch):
        q = queries[s:s + build_batch]
        b = q.shape[0]
        if b < build_batch:
            q = np.concatenate(
                [q, np.repeat(q[:1], build_batch - b, axis=0)])
        if batch_perm is not None:
            perm = np.random.RandomState(batch_perm + s).permutation(
                build_batch)
            res = np.asarray(_candidate_search_batch(
                nbrs_dev, vectors_dev, tiles_dev, entry_dev,
                jnp.asarray(q[perm]), cfg, pool))
            unperm = np.empty_like(res)
            unperm[perm] = res
            res = unperm
        else:
            res = np.asarray(_candidate_search_batch(
                nbrs_dev, vectors_dev, tiles_dev, entry_dev, jnp.asarray(q),
                cfg, pool))
        out.append(_visited_to_rows(res[:b], n)
                   if pool == "visited" else res[:b].astype(np.int32))
    width = max(c.shape[1] for c in out)
    out = [np.pad(c, ((0, 0), (0, width - c.shape[1])),
                  constant_values=n) if c.shape[1] < width else c
           for c in out]
    return np.concatenate(out, axis=0)


def _canonical_candidates(ids: np.ndarray, cur: np.ndarray,
                          node_ids: np.ndarray, n: int) -> np.ndarray:
    """Merge search results with current neighbors into the canonical
    candidate form: per row ascending unique ids, self and invalid entries
    mapped to the sentinel ``n``, sentinel-padded to fixed width.

    Canonicalization is what buys batch invariance: however the search
    chunks delivered the ids, every row enters the prune as the same
    ascending set — matching the ``np.unique`` ordering of the serial
    reference.
    """
    allc = np.concatenate([ids, cur], axis=1).astype(np.int64)
    allc = np.where((allc < 0) | (allc >= n), n, allc)
    allc = np.where(allc == node_ids[:, None], n, allc)
    allc = np.sort(allc, axis=1)
    dup = np.zeros(allc.shape, bool)
    dup[:, 1:] = allc[:, 1:] == allc[:, :-1]
    allc = np.sort(np.where(dup, n, allc), axis=1)
    return allc.astype(np.int32)


# ---------------------------------------------------------------------------
# Round application: forward prune + deterministic reverse edges
# ---------------------------------------------------------------------------

_PRUNE_CHUNK = 2048   # rows per robust_prune_batch call (bounds B·C·d memory)


def _prune_round(data: np.ndarray, node_ids: np.ndarray, cand: np.ndarray,
                 degree: int, alpha: float, metric: str,
                 serial: bool) -> np.ndarray:
    """α-prune every row of a round; returns (B, degree) sentinel-padded."""
    n = data.shape[0]
    if serial:
        out = np.full((node_ids.shape[0], degree), n, np.int32)
        for i, node in enumerate(node_ids):
            c = cand[i][cand[i] < n]
            d = _prune_dists(data, c, data[node], metric)
            kept = _robust_prune(data, int(node), c, d, degree, alpha,
                                 metric=metric)
            out[i, :kept.shape[0]] = kept
        return out
    chunks = [robust_prune_batch(data, node_ids[s:s + _PRUNE_CHUNK],
                                 cand[s:s + _PRUNE_CHUNK], degree, alpha,
                                 metric=metric)
              for s in range(0, node_ids.shape[0], _PRUNE_CHUNK)]
    return np.concatenate(chunks, axis=0)


def _apply_reverse(nbrs: np.ndarray, data: np.ndarray,
                   round_ids: np.ndarray, pruned: np.ndarray,
                   degree: int, alpha: float, metric: str,
                   serial: bool) -> None:
    """Apply a round's reverse edges p -> u for every forward edge u in
    pruned[p], mutating ``nbrs`` rows of the targets u in place.

    Determinism rule: collect ALL (u, p) pairs of the round, lexsort by
    (u, p), then per target u (ascending) append the fresh in-neighbors in
    ascending-p order; on overflow past ``degree`` the row is re-pruned
    ONCE over the ascending unique union — lowest-id-first at every tie, so
    the result is independent of how the round was batched.
    """
    n = data.shape[0]
    valid = pruned < n
    if not valid.any():
        return
    u_arr = pruned[valid]
    p_arr = np.repeat(round_ids, valid.sum(axis=1))
    order = np.lexsort((p_arr, u_arr))
    u_arr, p_arr = u_arr[order], p_arr[order]
    targets, starts = np.unique(u_arr, return_index=True)
    bounds = np.append(starts, u_arr.shape[0])

    over_nodes: List[int] = []
    over_cands: List[np.ndarray] = []
    for t, u in enumerate(targets):
        u = int(u)
        incoming = p_arr[bounds[t]:bounds[t + 1]]
        cur = nbrs[u][nbrs[u] < n]
        fresh = np.setdiff1d(incoming, cur)       # sorted unique, asc p
        fresh = fresh[fresh != u]
        if fresh.shape[0] == 0:
            continue
        if cur.shape[0] + fresh.shape[0] <= degree:
            row = np.concatenate([cur, fresh])
            nbrs[u, :row.shape[0]] = row
            nbrs[u, row.shape[0]:] = n
            continue
        cand = np.unique(np.concatenate([cur, fresh]))
        cand = cand[cand != u]
        if serial:
            d = _prune_dists(data, cand, data[u], metric)
            kept = _robust_prune(data, u, cand, d, degree, alpha,
                                 metric=metric)
            nbrs[u, :kept.shape[0]] = kept
            nbrs[u, kept.shape[0]:] = n
        else:
            over_nodes.append(u)
            over_cands.append(cand)
    if over_nodes:
        width = max(c.shape[0] for c in over_cands)
        cmat = np.full((len(over_nodes), width), n, np.int32)
        for i, c in enumerate(over_cands):
            cmat[i, :c.shape[0]] = c
        node_arr = np.asarray(over_nodes, np.int64)
        for s in range(0, node_arr.shape[0], _PRUNE_CHUNK):
            kept = robust_prune_batch(
                data, node_arr[s:s + _PRUNE_CHUNK],
                cmat[s:s + _PRUNE_CHUNK], degree, alpha, metric=metric)
            nbrs[node_arr[s:s + _PRUNE_CHUNK]] = kept


# ---------------------------------------------------------------------------
# Batch insertion (ParlayANN-style) + refinement
# ---------------------------------------------------------------------------

def insert_points(
    nbrs: np.ndarray,
    data: np.ndarray,
    entry: int,
    new_ids: np.ndarray,
    n_base: int,
    *,
    degree: int,
    alpha: float,
    ef: int,
    metric: str,
    build_batch: int = 32,
    build_backend: str = "ref",
    serial: bool = False,
    batch_perm: Optional[int] = None,
) -> None:
    """Insert ``new_ids`` (in order) into the live padded adjacency
    ``nbrs`` (mutated in place) by prefix-doubling batch insertion.

    ``nbrs`` is the full (N, degree) int32 table, sentinel-padded;
    not-yet-inserted rows must be fully sentinel.  ``n_base`` is how many
    points are already live (0 for a fresh build — the first new id then
    bootstraps the graph bare).  Every round: ONE batch-major candidate
    search per ``build_batch`` chunk against the frozen snapshot, a
    vectorized α-prune of the whole round, then the deterministic reverse
    pass.  Round sizes double from the live count, so the schedule — and
    therefore the graph — depends only on the insertion order, never on
    ``build_batch``.
    """
    new_ids = np.asarray(new_ids, np.int64)
    n = data.shape[0]
    cfg = _build_search_config(ef, metric, build_backend)
    vectors_dev, tiles_dev = _device_tables(data, build_backend)
    entry_dev = jnp.asarray(entry, jnp.int32)

    pos = 0
    inserted = n_base
    if inserted == 0 and new_ids.shape[0] > 0:
        nbrs[new_ids[0]] = n          # bootstrap: first point, no edges
        pos, inserted = 1, 1
    while pos < new_ids.shape[0]:
        take = min(inserted, new_ids.shape[0] - pos)
        _process_round(nbrs, data, vectors_dev, tiles_dev, entry_dev,
                       new_ids[pos:pos + take], cfg, degree, alpha, metric,
                       build_batch, serial, batch_perm)
        pos += take
        inserted += take


def _process_round(
    nbrs: np.ndarray, data: np.ndarray, vectors_dev: jax.Array,
    tiles_dev: Optional[jax.Array], entry_dev: jax.Array,
    round_ids: np.ndarray, cfg: SearchConfig,
    degree: int, alpha: float, metric: str, build_batch: int,
    serial: bool, batch_perm: Optional[int], pool: str = "visited",
) -> None:
    """One build round: search the frozen snapshot for every round point,
    α-prune each over its candidate pool ∪ current row, write the forward
    rows, then run the deterministic reverse pass."""
    n = data.shape[0]
    vis = _search_candidates(
        jnp.asarray(nbrs), vectors_dev, tiles_dev, entry_dev,
        data[round_ids], cfg, build_batch, batch_perm, pool)
    cand = _canonical_candidates(vis, nbrs[round_ids], round_ids, n)
    pruned = _prune_round(data, round_ids, cand, degree, alpha, metric,
                          serial)
    nbrs[round_ids] = pruned
    _apply_reverse(nbrs, data, round_ids, pruned, degree, alpha, metric,
                   serial)


def _refine_pass(
    nbrs: np.ndarray, data: np.ndarray, entry: int, order: np.ndarray, *,
    degree: int, alpha: float, ef: int, metric: str,
    build_batch: int, build_backend: str, serial: bool,
    batch_perm: Optional[int],
) -> None:
    """One refinement pass: every vertex is re-processed in the SAME
    doubling round partition as insertion (1, 1, 2, 4, ...), each round
    searching the graph as left by the previous rounds.  Gauss-Seidel at
    round granularity: later rounds see earlier rounds' refined rows —
    replacing all rows against one frozen snapshot (Jacobi) measurably
    degrades navigability, because simultaneous replacement severs the
    in/out-edge interdependencies the insertion pass built up.  The round
    partition is fixed by ``order`` alone, so the pass stays deterministic
    and ``build_batch``-invariant.

    Refinement prunes over the NARROW ``"results"`` pool (top-ef results ∪
    current row — the seed builder's pass semantics), not the visited set:
    on a fully built graph the visited pool is so rich in near candidates
    that a degree-capped re-prune fills every slot locally and evicts the
    long-range descent edges the insertion pass created (measured: the
    entry point's longest edge shrinks ~3x and beam recall collapses).
    The narrow pool polishes local structure while incumbent long edges
    keep their slots."""
    cfg = _build_search_config(ef, metric, build_backend)
    vectors_dev, tiles_dev = _device_tables(data, build_backend)
    entry_dev = jnp.asarray(entry, jnp.int32)
    pos, step = 0, 1
    while pos < order.shape[0]:
        take = min(step, order.shape[0] - pos)
        _process_round(nbrs, data, vectors_dev, tiles_dev, entry_dev,
                       order[pos:pos + take], cfg, degree, alpha, metric,
                       build_batch, serial, batch_perm, pool="results")
        pos += take
        step *= 2


def build_nsg(
    data: np.ndarray,
    degree: int = 32,
    knn_k: int = 32,
    alpha: float = 1.2,
    ef_construction: int = 64,
    seed: int = 0,
    passes: int = 2,
    metric: str = "l2",
    build_batch: int = 32,
    build_backend: str = "ref",
    batch_perm: Optional[int] = None,
    serial: bool = False,
) -> PaddedCSR:
    """Vamana/NSG-style construction by batched prefix-doubling insertion
    (medoid-first random order) plus ``passes - 1`` synchronous α-pruned
    refinement passes.  The insertion pass prunes with α=1 when refinement
    follows (the seed builder's schedule); a single-pass build prunes with
    ``alpha`` directly.  Last, NSG's connectivity step
    (:func:`connect_from`) links every vertex the medoid cannot reach.

    ``metric``: "l2" (default), "ip" (MIPS graph — ip-NSW-style pruning on
    negative-inner-product distances), or "cosine" (the base vectors are
    unit-normalized HERE and the graph built with l2, which orders
    identically to cosine on the unit sphere — the returned index stores
    the normalized vectors).

    ``knn_k`` is accepted for signature compatibility with the seed
    builder; batch insertion needs no kNN seed graph, so it is ignored.
    ``build_batch`` tiles the device-side candidate searches and
    ``build_backend`` picks their distance kernel — neither changes a
    single output bit (``tests/test_build_batch.py``).  ``batch_perm``
    shuffles each search chunk (and unshuffles results): the determinism
    audit knob.  ``serial`` switches to the scalar per-point reference
    kernels (see :func:`build_nsg_serial`).
    """
    del knn_k
    n = data.shape[0]
    data = np.asarray(data, np.float32)
    if metric == "cosine":
        data = normalize_rows(data)
        metric = "l2"
    elif metric not in ("l2", "ip"):
        raise ValueError(f"unknown metric {metric!r}")
    medoid = compute_medoid(data, metric=metric)
    rng = np.random.RandomState(seed)
    perm = rng.permutation(n)
    order = np.concatenate([[medoid], perm[perm != medoid]])
    nbrs = np.full((n, degree), n, np.int32)
    kw = dict(degree=degree, ef=ef_construction, metric=metric,
              build_batch=build_batch, build_backend=build_backend,
              serial=serial, batch_perm=batch_perm)
    a_ins = alpha if passes <= 1 else 1.0
    insert_points(nbrs, data, medoid, order, 0, alpha=a_ins, **kw)
    for _ in range(max(passes - 1, 0)):
        _refine_pass(nbrs, data, medoid, order, alpha=alpha, **kw)
    connect_from(nbrs, data, medoid, ef=ef_construction, metric=metric,
                 build_backend=build_backend)
    return make_padded_csr(nbrs, data, medoid=medoid)


def reachable(nbrs: np.ndarray, roots, reached: Optional[np.ndarray] = None
              ) -> np.ndarray:
    """(N,) bool: the vertices reachable from ``roots`` along out-edges
    (breadth-first over the padded adjacency), grown in place from
    ``reached`` when given."""
    n = nbrs.shape[0]
    reached = np.zeros(n, bool) if reached is None else reached
    frontier = np.unique(np.asarray(roots, np.int64))
    frontier = frontier[~reached[frontier]]
    while frontier.size:
        reached[frontier] = True
        nxt = nbrs[frontier].reshape(-1)
        nxt = nxt[nxt < n]
        frontier = np.unique(nxt[~reached[nxt]])
    return reached


def connect_from(nbrs: np.ndarray, data: np.ndarray, root: int, *, ef: int,
                 metric: str, build_backend: str = "ref") -> int:
    """NSG's connectivity step (Fu et al. 2019, "tree spanning"): make every
    vertex reachable from ``root``, in place.

    While some vertex is unreached, take the lowest unreached id v, search
    the current graph for v's vector from ``root``, and link the nearest
    REACHED vertex u the search returned to v (u = ``root`` when it found
    none); everything v reaches becomes reached with it.  The link goes
    into a padding slot of the nearest such u that has one; when every
    row the search returned is full, it replaces, in the nearest u's row,
    the edge to the neighbour with the largest in-degree — never an
    earlier link.  A replaced edge may have been some vertex's only way
    in, so a final sweep re-checks reachability from ``root``.
    Deterministic.  Returns the number of links added.

    Degree-capped pruning can leave whole regions without an in-edge from
    the rest of the graph — on well-separated clusters every vertex's
    slots fill inside its own cluster — and a search from the single entry
    can then never reach them."""
    n = nbrs.shape[0]
    cfg = _build_search_config(ef, metric, build_backend)
    vectors_dev, tiles_dev = _device_tables(data, build_backend)
    root_dev = jnp.asarray(root, jnp.int32)
    indeg = np.bincount(nbrs[nbrs < n], minlength=n)
    linked = np.zeros(nbrs.shape, bool)         # slots holding a link
    reached = reachable(nbrs, [root])
    while not reached.all():
        while not reached.all():
            v = int(np.argmin(reached))
            found = _search_candidates(
                jnp.asarray(nbrs), vectors_dev, tiles_dev, root_dev,
                data[v:v + 1], cfg, 1, pool="results")[0]
            near = found[found < n]
            near = near[reached[near]]
            has_free = (nbrs[near] >= n).any(axis=1)
            if has_free.any():
                u = int(near[np.argmax(has_free)])
            else:
                u = next((int(x) for x in np.concatenate(
                    [near, np.flatnonzero(reached)])
                    if not linked[x].all()), root)
            row = nbrs[u]
            free = np.flatnonzero(row >= n)
            if free.size:
                slot = int(free[0])
            else:
                slot = int(np.argmax(np.where(linked[u], -1, indeg[row])))
                indeg[row[slot]] -= 1
            row[slot] = v
            linked[u, slot] = True
            indeg[v] += 1
            reachable(nbrs, [v], reached)
        reached = reachable(nbrs, [root])
    return int(linked.sum())


def build_nsg_serial(
    data: np.ndarray,
    degree: int = 32,
    knn_k: int = 32,
    alpha: float = 1.2,
    ef_construction: int = 64,
    seed: int = 0,
    passes: int = 2,
    metric: str = "l2",
) -> PaddedCSR:
    """Per-point reference builder: identical round schedule and candidate
    searches to :func:`build_nsg`, but every prune runs the scalar
    :func:`_robust_prune` loop and reverse edges apply one target at a
    time.  ``build_nsg(..., build_batch=1)`` must reproduce its output bit
    for bit — the batched path's correctness oracle."""
    return build_nsg(
        data, degree=degree, knn_k=knn_k, alpha=alpha,
        ef_construction=ef_construction, seed=seed, passes=passes,
        metric=metric, build_batch=1, serial=True)


# ---------------------------------------------------------------------------
# Incremental maintenance: tombstone-delete repair
# ---------------------------------------------------------------------------

def repair_deleted(
    nbrs: np.ndarray,
    data: np.ndarray,
    tombstone: np.ndarray,
    *,
    degree: int,
    alpha: float,
    metric: str,
    serial: bool = False,
) -> int:
    """Repair the neighborhood of tombstoned vertices (FreshDiskANN-style).

    Every live in-neighbor u of a deleted vertex d re-prunes over
    ``(nbrs[u] \\ deleted) ∪ (nbrs[d] \\ deleted \\ {u})`` — u inherits its
    dead neighbors' out-edges so paths THROUGH d stay representable, then
    the α-prune restores the degree bound.  Deleted rows keep their
    out-edges (they remain navigable waypoints; search masks them from
    results).  All affected rows are collected against the pre-repair
    snapshot and pruned in one vectorized call — deterministic, order-free.
    Returns the number of repaired rows.
    """
    n = data.shape[0]
    tombstone = np.asarray(tombstone, bool)
    deleted = np.where(tombstone)[0]
    if deleted.shape[0] == 0:
        return 0
    snapshot = nbrs.copy()
    dead_edge = (snapshot < n) & tombstone[np.minimum(snapshot, n - 1)]
    affected = np.where(dead_edge.any(axis=1) & ~tombstone)[0]
    if affected.shape[0] == 0:
        return 0

    cands: List[np.ndarray] = []
    for u in affected:
        row = snapshot[u][snapshot[u] < n]
        keepers = row[~tombstone[row]]
        inherited = snapshot[row[tombstone[row]]].ravel()
        inherited = inherited[inherited < n]
        inherited = inherited[~tombstone[inherited]]
        cand = np.unique(np.concatenate([keepers, inherited]))
        cand = cand[cand != u]
        cands.append(cand)
    width = max(max(c.shape[0] for c in cands), 1)
    cmat = np.full((affected.shape[0], width), n, np.int32)
    for i, c in enumerate(cands):
        cmat[i, :c.shape[0]] = c
    if serial:
        for i, u in enumerate(affected):
            c = cmat[i][cmat[i] < n]
            d = _prune_dists(data, c, data[u], metric)
            kept = _robust_prune(data, int(u), c, d, degree, alpha,
                                 metric=metric)
            nbrs[u, :kept.shape[0]] = kept
            nbrs[u, kept.shape[0]:] = n
    else:
        for s in range(0, affected.shape[0], _PRUNE_CHUNK):
            kept = robust_prune_batch(
                data, affected[s:s + _PRUNE_CHUNK].astype(np.int64),
                cmat[s:s + _PRUNE_CHUNK], degree, alpha, metric=metric)
            nbrs[affected[s:s + _PRUNE_CHUNK]] = kept
    return int(affected.shape[0])


# ---------------------------------------------------------------------------
# HNSW-style hierarchical index (the paper's second baseline)
# ---------------------------------------------------------------------------

class HNSWIndex(NamedTuple):
    base: PaddedCSR                 # level-0 graph (searched with BFiS)
    level_nbrs: Tuple[jax.Array, ...]   # per upper level: (N, R_l) int32
    level_nodes: Tuple[jax.Array, ...]  # per upper level: member node ids
    entry: int


def _upper_level_ids(sub_knn: np.ndarray, members: np.ndarray,
                     n: int) -> np.ndarray:
    """Map a sub-index kNN table onto global ids via a lookup table whose
    last entry IS the global sentinel: sub-sentinel rows (value ==
    len(members), from duplicate members) land on ``n`` and can never
    alias a real member id."""
    lut = np.concatenate(
        [members.astype(np.int64), np.asarray([n], np.int64)])
    return lut[np.minimum(sub_knn, members.shape[0])].astype(np.int32)


def build_hnsw(
    data: np.ndarray,
    degree: int = 32,
    upper_degree: int = 16,
    ml: float = 0.36,                # 1/ln(M) with M=16
    seed: int = 0,
    alpha: float = 1.2,
    metric: str = "l2",
    build_batch: int = 32,
    build_backend: str = "ref",
) -> HNSWIndex:
    """Simplified HNSW: geometric level sampling; each upper level is an
    α-pruned kNN graph over its members; level 0 reuses the (batched) NSG
    builder.  ``metric`` as in :func:`build_nsg` (cosine normalizes here)."""
    n = data.shape[0]
    data = np.asarray(data, np.float32)
    if metric == "cosine":
        data = normalize_rows(data)
        metric = "l2"
    rng = np.random.RandomState(seed)
    levels = np.minimum(
        (-np.log(np.maximum(rng.uniform(size=n), 1e-12)) * ml).astype(int), 6)
    base = build_nsg(data, degree=degree, alpha=alpha, seed=seed, passes=2,
                     metric=metric, build_batch=build_batch,
                     build_backend=build_backend)
    level_nbrs, level_nodes = [], []
    max_level = int(levels.max())
    entry = int(np.argmax(levels))
    for lvl in range(1, max_level + 1):
        members = np.where(levels >= lvl)[0].astype(np.int32)
        if members.shape[0] < 2:
            break
        sub = data[members]
        k = min(upper_degree, members.shape[0] - 1)
        sub_knn = knn_graph(sub, k, metric=metric)
        g = _upper_level_ids(sub_knn, members, n)
        full = np.full((n, upper_degree), n, np.int32)
        full[members, :k] = g
        level_nbrs.append(jnp.asarray(full))
        level_nodes.append(jnp.asarray(members))
    return HNSWIndex(base=base, level_nbrs=tuple(level_nbrs),
                     level_nodes=tuple(level_nodes), entry=entry)
