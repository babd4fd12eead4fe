"""Similarity-graph index structures.

The paper (§3.2, Fig. 2) stores the index as CSR topology + a separate
embedding matrix.  On TPU we use a *padded* CSR — a dense ``(N, R)`` int32
neighbor table (R = max out-degree, padding = sentinel ``N``) — because fixed
shapes are required under jit and similarity graphs are degree-truncated
anyway (NSG/HNSW cap out-degree to avoid the "out-degree explosion problem").

Neighbor grouping (§4.4, Fig. 11) is realized as a two-level layout:

* vertices are re-labelled by in-degree rank (degree-centric) or by measured
  access frequency (frequency-centric);
* the top ``n_top`` vertices additionally carry a *flattened* neighbor
  embedding tensor ``flat[(n_top, R, d)]`` so expanding a hot vertex is one
  contiguous ``dynamic_slice`` (an HBM burst) instead of R random gathers.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np


class PaddedCSR(NamedTuple):
    """Dense padded adjacency + vectors. ``nbrs[i, j] == n_nodes`` is padding."""
    nbrs: jax.Array        # (N, R) int32, padded with N
    vectors: jax.Array     # (N, d) float32/bfloat16 feature vectors
    medoid: jax.Array      # () int32, default entry point
    # two-level neighbor grouping (optional; zero-size when disabled)
    n_top: int             # static: number of top-level (flattened) vertices
    flat: jax.Array        # (n_top, R, d) flattened neighbor embeddings
    # quantized storage (repro.quant; None when the index is not quantized).
    # The quantized distance backends (ref_int8 | rowgather_int8 | ref_bf16)
    # gather from ``codes`` so the hot-path payload is 4x/2x smaller; the
    # f32 ``vectors`` stay the seeding + exact-re-ranking table.
    codes: Optional[jax.Array] = None    # (N, d) int8 | bfloat16
    scales: Optional[jax.Array] = None   # (N, 1) per-vector | (1, d) per-dim
    # the Pallas gather kernels' copies of ``vectors`` and of int8
    # ``codes`` in the row-tiled layout they read (kernels.platform.
    # row_tiles: (N, ceil(d/128), 128), zero-padded).  None until a
    # searcher with a Pallas backend is made (kernels.registry.
    # with_kernel_tables), which lays them out once, outside any jitted
    # call: for d > 128 the relayout is a copy of the whole table.
    vector_tiles: Optional[jax.Array] = None   # (N, k, 128) f32
    code_tiles: Optional[jax.Array] = None     # (N, k, 128) int8

    @property
    def n_nodes(self) -> int:
        return self.nbrs.shape[0]

    @property
    def degree(self) -> int:
        return self.nbrs.shape[1]

    @property
    def dim(self) -> int:
        return self.vectors.shape[1]


def make_padded_csr(
    nbrs: np.ndarray,
    vectors: np.ndarray,
    medoid: Optional[int] = None,
    n_top: int = 0,
    metric: str = "l2",
) -> PaddedCSR:
    """Build a PaddedCSR from host arrays; optionally flatten top vertices.

    ``nbrs`` rows may be ragged-padded with any value >= N or < 0; they are
    normalized to the sentinel N.  ``metric`` only affects the default
    medoid choice when ``medoid`` is None.
    """
    n, _ = nbrs.shape
    nbrs = nbrs.astype(np.int32)
    nbrs = np.where((nbrs < 0) | (nbrs >= n), n, nbrs)
    vectors = np.asarray(vectors)
    if medoid is None:
        medoid = int(compute_medoid(vectors, metric=metric))
    flat = _flatten_top(nbrs, vectors, n_top)
    return PaddedCSR(
        nbrs=jnp.asarray(nbrs),
        vectors=jnp.asarray(vectors),
        medoid=jnp.asarray(medoid, jnp.int32),
        n_top=int(n_top),
        flat=jnp.asarray(flat),
    )


def _flatten_top(nbrs: np.ndarray, vectors: np.ndarray, n_top: int) -> np.ndarray:
    """Materialize neighbor embeddings of the ``n_top`` hottest vertices."""
    r = nbrs.shape[1]
    d = vectors.shape[1]
    if n_top <= 0:
        return np.zeros((0, r, d), dtype=vectors.dtype)
    ids = nbrs[:n_top]                       # (n_top, R)
    safe = np.minimum(ids, vectors.shape[0] - 1)
    flat = vectors[safe]                     # (n_top, R, d)
    flat = np.where((ids < vectors.shape[0])[..., None], flat, np.inf)
    return flat.astype(vectors.dtype)


def compute_medoid(vectors: np.ndarray, metric: str = "l2",
                   alive: Optional[np.ndarray] = None) -> int:
    """Vertex closest to the dataset centroid (NSG's navigating node).

    For "ip" the navigating node is the vertex with the largest inner
    product against the centroid (the MIPS analog of "closest"); "cosine"
    callers pass pre-normalized vectors, where l2 and ip orderings agree.

    ``alive`` (optional (N,) bool mask) restricts both the centroid and the
    argmin/argmax to live vertices — the incremental-delete path re-elects a
    navigating node among survivors when the medoid is tombstoned.
    """
    v = np.asarray(vectors, np.float32)
    if alive is not None:
        alive = np.asarray(alive, bool)
        if not alive.any():
            raise ValueError("compute_medoid: no live vertices")
        centroid = v[alive].mean(axis=0)
        if metric == "ip":
            score = np.where(alive, v @ centroid, -np.inf)
            return int(np.argmax(score))
        d = np.where(alive, np.linalg.norm(v - centroid, axis=1), np.inf)
        return int(np.argmin(d))
    centroid = v.mean(axis=0)
    if metric == "ip":
        return int(np.argmax(v @ centroid))
    d = np.linalg.norm(v - centroid, axis=1)
    return int(np.argmin(d))


def remap_sentinels(nbrs: np.ndarray, old_n: int, new_n: int) -> np.ndarray:
    """Rewrite padding entries when the node count changes (incremental add).

    The padded-CSR sentinel is the node count itself, so growing a graph from
    ``old_n`` to ``new_n`` rows invalidates every ``old_n`` padding entry —
    it would alias the first inserted point.  Must run BEFORE the neighbor
    table is grown.  Returns a new array; out-of-range ids (>= old_n or < 0)
    all normalize to the new sentinel.
    """
    nbrs = np.asarray(nbrs, np.int32)
    return np.where((nbrs < 0) | (nbrs >= old_n),
                    np.int32(new_n), nbrs)


# ---------------------------------------------------------------------------
# Neighbor grouping (§4.4): vertex re-labelling strategies
# ---------------------------------------------------------------------------

def indegree_rank(nbrs: np.ndarray) -> np.ndarray:
    """Degree-centric ranking: permutation old_id -> rank (0 = hottest)."""
    n = nbrs.shape[0]
    flat = nbrs[nbrs < n]
    indeg = np.bincount(flat, minlength=n)
    order = np.argsort(-indeg, kind="stable")       # old ids, hottest first
    rank = np.empty(n, dtype=np.int64)
    rank[order] = np.arange(n)
    return rank


def frequency_rank(nbrs: np.ndarray, access_counts: np.ndarray) -> np.ndarray:
    """Frequency-centric ranking from measured query-time access counts."""
    n = nbrs.shape[0]
    order = np.argsort(-access_counts, kind="stable")
    rank = np.empty(n, dtype=np.int64)
    rank[order] = np.arange(n)
    return rank


def relabel(nbrs: np.ndarray, vectors: np.ndarray, rank: np.ndarray):
    """Apply a vertex re-labelling: new_id = rank[old_id].

    Returns (new_nbrs, new_vectors, old_from_new) — ``old_from_new`` maps the
    search results back to original ids.
    """
    n = nbrs.shape[0]
    old_from_new = np.argsort(rank, kind="stable")
    new_vectors = vectors[old_from_new]
    remap = np.concatenate([rank.astype(np.int64), [n]])  # sentinel maps to n
    safe = np.where((nbrs >= 0) & (nbrs <= n), nbrs, n)
    new_nbrs = remap[safe][:]
    new_nbrs = new_nbrs[old_from_new]
    return new_nbrs.astype(np.int32), new_vectors, old_from_new


def group_by_indegree(
    nbrs: np.ndarray,
    vectors: np.ndarray,
    medoid: Optional[int] = None,
    top_fraction: float = 0.001,
):
    """Full degree-centric neighbor-grouping pipeline (paper's default).

    Returns (PaddedCSR with flattened top level, old_from_new permutation).
    """
    rank = indegree_rank(nbrs)
    new_nbrs, new_vectors, old_from_new = relabel(nbrs, vectors, rank)
    n_top = max(1, int(round(nbrs.shape[0] * top_fraction)))
    if medoid is not None:
        medoid = int(rank[medoid])
    csr = make_padded_csr(new_nbrs, new_vectors, medoid=medoid, n_top=n_top)
    return csr, old_from_new


# ---------------------------------------------------------------------------
# Device-side neighbor-vector fetch (two-level)
# ---------------------------------------------------------------------------

def gather_neighbor_ids(graph: PaddedCSR, active_ids: jax.Array) -> jax.Array:
    """(..., M) active vertex ids -> (..., M, R) neighbor ids.

    Leading-dims agnostic: the batch-major engine passes (B, M) ids and gets
    all queries' neighbor rows in one gather; per-query callers pass (M,).
    Invalid/sentinel actives yield fully padded rows.
    """
    safe = jnp.minimum(active_ids, graph.n_nodes - 1)
    nbrs = graph.nbrs[safe]
    return jnp.where((active_ids < graph.n_nodes)[..., None], nbrs,
                     graph.n_nodes)


def fetch_neighbor_vectors(
    graph: PaddedCSR, active_ids: jax.Array, nbr_ids: jax.Array
) -> jax.Array:
    """Fetch (..., M, R, d) neighbor embeddings via the two-level layout.

    Leading-dims agnostic like :func:`gather_neighbor_ids` — the batch-major
    ``ref`` backend fetches a whole (B, M, R, d) expansion in one gather.
    Hot vertices (< n_top) read their flattened block (contiguous HBM burst);
    cold vertices gather rows from the embedding table.  Padding rows return
    +inf so downstream distances are +inf.
    """
    n = graph.n_nodes
    safe_nbr = jnp.minimum(nbr_ids, n - 1)
    gathered = graph.vectors[safe_nbr]                        # (..., M, R, d)
    gathered = jnp.where(
        (nbr_ids < n)[..., None], gathered,
        jnp.asarray(jnp.inf, gathered.dtype))
    if graph.n_top == 0:
        return gathered
    hot = active_ids < graph.n_top                            # (..., M)
    safe_act = jnp.clip(active_ids, 0, graph.n_top - 1)
    flat = graph.flat[safe_act]                               # (..., M, R, d)
    return jnp.where(hot[..., None, None], flat, gathered)


def top_level_hit_fraction(graph: PaddedCSR, active_ids: jax.Array) -> jax.Array:
    """Fraction of expansions served by the flattened top level (profiling)."""
    valid = active_ids < graph.n_nodes
    hits = (active_ids < graph.n_top) & valid
    return jnp.sum(hits) / jnp.maximum(jnp.sum(valid), 1)
