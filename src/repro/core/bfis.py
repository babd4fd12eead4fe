"""Best-first search (Algorithm 1) and single-queue top-M relaxation (§4.1).

``search_topm`` is the bulk-synchronous form of Speed-ANN's parallel neighbor
expansion: each step selects the top-M unchecked candidates from ONE shared
frontier and expands them simultaneously.  ``M=1`` is exactly the paper's
BFiS (the NSG/HNSW search kernel); larger M exposes path-wise parallelism;
``staged=True`` doubles M every ``stage_every`` steps (§4.2).

The full Algorithm 3 (private walker queues + redundant-expansion-aware lazy
synchronization) lives in ``speedann.py``; this module is both the baseline
and the building block.

**Batch-major engine.**  ``search_topm_batch`` runs ONE ``lax.while_loop``
over batch-leading state: ``Frontier``/``Visited``/``SearchStats`` all carry
a leading ``(B,)`` query axis and every global step issues a SINGLE distance
launch over the whole ``(B, M, R)`` expansion (the workload the Pallas
kernels amortize).  Converged queries are masked no-ops — the loop body's
new state is selected per lane against the lane's own liveness predicate,
which is exactly ``jax.vmap``'s batching rule for ``while_loop``, so the
batch-major path is bit-identical (ids, dists, stats) to vmapping the
per-query search.  The per-query entry points (``search_topm``,
``search_speedann``) remain as thin ``B=1`` wrappers.
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.config import SearchConfig
from repro.core import queue as fq
from repro.core import visited as vs
from repro.core.graph import (PaddedCSR, fetch_neighbor_vectors,
                              gather_neighbor_ids)
from repro.core.metrics import SearchStats, batch_unique_counts

# dist_fn(graph, active_ids (B, M), nbr_ids (B, M, R), queries (B, d))
# -> (B, M, R) distances, float32, smaller = closer, +inf for padded ids.
# BATCH-MAJOR contract: one call covers every query's expansion for the
# step — backends launch ONE kernel over the flattened (B, M·R) candidate
# grid instead of per-lane gathers.  The queries are float32; WHICH stored
# table a backend reads (f32 ``graph.vectors``, int8 ``graph.codes`` +
# ``graph.scales``, bf16 codes) and in what precision it accumulates is the
# backend's own business — the search algorithms only see the f32 result,
# so quantized and exact backends are interchangeable here.
DistFn = Callable[[PaddedCSR, jax.Array, jax.Array, jax.Array], jax.Array]


def resolve_dist_fn(cfg: SearchConfig,
                    dist_fn: Optional[DistFn] = None) -> DistFn:
    """An explicit ``dist_fn`` wins; otherwise ``cfg.dist_backend`` resolves
    through the kernel registry (``"ref" | "rowgather" | "dma"``)."""
    if dist_fn is not None:
        return dist_fn
    # import here so ref-only users never touch the Pallas import path
    from repro.kernels.registry import resolve_backend
    return resolve_backend(cfg)


def dist_l2(graph: PaddedCSR, active_ids: jax.Array, nbr_ids: jax.Array,
            queries: jax.Array) -> jax.Array:
    """Reference squared-L2 distance via the two-level vector fetch.

    Leading-dims agnostic: (B, M, R) batch-major ids with (B, d) queries,
    or (M, R) with (d,) for per-query callers."""
    vecs = fetch_neighbor_vectors(graph, active_ids, nbr_ids)
    diff = vecs.astype(jnp.float32) \
        - queries.astype(jnp.float32)[..., None, None, :]
    return jnp.sum(diff * diff, axis=-1)


def dist_ip(graph: PaddedCSR, active_ids: jax.Array, nbr_ids: jax.Array,
            queries: jax.Array) -> jax.Array:
    """Reference negative-inner-product distance (MIPS; cosine when the
    index vectors and query are pre-normalized).

    Padding rows of the two-level fetch are +inf, so the dot product is
    masked explicitly by neighbor validity instead of relying on the inf
    arithmetic (inf * 0 -> nan)."""
    vecs = fetch_neighbor_vectors(graph, active_ids, nbr_ids)
    d = -jnp.sum(vecs.astype(jnp.float32)
                 * queries.astype(jnp.float32)[..., None, None, :], axis=-1)
    return jnp.where(nbr_ids < graph.n_nodes, d, jnp.inf)


def make_ref_dist_fn(metric: str = "l2") -> DistFn:
    """Metric tag -> pure-jnp two-level batch-major DistFn ("cosine" == ip:
    the facade pre-normalizes base vectors and queries)."""
    if metric in ("ip", "cosine"):
        return dist_ip
    if metric == "l2":
        return dist_l2
    raise ValueError(f"unknown metric {metric!r}")


def point_dist(v: jax.Array, q: jax.Array, metric: str = "l2") -> jax.Array:
    """Point-to-query distance used to seed the search frontier.

    Leading-dims agnostic: (d,) vectors give a scalar, (B, d) give (B,)."""
    v = v.astype(jnp.float32)
    q = q.astype(jnp.float32)
    if metric in ("ip", "cosine"):
        return -jnp.sum(v * q, axis=-1)
    return jnp.sum((v - q) ** 2, axis=-1)


def lane_select(alive: jax.Array, new: NamedTuple, old: NamedTuple,
                scopes: Tuple[str, ...]):
    """Per-lane carry masking: where ``alive[b]`` take ``new``, else keep
    ``old`` — the ``jax.vmap`` while_loop batching rule, applied explicitly
    by the batch-major engine so converged queries are exact no-ops.  Each
    field of the carry is masked under the named scope of the phase that
    owns it (``scopes``, aligned with the fields)."""
    def sel(n, o):
        pred = alive.reshape(alive.shape + (1,) * (n.ndim - alive.ndim))
        return jnp.where(pred, n, o)
    out = []
    for field, scope in zip(new._fields, scopes, strict=True):
        with jax.named_scope(scope):
            out.append(jax.tree.map(sel, getattr(new, field),
                                    getattr(old, field)))
    return type(new)(*out)


def expand_batch(
    graph: PaddedCSR,
    queries: jax.Array,
    frontier: fq.Frontier,
    visited: vs.Visited,
    m_max: int,
    m: jax.Array | int,
    dist_fn: DistFn = dist_l2,
    lane_mask: Optional[jax.Array] = None,
) -> Tuple[fq.Frontier, vs.Visited, jax.Array, jax.Array, jax.Array]:
    """One batch-major neighbor-expansion round (Algorithm 1 lines 6–13,
    width m, all B queries at once).

    ``frontier``/``visited`` carry a leading (B,) axis; ``m`` may be scalar
    or per-query (B,).  The ONLY cross-lane fusion is the distance call:
    one ``dist_fn`` launch covers the whole (B, m_max, R) candidate grid.
    Returns (frontier', visited', update_positions (B,), n_comps (B,),
    n_uniq (B,)) where ``n_uniq`` is the first-toucher count feeding
    ``SearchStats.uniq_comps`` — fresh candidates whose id no lower-index
    lane expands this round.  ``lane_mask`` (B,) bool excludes lanes whose
    state the caller will discard (converged/step-budget-dead lanes still
    ride in the batch as no-op work, but they must not claim first-toucher
    credit away from live lanes — the counters stay exact and front-slice
    invariant).
    """
    bsz = queries.shape[0]
    with jax.named_scope("ann.select"):
        frontier, active_ids, active_valid = fq.select_unchecked_batch(
            frontier, m_max, m)
    with jax.named_scope("ann.neighbors"):
        nbrs = gather_neighbor_ids(graph, active_ids)      # (B, m_max, R)
        flat = nbrs.reshape(bsz, -1)
        valid = (flat < graph.n_nodes) \
            & jnp.repeat(active_valid, graph.degree, axis=-1)
    with jax.named_scope("ann.visited"):
        visited, fresh = vs.check_and_insert_batch(visited, flat, valid)
    with jax.named_scope("ann.distance"):
        # the frontier stores f32 keys; normalize here so a backend that
        # reduces in another precision (int32-accumulated int8, bf16) can't
        # leak its accumulator dtype into the queue
        dists = dist_fn(graph, active_ids, nbrs, queries).astype(
            jnp.float32).reshape(bsz, -1)
    with jax.named_scope("ann.queue"):
        dists = jnp.where(fresh, dists, jnp.inf)
        cand_ids = jnp.where(fresh, flat, fq.INVALID_ID)
        frontier, up_pos, _ = fq.insert_batch(frontier, cand_ids, dists)
    with jax.named_scope("ann.counters"):
        counted = fresh if lane_mask is None \
            else fresh & lane_mask[:, None]
        n_uniq = batch_unique_counts(flat, counted)
        n_comps = jnp.sum(fresh, axis=-1).astype(jnp.int32)
    return frontier, visited, up_pos, n_comps, n_uniq


def expand(
    graph: PaddedCSR,
    q: jax.Array,
    frontier: fq.Frontier,
    visited: vs.Visited,
    m_max: int,
    m: jax.Array | int,
    dist_fn: DistFn = dist_l2,
) -> Tuple[fq.Frontier, vs.Visited, jax.Array, jax.Array]:
    """Per-query expansion round (the ``core.distributed`` walker building
    block): lifts the query to a B=1 batch for the batch-major ``dist_fn``.

    Returns (frontier', visited', update_position, n_distance_comps).
    A single lane has no cross-lane overlap (uniq == comps), so no
    first-toucher count is returned here.
    """
    frontier, active_ids, active_valid = fq.select_unchecked(
        frontier, m_max, m)
    nbrs = gather_neighbor_ids(graph, active_ids)          # (m_max, R)
    flat = nbrs.reshape(-1)
    valid = (flat < graph.n_nodes) & jnp.repeat(active_valid, graph.degree)
    visited, fresh = vs.check_and_insert(visited, flat, valid)
    dists = dist_fn(graph, active_ids[None], nbrs[None], q[None])[0]
    dists = dists.astype(jnp.float32).reshape(-1)
    dists = jnp.where(fresh, dists, jnp.inf)
    cand_ids = jnp.where(fresh, flat, fq.INVALID_ID)
    frontier, up_pos, _ = fq.insert(frontier, cand_ids, dists)
    return frontier, visited, up_pos, jnp.sum(fresh).astype(jnp.int32)


class _TopMState(NamedTuple):
    frontier: fq.Frontier     # leaves (B, L)
    visited: vs.Visited       # table (B, ...)
    stats: SearchStats        # leaves (B,)


def _seed_ids(graph: PaddedCSR, start: Optional[jax.Array],
              batch: int) -> jax.Array:
    """(B,) int32 traversal entry points: the medoid (build-time entry
    policy, e.g. MIPS max-norm — see ``IndexSpec.entry_policy``) unless the
    caller provides per-query starts."""
    if start is None:
        return jnp.broadcast_to(
            jnp.asarray(graph.medoid, jnp.int32), (batch,))
    return jnp.broadcast_to(jnp.asarray(start, jnp.int32), (batch,))


def _init_state_batch(
    graph: PaddedCSR, queries: jax.Array, cfg: SearchConfig,
    start: Optional[jax.Array],
) -> _TopMState:
    """Batch-major initial state for (B, d) queries: frontier (B, L),
    visited (B, ...), stats leaves (B,), seeded at the entry point."""
    bsz = queries.shape[0]
    with jax.named_scope("ann.queue"):
        frontier = fq.make_frontier_batch(cfg.queue_len, bsz)
    with jax.named_scope("ann.visited"):
        visited = vs.make_visited_batch(cfg.visited_mode, graph.n_nodes,
                                        bsz, cfg.hash_bits)
    with jax.named_scope("ann.select"):
        s = _seed_ids(graph, start, bsz)
    with jax.named_scope("ann.visited"):
        visited, _ = vs.check_and_insert_batch(
            visited, s[:, None], jnp.ones((bsz, 1), bool))
    with jax.named_scope("ann.distance"):
        v = graph.vectors[s].astype(jnp.float32)           # (B, d)
        d0 = point_dist(v, queries, cfg.metric)[:, None]
    with jax.named_scope("ann.queue"):
        frontier, _, _ = fq.insert_batch(frontier, s[:, None], d0)
    with jax.named_scope("ann.counters"):
        # the seed computation participates in first-toucher accounting
        # too: a shared entry point (the medoid) is the batch's first
        # overlapping row
        seed_uniq = batch_unique_counts(s[:, None],
                                        jnp.ones((bsz, 1), bool))
        stats = SearchStats.zero_batch(bsz)._replace(
            dist_comps=jnp.ones((bsz,), jnp.int32),
            uniq_comps=seed_uniq,
            batch_dup_comps=jnp.int32(1) - seed_uniq)
    return _TopMState(frontier, visited, stats)


def staged_m(step: jax.Array, cfg: SearchConfig) -> jax.Array:
    """§4.2 staging function: M doubles every ``stage_every`` steps.

    Elementwise — a (B,) step vector yields per-query widths."""
    if not cfg.staged:
        return jnp.broadcast_to(jnp.int32(cfg.m_max), jnp.shape(step))
    expo = jnp.minimum(step // cfg.stage_every, 30).astype(jnp.int32)
    return jnp.minimum(jnp.left_shift(jnp.int32(1), expo),
                       jnp.int32(cfg.m_max))


def _run_topm_batch(
    graph: PaddedCSR,
    queries: jax.Array,
    cfg: SearchConfig,
    start: Optional[jax.Array] = None,
    dist_fn: Optional[DistFn] = None,
) -> _TopMState:
    """Run the batch-major top-M loop to convergence; returns the final
    state (frontier + visited + stats), from which the public entry points
    slice their results."""
    dist_fn = resolve_dist_fn(cfg, dist_fn)
    st = _init_state_batch(graph, queries, cfg, start)

    def lanes_live(s: _TopMState) -> jax.Array:
        with jax.named_scope("ann.queue"):
            return fq.has_unchecked_batch(s.frontier) \
                & (s.stats.steps < cfg.max_steps)

    def cond(s: _TopMState):
        return jnp.any(lanes_live(s))

    def body(s: _TopMState):
        alive = lanes_live(s)
        with jax.named_scope("ann.queue"):
            live = fq.has_unchecked_batch(s.frontier).astype(jnp.int32)
        with jax.named_scope("ann.select"):
            m = staged_m(s.stats.steps, cfg)
        frontier, visited, _, n, uniq = expand_batch(
            graph, queries, s.frontier, s.visited, cfg.m_max, m, dist_fn,
            lane_mask=alive)
        with jax.named_scope("ann.counters"):
            stats = s.stats._replace(
                steps=s.stats.steps + live,
                local_steps=s.stats.local_steps
                + jnp.minimum(m, jnp.int32(cfg.m_max)) * live,
                dist_comps=s.stats.dist_comps + n,
                uniq_comps=s.stats.uniq_comps + uniq,
                batch_dup_comps=s.stats.batch_dup_comps + (n - uniq),
                crit_rounds=s.stats.crit_rounds + live,
            )
        return lane_select(
            alive, _TopMState(frontier, visited, stats), s,
            ("ann.queue", "ann.visited", "ann.counters"))

    # the loop's own control (its while op, the carry copies XLA inserts)
    # under ann.loop; the phases inside keep their own scopes
    with jax.named_scope("ann.loop"):
        return jax.lax.while_loop(cond, body, st)


def search_topm_batch(
    graph: PaddedCSR,
    queries: jax.Array,
    cfg: SearchConfig,
    start: Optional[jax.Array] = None,
    dist_fn: Optional[DistFn] = None,
) -> Tuple[jax.Array, jax.Array, SearchStats]:
    """Batch-major single-queue top-M search over a (B, d) query batch.

    One ``lax.while_loop`` advances every query per iteration (ONE distance
    launch per global step for the whole batch); converged lanes are masked
    no-ops, so per-query counters stay exact and results are bit-identical
    to vmapping :func:`search_topm`.  ``cfg.m_max == 1`` reproduces BFiS /
    Algorithm 1 exactly.  Returns (ids (B, k), dists (B, k), stats (B,)).
    """
    st = _run_topm_batch(graph, queries, cfg, start, dist_fn)
    with jax.named_scope("ann.queue"):
        ids, dists = fq.results_batch(st.frontier, cfg.k)
    return ids, dists, st.stats


def search_topm_batch_visited(
    graph: PaddedCSR,
    queries: jax.Array,
    cfg: SearchConfig,
    start: Optional[jax.Array] = None,
    dist_fn: Optional[DistFn] = None,
) -> Tuple[jax.Array, jax.Array, SearchStats, jax.Array]:
    """:func:`search_topm_batch` that ALSO returns the per-lane visited set
    as a (B, N) bool mask (requires ``cfg.visited_mode == "bitmap"``).

    The visited set — every vertex whose distance the traversal evaluated,
    not just the k survivors — is Vamana's robust-prune candidate pool V:
    it contains the far-out vertices along the entry→neighborhood descent
    path, whose pruned survivors become the graph's long-range edges.  The
    batched builder (``core.build``) is the consumer.  Per-lane content is
    batch-invariant like the results themselves.
    """
    if cfg.visited_mode != "bitmap":
        raise ValueError(
            "search_topm_batch_visited needs visited_mode='bitmap' (the "
            f"(B, N) mask IS the visited set); got {cfg.visited_mode!r}")
    st = _run_topm_batch(graph, queries, cfg, start, dist_fn)
    ids, dists = fq.results_batch(st.frontier, cfg.k)
    # the traversal keeps one bit per vertex; the mask exists only here
    return ids, dists, st.stats, vs.unpack(st.visited, graph.n_nodes)


def search_topm(
    graph: PaddedCSR,
    q: jax.Array,
    cfg: SearchConfig,
    start: Optional[jax.Array] = None,
    dist_fn: Optional[DistFn] = None,
) -> Tuple[jax.Array, jax.Array, SearchStats]:
    """Single-query top-M search — a thin B=1 wrapper over the batch-major
    engine.  Returns (ids (k,), dists (k,), stats).
    """
    start_b = None if start is None \
        else jnp.asarray(start, jnp.int32).reshape(1)
    ids, dists, stats = search_topm_batch(
        graph, q[None, :], cfg, start=start_b, dist_fn=dist_fn)
    return ids[0], dists[0], jax.tree.map(lambda t: t[0], stats)


def bfis_search_batch(graph, queries, cfg: SearchConfig, **kw):
    """Algorithm 1 (the NSG baseline): top-M search with M=1, no staging,
    batch-major over (B, d) queries -> (ids (B, k), dists (B, k),
    stats (B,))."""
    return search_topm_batch(
        graph, queries, cfg.with_(m_max=1, staged=False), **kw)


# ---------------------------------------------------------------------------
# HNSW-style hierarchical search (the paper's second baseline)
# ---------------------------------------------------------------------------

def greedy_descent(
    level_nbrs: jax.Array, vectors: jax.Array, entry: jax.Array,
    q: jax.Array, max_hops: int = 64, metric: str = "l2",
) -> jax.Array:
    """Greedy walk on one upper level: hop to the closest neighbor until a
    local minimum (HNSW's ef=1 upper-level search)."""
    n = vectors.shape[0]
    qf = q.astype(jnp.float32)

    def dist_of(i):
        v = vectors[jnp.minimum(i, n - 1)].astype(jnp.float32)
        return jnp.where(i < n, point_dist(v, qf, metric), jnp.inf)

    def cond(carry):
        cur, cur_d, moved, hops = carry
        return moved & (hops < max_hops)

    def body(carry):
        cur, cur_d, _, hops = carry
        nb = level_nbrs[cur]                        # (R_l,)
        vecs = vectors[jnp.minimum(nb, n - 1)].astype(jnp.float32)
        if metric in ("ip", "cosine"):
            d = -jnp.sum(vecs * qf[None, :], axis=-1)
        else:
            d = jnp.sum((vecs - qf[None, :]) ** 2, axis=-1)
        d = jnp.where(nb < n, d, jnp.inf)
        j = jnp.argmin(d)
        better = d[j] < cur_d
        return (jnp.where(better, nb[j], cur),
                jnp.where(better, d[j], cur_d),
                better, hops + 1)

    cur, _, _, _ = jax.lax.while_loop(
        cond, body, (entry, dist_of(entry), jnp.bool_(True), jnp.int32(0)))
    return cur


def hnsw_search_batch(index, queries: jax.Array, cfg: SearchConfig,
                      dist_fn: Optional[DistFn] = None):
    """HNSW baseline: greedy descent through upper levels, then the
    batch-major BFiS at level 0 (per-query entry points ride in as
    ``start``)."""
    base = index.base

    def one(q):
        cur = jnp.asarray(index.entry, jnp.int32)
        for lvl in range(len(index.level_nbrs) - 1, -1, -1):
            cur = greedy_descent(index.level_nbrs[lvl], base.vectors, cur, q,
                                 metric=cfg.metric)
        return cur

    starts = jax.vmap(one)(queries)
    return search_topm_batch(
        base, queries, cfg.with_(m_max=1, staged=False), start=starts,
        dist_fn=dist_fn)
