"""Speed-ANN intra-query parallel search — Algorithm 3 + §4.2/§4.3/§4.4.

Structure of one *global step* (outer loop iteration):

  1. scatter: the global queue's unchecked candidates are divided
     round-robin among the ``M`` active walkers (staged: M doubles every
     ``stage_every`` global steps up to ``num_walkers``);
  2. local search: every walker runs a private best-first search on its own
     bounded queue — no communication with other walkers (collective-free on
     TPU; lock-free on CPU in the paper);
  3. CheckMetrics (Algorithm 2): after each local round the mean *update
     position* ū over active walkers is compared against ``L·R``; when
     ū ≥ L·R (walkers inserting only near the queue tail ⇒ searching
     unpromising regions) a merge is triggered;
  4. merge: local queues collapse into the global queue (dedup, prefer
     checked); walker visited maps are OR-merged ("eventual consistency",
     §4.4); counters accumulate.

**Batch-major engine.**  ``search_speedann_batch`` runs the whole (B, d)
query batch through ONE outer ``lax.while_loop``: frontiers are (B, L),
walker queues (B, W, L), visited maps (B, W, ...), stats (B,).  Each local
round flattens the (B, W) walker lanes into the batch axis of the distance
backend, so ALL queries' walker expansions are ONE kernel launch.  Converged
queries are masked no-ops (per-lane carry select — exactly ``jax.vmap``'s
while_loop rule), so the batch-major path is bit-identical to vmapping the
per-query search and per-query counters stay exact.  ``search_speedann``
remains as a thin B=1 wrapper.

Walkers here are *vmapped lanes on one device*; ``core.distributed`` lifts
the same step functions onto a ``shard_map`` walker mesh axis where the merge
becomes an ``all_gather`` and CheckMetrics a scalar ``psum``.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.config import SearchConfig
from repro.core import queue as fq
from repro.core import visited as vs
from repro.core.bfis import (DistFn, _seed_ids, expand_batch, lane_select,
                             point_dist, resolve_dist_fn, staged_m)
from repro.core.metrics import SearchStats, batch_unique_counts


class _LocalState(NamedTuple):
    locals_: fq.Frontier      # (B, W, L) private walker queues
    visited: vs.Visited       # (B, W, ...) private visited maps
    up_pos: jax.Array         # (B, W) latest update positions
    lstep: jax.Array          # (B,) local rounds taken this segment
    do_merge: jax.Array       # (B,) bool — CheckMetrics flag
    comps: jax.Array          # (B,) distance computations this segment
    uniq: jax.Array           # (B,) first-toucher comps this segment (over
    #                           the whole flattened B·W walker grid — the
    #                           rows a batch-dedup backend would gather)


class _GlobalState(NamedTuple):
    frontier: fq.Frontier     # (B, L) global queue S
    visited: vs.Visited       # (B, W, ...) walker visited maps (persist)
    stats: SearchStats        # leaves (B,)


def check_metrics(up_pos: jax.Array, active: jax.Array, cfg: SearchConfig
                  ) -> jax.Array:
    """Algorithm 2: ū ≥ L·R over the ``active`` lowest-index walkers."""
    w = up_pos.shape[0]
    is_active = jnp.arange(w) < active
    u_bar = (jnp.sum(jnp.where(is_active, up_pos, 0))
             / jnp.maximum(jnp.sum(is_active), 1))
    return u_bar >= cfg.queue_len * cfg.sync_ratio


def _local_segment_batch(
    graph, queries: jax.Array, locals_: fq.Frontier, visited: vs.Visited,
    active: jax.Array, cfg: SearchConfig, dist_fn: DistFn,
    query_mask: Optional[jax.Array] = None,
) -> Tuple[fq.Frontier, vs.Visited, jax.Array, jax.Array, jax.Array]:
    """Lines 11–22 batch-major: collective-free private best-first searches
    for every query's walker pool at once.

    Each local round flattens the (B, W) walker lanes into one (B·W,)
    batch-major expansion — ONE distance launch for the whole batch's
    walkers.  Per query, the segment runs until CheckMetrics fires, every
    walker exhausts its queue, or the ``local_steps`` budget is hit;
    finished queries are masked no-ops.  ``query_mask`` (B,) excludes
    queries whose state the caller discards from first-toucher accounting
    (see ``expand_batch``).  Returns (locals', visited', rounds (B,),
    comps (B,), uniq (B,))."""
    w = cfg.num_walkers
    cap = cfg.queue_len
    bsz = queries.shape[0]
    with jax.named_scope("ann.distance"):
        q_rep = jnp.repeat(queries, w, axis=0)             # (B·W, d)

    def flatten_bw(t):
        return t.reshape((bsz * w,) + t.shape[2:])

    def unflatten_bw(t):
        return t.reshape((bsz, w) + t.shape[1:])

    def is_active_mask():
        return jnp.arange(w)[None, :] < active[:, None]    # (B, W)

    def lanes_live(s: _LocalState) -> jax.Array:
        with jax.named_scope("ann.queue"):
            any_work = jnp.any(
                fq.has_unchecked_batch(s.locals_) & is_active_mask(),
                axis=-1)
            return (~s.do_merge) & any_work & (s.lstep < cfg.local_steps)

    def cond(s: _LocalState):
        return jnp.any(lanes_live(s))

    def body(s: _LocalState):
        alive = lanes_live(s)
        with jax.named_scope("ann.queue"):
            counted_q = alive if query_mask is None \
                else alive & query_mask
            had_work = fq.has_unchecked_batch(s.locals_) & is_active_mask()
            # ONE batch-major expansion over all B·W walker lanes (M=1 each)
            fr = jax.tree.map(flatten_bw, s.locals_)
        with jax.named_scope("ann.visited"):
            vis = jax.tree.map(flatten_bw, s.visited)
        with jax.named_scope("ann.counters"):
            lane_mask = jnp.repeat(counted_q, w)
        fr, vis, up, n, uniq = expand_batch(
            graph, q_rep, fr, vis, 1, 1, dist_fn, lane_mask=lane_mask)
        with jax.named_scope("ann.queue"):
            locals2 = jax.tree.map(unflatten_bw, fr)
        with jax.named_scope("ann.visited"):
            visited2 = jax.tree.map(unflatten_bw, vis)
        with jax.named_scope("ann.queue"):
            up = up.reshape(bsz, w)
        with jax.named_scope("ann.counters"):
            n = n.reshape(bsz, w)
            uniq = uniq.reshape(bsz, w)
        with jax.named_scope("ann.queue"):
            # walkers with no unchecked candidates saturate at L (stuck)
            up = jnp.where(had_work, up, cap).astype(jnp.int32)
            # Algorithm 2 on the walkers' update positions
            do_merge = jax.vmap(
                lambda u, a: check_metrics(u, a, cfg))(up, active)
            lstep = s.lstep + 1
        with jax.named_scope("ann.counters"):
            comps = s.comps + jnp.sum(jnp.where(had_work, n, 0), axis=-1)
            uniq = s.uniq + jnp.sum(jnp.where(had_work, uniq, 0), axis=-1)
        new = _LocalState(
            locals_=locals2, visited=visited2, up_pos=up,
            lstep=lstep, do_merge=do_merge, comps=comps, uniq=uniq)
        return lane_select(
            alive, new, s,
            ("ann.queue", "ann.visited", "ann.queue", "ann.queue",
             "ann.queue", "ann.counters", "ann.counters"))

    init = _LocalState(
        locals_=locals_, visited=visited,
        up_pos=jnp.zeros((bsz, w), jnp.int32),
        lstep=jnp.zeros((bsz,), jnp.int32),
        do_merge=jnp.zeros((bsz,), bool),
        comps=jnp.zeros((bsz,), jnp.int32),
        uniq=jnp.zeros((bsz,), jnp.int32))
    with jax.named_scope("ann.loop"):
        out = jax.lax.while_loop(cond, body, init)
    return out.locals_, out.visited, out.lstep, out.comps, out.uniq


def search_speedann_batch(
    graph,
    queries: jax.Array,
    cfg: SearchConfig,
    start: Optional[jax.Array] = None,
    dist_fn: Optional[DistFn] = None,
) -> Tuple[jax.Array, jax.Array, SearchStats]:
    """Batch-major Speed-ANN (Algorithm 3) over a (B, d) query batch.

    Returns (ids (B, k), dists (B, k), stats (B,)); bit-identical to
    vmapping :func:`search_speedann` over the batch.
    """
    dist_fn = resolve_dist_fn(cfg, dist_fn)
    w, cap = cfg.num_walkers, cfg.queue_len
    bsz = queries.shape[0]

    with jax.named_scope("ann.queue"):
        frontier = fq.make_frontier_batch(cap, bsz)
    with jax.named_scope("ann.visited"):
        visited0 = vs.make_visited_batch(cfg.visited_mode, graph.n_nodes,
                                         bsz, cfg.hash_bits)
    with jax.named_scope("ann.select"):
        s0 = _seed_ids(graph, start, bsz)
    with jax.named_scope("ann.visited"):
        visited0, _ = vs.check_and_insert_batch(
            visited0, s0[:, None], jnp.ones((bsz, 1), bool))
    with jax.named_scope("ann.distance"):
        v0 = graph.vectors[s0].astype(jnp.float32)
        d0 = point_dist(v0, queries, cfg.metric)[:, None]
    with jax.named_scope("ann.queue"):
        frontier, _, _ = fq.insert_batch(frontier, s0[:, None], d0)
    # Expand the starting point once before dividing work, so the first
    # scatter has a full frontier to distribute (paper Fig. 4: the search
    # fans out from P's neighbors; without this, NoSync would degenerate to
    # a single busy walker).
    frontier, visited0, _, n0, uniq0 = expand_batch(
        graph, queries, frontier, visited0, 1, 1, dist_fn)
    # replicate the seed visited map to all walkers (consistent at t=0)
    with jax.named_scope("ann.visited"):
        visited = jax.tree.map(
            lambda t: jnp.broadcast_to(t[:, None], (bsz, w) + t.shape[1:]),
            visited0)

    with jax.named_scope("ann.counters"):
        seed_uniq = batch_unique_counts(s0[:, None],
                                        jnp.ones((bsz, 1), bool))
        init = _GlobalState(
            frontier=frontier, visited=visited,
            stats=SearchStats.zero_batch(bsz)._replace(
                dist_comps=jnp.int32(1) + n0,
                uniq_comps=seed_uniq + uniq0,
                batch_dup_comps=(jnp.int32(1) - seed_uniq) + (n0 - uniq0)))

    def lanes_live(s: _GlobalState) -> jax.Array:
        with jax.named_scope("ann.queue"):
            return fq.has_unchecked_batch(s.frontier) \
                & (s.stats.steps < cfg.max_steps)

    def cond(s: _GlobalState):
        return jnp.any(lanes_live(s))

    def body(s: _GlobalState):
        # invariant: s.visited is OR-merged (all walkers agree) on entry
        alive = lanes_live(s)
        with jax.named_scope("ann.queue"):
            live = fq.has_unchecked_batch(s.frontier).astype(jnp.int32)
        with jax.named_scope("ann.select"):
            m = jnp.minimum(staged_m(s.stats.steps, cfg).astype(jnp.int32),
                            w)
        with jax.named_scope("ann.visited"):
            union_before = jax.vmap(vs.popcount)(s.visited)
        with jax.named_scope("ann.select"):
            # Line 7: divide unchecked candidates among active walkers.
            locals_ = jax.vmap(
                lambda f, a: fq.scatter_round_robin(f, w, a))(s.frontier, m)
        # Lines 11–22: collective-free local searches + CheckMetrics.
        locals_, visited, rounds, comps, uniq = _local_segment_batch(
            graph, queries, locals_, s.visited, m, cfg, dist_fn,
            query_mask=alive)
        # Line 23: merge local queues into the global queue; §4.4: visited
        # maps reach eventual consistency here.
        with jax.named_scope("ann.queue"):
            merged, _ = jax.vmap(fq.merge_frontiers)(locals_)
        with jax.named_scope("ann.visited"):
            visited = jax.vmap(vs.merge_visited)(visited)
            union_after = jax.vmap(vs.popcount)(visited)
        with jax.named_scope("ann.counters"):
            # cross-walker duplicate computations = work minus union growth
            n_dups = comps - (union_after - union_before)
            stats = s.stats._replace(
                steps=s.stats.steps + live,
                local_steps=s.stats.local_steps + rounds * m,
                dist_comps=s.stats.dist_comps + comps,
                dup_comps=s.stats.dup_comps + jnp.maximum(n_dups, 0),
                syncs=s.stats.syncs + live,
                crit_rounds=s.stats.crit_rounds + rounds,
                uniq_comps=s.stats.uniq_comps + uniq,
                batch_dup_comps=s.stats.batch_dup_comps + (comps - uniq),
            )
        return lane_select(
            alive, _GlobalState(frontier=merged, visited=visited,
                                stats=stats), s,
            ("ann.queue", "ann.visited", "ann.counters"))

    with jax.named_scope("ann.loop"):
        out = jax.lax.while_loop(cond, body, init)
    with jax.named_scope("ann.queue"):
        ids, dists = fq.results_batch(out.frontier, cfg.k)
    return ids, dists, out.stats


def search_speedann(
    graph,
    q: jax.Array,
    cfg: SearchConfig,
    start: Optional[jax.Array] = None,
    dist_fn: Optional[DistFn] = None,
) -> Tuple[jax.Array, jax.Array, SearchStats]:
    """Full Speed-ANN search for one query — a thin B=1 wrapper over the
    batch-major engine."""
    start_b = None if start is None \
        else jnp.asarray(start, jnp.int32).reshape(1)
    ids, dists, stats = search_speedann_batch(
        graph, q[None, :], cfg, start=start_b, dist_fn=dist_fn)
    return ids[0], dists[0], jax.tree.map(lambda t: t[0], stats)


# Named ablation variants (§5.3) ------------------------------------------

def variant(cfg: SearchConfig, name: str) -> SearchConfig:
    """The paper's §5.3 configurations."""
    if name == "bfis":               # NSG baseline
        return cfg.with_(m_max=1, num_walkers=1, staged=False)
    if name == "edge_parallel":      # NSG-32T: one global candidate per
        # step (M=1), but its edge expansion is spread across ALL walkers —
        # unlike "bfis" the walker pool is kept, so the §5.3 ablation
        # separates edge parallelism from path parallelism.
        return cfg.with_(m_max=1, staged=False)
    if name == "nostaged":           # Speed-ANN-NoStaged: fixed M=W
        return cfg.with_(staged=False)
    if name == "nosync":             # Speed-ANN-NoSync: all workers start at
        # once, search independently, merge only at the end (§5.3 (iii))
        return cfg.with_(staged=False, sync_ratio=2.0,
                         local_steps=cfg.max_steps)
    if name == "adaptive":           # Speed-ANN-Adaptive (the paper's method)
        return cfg
    raise ValueError(name)
