"""Distributed Speed-ANN on a device mesh via ``shard_map``.

Two orthogonal distribution modes, composable on a ("data", "model") mesh:

* **walker sharding** (the paper's intra-query parallelism, cross-device):
  the query batch is sharded over ``data``; each device along ``model`` is
  one Speed-ANN *walker* holding a private frontier and visited map over a
  replicated graph.  Walker expansions use the per-query ``core.bfis.expand``
  (which lifts each call to a B=1 batch of the batch-major ``DistFn``);
  corpus shards run the full batch-major engine on their local query slice.  A global round = scatter (replicated global queue,
  owner = axis_index) → collective-free local segment → CheckMetrics (one
  scalar ``psum`` per local round — the lazy-synchronization trigger) →
  merge (``all_gather`` of local frontiers + dedup + top-L; visited maps
  OR-reduced).  Between merges there are NO collectives: the paper's
  "workers searching asynchronously without global queue contention".

* **corpus sharding** (billion-scale practicality, §5.5): the dataset is
  partitioned; each ``model`` device owns one partition with its own
  sub-index and searches it independently; final answers are the global
  top-K over an ``all_gather`` of per-shard top-K lists.  Walker and corpus
  sharding compose (walkers within a shard) for multi-pod meshes.

The distributed outer loop uses a STATIC round budget (``global_rounds``)
instead of a data-dependent while: bounded rounds ⇒ bounded, deterministic
tail latency (the serving-side straggler-mitigation policy; converged
queries no-op and counters stay exact).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.config import SearchConfig
from repro.core import queue as fq
from repro.core import visited as vs
from repro.core.bfis import (DistFn, expand, point_dist, resolve_dist_fn,
                             staged_m)
from repro.core.graph import PaddedCSR
from repro.core.metrics import SearchStats
from repro.kernels.registry import with_kernel_tables


def shard_map(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking off (stats leaves are
    reduced to uniform values manually)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def make_search_mesh(shape, names=("data", "model")) -> Mesh:
    """A device mesh for the search paths (explicit Auto axis types)."""
    names = tuple(names)
    return jax.make_mesh(tuple(shape), names,
                         axis_types=(jax.sharding.AxisType.Auto,)
                         * len(names))


# ---------------------------------------------------------------------------
# Walker-sharded Speed-ANN
# ---------------------------------------------------------------------------

def _scatter_share(f: fq.Frontier, walker: jax.Array, active: jax.Array
                   ) -> fq.Frontier:
    """This walker's share of the replicated global queue (Line 7).

    Equivalent to ``queue.scatter_round_robin(...)[walker]`` but computed
    locally from the replica — no communication.
    """
    unchecked = ~f.checked & (f.ids != fq.INVALID_ID)
    ranks = jnp.cumsum(unchecked.astype(jnp.int32)) - 1
    owner = jnp.where(unchecked, ranks % jnp.maximum(active, 1), -1)
    keep = (owner == walker) & (walker < active)
    shared = f.checked & (f.ids != fq.INVALID_ID)
    ids = jnp.where(keep | shared, f.ids, fq.INVALID_ID)
    dists = jnp.where(keep | shared, f.dists, fq.INF)
    checked = jnp.where(keep, False, True)
    dists, ids, checked8 = jax.lax.sort(
        (dists, ids, checked.astype(jnp.int32)), num_keys=2, is_stable=True)
    return fq.Frontier(ids=ids, dists=dists,
                       checked=(checked8 == 1) | (ids == fq.INVALID_ID))


def _merge_all_walkers(local: fq.Frontier, axis: str) -> fq.Frontier:
    """Line 23 across devices: all_gather local queues, dedup, top-L."""
    stacked = jax.tree.map(
        functools.partial(jax.lax.all_gather, axis_name=axis), local)
    merged, _ = fq.merge_frontiers(stacked)
    return merged


def _reduce_visited(v: vs.Visited, axis: str) -> vs.Visited:
    """§4.4 eventual consistency across devices at a sync point."""
    if v.mode_bitmap:
        table = jax.lax.pmax(v.table.astype(jnp.uint8), axis) > 0
        return v._replace(table=table)
    if v.mask == 0:
        return v
    tables = jax.lax.all_gather(v.table, axis)        # (W, size)

    def fold(acc, t):
        take = (acc == jnp.int32(-1)) & (t != jnp.int32(-1))
        return jnp.where(take, t, acc), None

    merged, _ = jax.lax.scan(fold, tables[0], tables[1:])
    return v._replace(table=merged)


def walker_sharded_search(
    graph: PaddedCSR,
    queries: jax.Array,
    cfg: SearchConfig,
    mesh: Mesh,
    data_axis: str = "data",
    walker_axis: str = "model",
    dist_fn: Optional[DistFn] = None,
) -> Tuple[jax.Array, jax.Array, SearchStats]:
    """Speed-ANN with one walker per device along ``walker_axis``.

    queries: (B, d) global batch, B divisible by mesh.shape[data_axis].
    Returns (ids (B,k), dists (B,k), stats batched over B).
    """
    dist_fn = resolve_dist_fn(cfg, dist_fn)
    n_walkers = int(mesh.shape[walker_axis])
    n_top, n_nodes = graph.n_top, graph.n_nodes

    def per_query(nbrs, vectors, medoid, flat, tiles, q, walker):
        g = PaddedCSR(nbrs=nbrs, vectors=vectors, medoid=medoid,
                      n_top=n_top, flat=flat, vector_tiles=tiles)
        cap = cfg.queue_len
        frontier = fq.make_frontier(cap)
        visited = vs.make_visited(cfg.visited_mode, n_nodes, cfg.hash_bits)
        visited, _ = vs.check_and_insert(
            visited, medoid[None], jnp.ones((1,), bool))
        v0 = vectors[medoid].astype(jnp.float32)
        d0 = point_dist(v0, q, cfg.metric)[None]
        frontier, _, _ = fq.insert(frontier, medoid[None], d0)
        frontier, visited, _, n0 = expand(g, q, frontier, visited, 1, 1,
                                          dist_fn)
        stats = SearchStats.zero()._replace(dist_comps=1 + n0)

        def round_(r, carry):
            frontier, visited, stats = carry
            live = fq.has_unchecked(frontier)
            m = jnp.minimum(staged_m(stats.steps, cfg), n_walkers)
            local = _scatter_share(frontier, walker, m)
            union_before = vs.popcount(visited)

            def lcond(c):
                fr, vis, up, ls, merge_flag, comps = c
                return (~merge_flag) & (ls < cfg.local_steps)

            def lbody(c):
                fr, vis, up, ls, merge_flag, comps = c
                had = fq.has_unchecked(fr) & (walker < m)
                fr2, vis2, u, nn = expand(g, q, fr, vis, 1, 1, dist_fn)
                u = jnp.where(had, u, cap).astype(jnp.int32)
                # CheckMetrics: ONE scalar all-reduce per local round — the
                # only communication between merges
                u_sum = jax.lax.psum(
                    jnp.where(walker < m, u, 0), walker_axis)
                u_bar = u_sum / jnp.maximum(m, 1)
                any_work = jax.lax.psum(
                    had.astype(jnp.int32), walker_axis) > 0
                merge_flag = (u_bar >= cap * cfg.sync_ratio) | ~any_work
                return (fr2, vis2, u, ls + 1, merge_flag,
                        comps + jnp.where(had, nn, 0))

            local, visited, _, rounds, _, comps = jax.lax.while_loop(
                lcond, lbody,
                (local, visited, jnp.int32(0), jnp.int32(0),
                 jnp.bool_(False), jnp.int32(0)))
            frontier = _merge_all_walkers(local, walker_axis)
            visited = _reduce_visited(visited, walker_axis)
            # all stats fields must be uniform along the walker axis (the
            # output spec replicates them), so reduce per-walker counters
            total_comps = jax.lax.psum(comps, walker_axis)
            n_dups = jnp.maximum(
                total_comps - (vs.popcount(visited) - union_before), 0)
            stats = stats._replace(
                steps=stats.steps + live.astype(jnp.int32),
                local_steps=stats.local_steps + rounds * m,  # uniform rounds
                dist_comps=stats.dist_comps + total_comps,
                dup_comps=stats.dup_comps + jnp.where(live, n_dups, 0),
                syncs=stats.syncs + live.astype(jnp.int32),
                crit_rounds=stats.crit_rounds + rounds)
            return frontier, visited, stats

        frontier, visited, stats = jax.lax.fori_loop(
            0, cfg.global_rounds, round_, (frontier, visited, stats))
        ids, dists = fq.results(frontier, cfg.k)
        return ids, dists, stats

    def shard_body(nbrs, vectors, medoid, flat, tiles, q_local):
        walker = jax.lax.axis_index(walker_axis).astype(jnp.int32)
        fn = functools.partial(per_query, nbrs, vectors, medoid, flat,
                               tiles, walker=walker)
        ids, dists, stats = jax.vmap(fn)(q_local)
        return ids, dists, stats

    rep = P()   # graph replicated on all devices
    fn = shard_map(
        shard_body, mesh=mesh,
        in_specs=(rep, rep, rep, rep, rep, P(data_axis, None)),
        out_specs=(P(data_axis, None), P(data_axis, None),
                   jax.tree.map(lambda _: P(data_axis), SearchStats.zero())),
    )
    return fn(graph.nbrs, graph.vectors, graph.medoid, graph.flat,
              graph.vector_tiles, queries)


# ---------------------------------------------------------------------------
# Corpus-sharded search (billion-scale, §5.5)
# ---------------------------------------------------------------------------

class ShardedIndex(NamedTuple):
    """Per-shard sub-indices stacked on a leading shard axis."""
    nbrs: jax.Array        # (S, N_s, R) partition-local neighbor ids
    vectors: jax.Array     # (S, N_s, d)
    medoids: jax.Array     # (S,)
    offsets: jax.Array     # (S,) global id = offsets[s] + local id
    # the Pallas kernels' row-tiled copy (see kernels.registry.
    # with_kernel_tables); None for the ref backends
    vector_tiles: Optional[jax.Array] = None   # (S, N_s, k, 128)

    @property
    def num_shards(self) -> int:
        return self.nbrs.shape[0]

    @property
    def dim(self) -> int:
        return self.vectors.shape[-1]


def build_partitioned(data: np.ndarray, num_shards: int, degree: int = 24,
                      mesh: Optional[Mesh] = None, shard_axis: str = "model",
                      **nsg_kw) -> ShardedIndex:
    """Partition the corpus contiguously and build one sub-index per shard.

    (Real deployments partition by clustering; contiguous split keeps the
    builder simple and the search path identical.)

    With ``mesh``, every stacked array is placed once, here, with shard s
    on the devices at position s of ``mesh``'s ``shard_axis`` — the
    placement :func:`corpus_sharded_search` reads, so no call moves the
    corpus.  Without it the arrays stay on the default device.
    """
    from repro.core.build import build_nsg
    n = data.shape[0]
    per = n // num_shards
    nbrs, vecs, meds, offs = [], [], [], []
    for s in range(num_shards):
        lo, hi = s * per, (s + 1) * per if s < num_shards - 1 else n
        sub = np.asarray(data[lo:hi], np.float32)
        g = build_nsg(sub, degree=degree, **nsg_kw)
        nbrs.append(np.asarray(g.nbrs))
        vecs.append(np.asarray(g.vectors))
        meds.append(int(g.medoid))
        offs.append(lo)
    # pad shards to a common size
    max_n = max(x.shape[0] for x in vecs)
    d = vecs[0].shape[1]
    r = nbrs[0].shape[1]
    for s in range(num_shards):
        pad = max_n - vecs[s].shape[0]
        if pad:
            vecs[s] = np.concatenate(
                [vecs[s], np.full((pad, d), np.inf, np.float32)])
            nbrs[s] = np.concatenate(
                [np.where(nbrs[s] >= nbrs[s].shape[0], max_n, nbrs[s]),
                 np.full((pad, r), max_n, np.int32)]).astype(np.int32)
        else:
            nbrs[s] = nbrs[s].astype(np.int32)
    arrays = dict(nbrs=np.stack(nbrs), vectors=np.stack(vecs),
                  medoids=np.asarray(meds, np.int32),
                  offsets=np.asarray(offs, np.int32))
    if mesh is None:
        return ShardedIndex(**{k: jnp.asarray(v) for k, v in arrays.items()})
    on_shards = NamedSharding(mesh, P(shard_axis))
    return ShardedIndex(**{k: jax.device_put(v, on_shards)
                           for k, v in arrays.items()})


def corpus_sharded_search(
    index: ShardedIndex,
    queries: jax.Array,
    cfg: SearchConfig,
    mesh: Mesh,
    data_axis: str = "data",
    shard_axis: str = "model",
    dist_fn: Optional[DistFn] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Each ``shard_axis`` device searches its partition; global top-K merge.

    Returns (global ids (B,k), dists (B,k)).
    """
    from repro.core.bfis import search_topm_batch

    dist_fn = resolve_dist_fn(cfg, dist_fn)
    n_top = 0

    def shard_body(nbrs, vectors, medoid, offset, tiles, q_local):
        nbrs = nbrs[0]
        vectors = vectors[0]
        medoid = medoid[0]
        offset = offset[0]
        g = PaddedCSR(nbrs=nbrs, vectors=vectors, medoid=medoid, n_top=n_top,
                      flat=jnp.zeros((0, nbrs.shape[1], vectors.shape[1]),
                                     vectors.dtype),
                      vector_tiles=None if tiles is None else tiles[0])
        # batch-major engine inside the shard: the device's whole local
        # query batch advances through one while_loop / one distance launch
        # per step (bit-identical to the per-query vmap it replaces)
        ids, dists, _ = search_topm_batch(g, q_local, cfg, dist_fn=dist_fn)
        gids = jnp.where(ids == fq.INVALID_ID, fq.INVALID_ID, ids + offset)
        # gather per-shard top-k across the shard axis and reduce
        all_ids = jax.lax.all_gather(gids, shard_axis)     # (S, b, k)
        all_d = jax.lax.all_gather(dists, shard_axis)
        s, b, k = all_ids.shape
        flat_i = jnp.moveaxis(all_ids, 0, 1).reshape(b, s * k)
        flat_d = jnp.moveaxis(all_d, 0, 1).reshape(b, s * k)
        flat_d, flat_i = jax.lax.sort((flat_d, flat_i), num_keys=2,
                                      is_stable=True, dimension=-1)
        return flat_i[:, :cfg.k], flat_d[:, :cfg.k]

    fn = shard_map(
        shard_body, mesh=mesh,
        in_specs=(P(shard_axis), P(shard_axis), P(shard_axis), P(shard_axis),
                  P(shard_axis), P(data_axis, None)),
        out_specs=(P(data_axis, None), P(data_axis, None)),
    )
    return fn(index.nbrs, index.vectors, index.medoids, index.offsets,
              index.vector_tiles, queries)


# ---------------------------------------------------------------------------
# Engine-shaped entry points (facade types in, facade types out)
#
# The raw shard_map functions above take (PaddedCSR | ShardedIndex,
# SearchConfig) — the internal plumbing types.  The serving layer speaks
# AnnIndex + SearchParams, so these adapters let AnnEngine (and anything
# else engine-shaped) route dispatch through the distributed paths without
# re-wiring metric normalization, config lowering, or id remapping.
# ---------------------------------------------------------------------------

def walker_engine_search(index, queries, params, mesh: Optional[Mesh] = None):
    """Walker-sharded dispatch with facade types: ``AnnIndex`` +
    ``SearchParams`` in, ``SearchResult`` out.

    Delegates to ``index.searcher(algorithm="sharded")`` so the query
    normalization (cosine), grouping id remap, and searcher caching are the
    facade's own — one walker per device along the mesh's ``model`` axis,
    the query batch sharded over ``data``.  ``mesh=None`` uses the default
    (1, n_devices) search mesh.
    """
    return index.search(queries, params.with_(algorithm="sharded"),
                        mesh=mesh)


def build_partitioned_index(data, num_shards: int, spec=None,
                            mesh: Optional[Mesh] = None) -> ShardedIndex:
    """Corpus partitioning driven by an :class:`repro.ann.IndexSpec`.

    Honors the spec's builder knobs (degree, knn_k, ef_construction, passes,
    seed, and the batched-construction ``build_batch``/``build_backend``
    tile — every per-shard build runs through the batch-insertion path) and
    its metric: for ``cosine`` the corpus is unit-normalized before
    partitioning (cosine == ip on the unit sphere), matching
    ``AnnIndex.build``.  Returns a :class:`ShardedIndex` for
    :func:`corpus_sharded_search` / :func:`corpus_engine_searcher`; with
    ``mesh`` its shards are placed on the mesh's ``model`` axis (see
    :func:`build_partitioned`).
    """
    from repro.ann.spec import IndexSpec
    from repro.core.build import normalize_rows
    if spec is None:
        spec = IndexSpec()
    if spec.quant.enabled:
        raise ValueError("quantized storage is not wired into the "
                         "corpus-sharded path; use IndexSpec(quant='none')")
    data = np.asarray(data, np.float32)
    if spec.metric == "cosine":
        data = normalize_rows(data)
    build_metric = "l2" if spec.metric == "cosine" else spec.metric
    return build_partitioned(
        data, num_shards, degree=spec.degree, knn_k=spec.resolved_knn_k,
        alpha=spec.alpha, ef_construction=spec.resolved_ef,
        passes=spec.passes, seed=spec.seed, metric=build_metric,
        build_batch=spec.build_batch, build_backend=spec.build_backend,
        mesh=mesh)


def corpus_engine_searcher(index: ShardedIndex, params, mesh: Mesh,
                           metric: str = "l2"):
    """A batched callable ``fn(queries (B, d)) -> (ids, dists, stats)`` over
    a partitioned corpus — the corpus-sharded analogue of
    ``AnnIndex.searcher``, shaped for the serving engine.

    Each ``model`` device searches its own partition with a sequential
    best-first walker (top-M with M=1 — walker parallelism within a shard
    composes via a 3D mesh instead) and the global top-K is merged across
    shards.  Queries are unit-normalized here for ``metric="cosine"``.
    ``stats`` is a zero-filled :class:`SearchStats` batched over B: per-query
    counters do not cross the shard merge.
    """
    cfg = params.to_search_config(metric).with_(m_max=1, staged=False,
                                                num_walkers=1)
    normalize = metric == "cosine"
    index = with_kernel_tables(index, cfg.dist_backend)

    @jax.jit
    def jitted(idx, q):
        q = q.astype(jnp.float32)
        if normalize:
            q = q / jnp.maximum(
                jnp.linalg.norm(q, axis=-1, keepdims=True), 1e-12)
        ids, dists = corpus_sharded_search(idx, q, cfg, mesh)
        zero = jnp.zeros((q.shape[0],), jnp.int32)
        stats = jax.tree.map(lambda _: zero, SearchStats.zero())
        return ids, dists, stats

    def fn(queries):
        q = jnp.asarray(queries)
        if q.ndim != 2:
            raise ValueError(f"queries must be (B, d), got {q.shape}")
        return jitted(index, q)

    return fn
