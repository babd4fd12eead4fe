"""Batched ANN serving engine: bucketed shapes + jit-cache reuse + sharding.

Online vector-search traffic arrives as variable-size query batches, but jit
compiles one executable per input shape — naive serving recompiles on every
new batch size.  The engine quantizes incoming batches to a fixed ladder of
*buckets* (powers of two by default), pads the batch up to the bucket, and
reuses one compiled searcher per bucket, so steady-state traffic runs with a
bounded, warmed jit cache no matter how sizes fluctuate.  Batches larger than
the top bucket are served in top-bucket chunks.

The searcher itself is the full Speed-ANN stack (staged parallel expansion,
adaptive synchronization, bounded step budgets) with the distance backend
resolved once from ``SearchConfig.dist_backend`` — kernel selection is a
config knob, not a code path.  Each bucket's compiled executable is ONE
batch-major traversal program (``core.bfis``/``core.speedann``): the whole
padded batch advances through a single while_loop with one distance-kernel
launch per global step, instead of B vmapped per-query lanes — so the
bucket ladder directly trades padding waste against per-step launch
amortization.

The engine is a stage of the ``repro.ann`` facade lifecycle: pass an
:class:`repro.ann.AnnIndex` + :class:`repro.ann.SearchParams` (or call
``index.serve(params)``) and the engine serves through the index's own
cached searchers — inheriting the metric handling (query normalization for
cosine), neighbor-grouping id remap, quantized distance backends
(``backend="ref_int8" | "rowgather_int8" | "ref_bf16"`` on an index built
with ``IndexSpec(quant=...)``), and the two-stage re-ranked search
(``SearchParams.rerank_k``).  The legacy ``(PaddedCSR, SearchConfig)`` form
keeps working.

Three dispatch modes (``engine.mode``), one ``search()`` API:

* ``"single"`` — single-host algorithms (bfis | topm | speedann), the
  default.
* ``"sharded"`` — ``SearchParams(algorithm="sharded")`` on the facade path
  routes every bucket through ``core/distributed.walker_sharded_search``:
  one Speed-ANN walker per device along the mesh's ``model`` axis (the
  paper's intra-query parallelism, cross-device).  Pass ``mesh=`` or get
  the default (1, n_devices) search mesh.
* ``"corpus"`` — construct with a ``core/distributed.ShardedIndex`` (see
  ``build_partitioned_index``) + SearchParams + mesh: each ``model`` device
  searches its own corpus partition and the global top-K is merged.

The async request-coalescing front-end (single queries + deadlines in,
bucketed batches out) lives in :mod:`repro.serve.coalescer`; construct it in
one step with ``index.serve_async(params)``.

Typical use::

    engine = AnnIndex.build(data, spec).serve(params)
    engine.warmup(dim)                  # compile every bucket up front
    res = engine.search(queries)        # (B, d) for any B
    print(engine.stats())               # recall / latency / cache counters
"""
from __future__ import annotations

import time
from typing import Dict, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.ann.index import (AnnIndex, normalize_queries, remap_result_ids)
from repro.ann.spec import SearchParams
from repro.core.config import SearchConfig
from repro.core.bfis import (DistFn, bfis_search_batch, hnsw_search_batch,
                             resolve_dist_fn, search_topm_batch)
from repro.core.distributed import ShardedIndex, corpus_engine_searcher
from repro.core.metrics import SearchStats, recall_at_k, telemetry_per_lane
from repro.core.speedann import search_speedann_batch
from repro.kernels.registry import with_kernel_tables
from repro.obs import NULL_OBS, LogHistogram, Observability, device_annotation

DEFAULT_BUCKETS = (1, 2, 4, 8, 16, 32, 64)

#: Relative error of every latency percentile the engine reports: latency
#: samples land in a bounded log-bucketed sketch (``repro.obs.LogHistogram``)
#: instead of an unbounded list, so ``p50/p90/p95/p99`` are exact to within
#: ±1% while ``mean``/``max`` stay exact.  See docs/observability.md.
LATENCY_REL_ERR = 0.01

_ALGORITHMS = {
    "speedann": search_speedann_batch,
    "topm": search_topm_batch,
    "bfis": bfis_search_batch,
}


class ServeResult(NamedTuple):
    """One served request: results sliced back to the request's true size."""
    ids: np.ndarray          # (B, k) int32
    dists: np.ndarray        # (B, k) float32
    stats: SearchStats       # per-query counters, leaves shaped (B,)
    latency_ms: float        # wall clock for this request (all chunks)
    buckets: Tuple[int, ...]  # bucket(s) the request was quantized to


def _mesh_data_size(mesh) -> int:
    """Size of the mesh's query-sharding axis (1 when absent)."""
    if mesh is None:
        return 1
    return int(dict(mesh.shape).get("data", 1))


class AnnEngine:
    """Bucketed, jit-cached batched ANN serving on a fixed index."""

    def __init__(
        self,
        graph,
        cfg: SearchConfig,
        *,
        algorithm: Optional[str] = None,
        bucket_sizes: Sequence[int] = DEFAULT_BUCKETS,
        dist_fn: Optional[DistFn] = None,
        mesh=None,
        metric: Optional[str] = None,
        obs: Optional[Observability] = None,
    ):
        self.obs = obs if obs is not None else NULL_OBS
        self.index: Optional[AnnIndex] = None
        self.mesh = mesh
        self.mode = "single"
        self._normalize = False
        self._old_from_new = None
        self._corpus_fn = None

        if isinstance(graph, ShardedIndex):
            # corpus-sharded mode: one partition per device on the mesh's
            # model axis, global top-K merge across shards
            if not isinstance(cfg, SearchParams):
                raise ValueError(
                    "corpus-sharded serving takes SearchParams (the "
                    "ShardedIndex has no legacy SearchConfig path)")
            if mesh is None:
                raise ValueError(
                    "corpus-sharded serving needs an explicit mesh whose "
                    "'model' axis size equals index.num_shards "
                    "(see core.distributed.make_search_mesh)")
            if algorithm not in (None, "sharded"):
                raise ValueError(
                    "a ShardedIndex serves only the sharded dispatch; drop "
                    f"algorithm={algorithm!r}")
            self.mode = "corpus"
            self.params = cfg
            self.algorithm = "sharded"
            self.cfg = cfg.to_search_config(metric or "l2")
            self.graph = graph
            self._corpus_fn = corpus_engine_searcher(
                graph, cfg, mesh, metric=metric or "l2")
            self._finish_init(bucket_sizes)
            return

        if isinstance(graph, AnnIndex):
            self.index = graph
            graph = self.index.graph
            self._normalize = self.index.spec.metric == "cosine"
            self._old_from_new = self.index.old_from_new
        metric = self.index.spec.metric if self.index is not None else metric
        self.params: Optional[SearchParams] = None
        if isinstance(cfg, SearchParams):
            if algorithm is None:
                algorithm = cfg.algorithm
            if self.index is not None and dist_fn is None:
                # facade path: serve through the index's own searchers, so
                # the engine inherits everything the facade wires — metric
                # normalization, grouping remap, quantized distance
                # backends, and the two-stage re-ranked search (rerank_k)
                self.params = cfg.with_(algorithm=algorithm)
            elif cfg.rerank_k > 0:
                # the two-stage re-rank lives in the facade searcher;
                # silently serving single-stage results would hand the
                # caller lower recall than the identical params via
                # AnnIndex.search
                raise ValueError(
                    "rerank_k needs the facade serving path: construct the "
                    "engine as AnnEngine(AnnIndex, SearchParams) / "
                    "index.serve(params) without a custom dist_fn")
            cfg = cfg.to_search_config(metric or "l2")
        elif metric is not None and cfg.metric != metric:
            # the index's metric is authoritative over a hand-built config
            cfg = cfg.with_(metric=metric)
        if algorithm is None:
            algorithm = "speedann"
        if algorithm == "sharded":
            if self.params is None:
                raise ValueError(
                    "the legacy (graph, SearchConfig) engine serves the "
                    f"single-host algorithms {tuple(_ALGORITHMS)}; the "
                    "shard_map walker path serves through the facade — "
                    "index.serve(SearchParams(algorithm='sharded'), "
                    "mesh=...)")
            # walker-sharded mode: every bucket dispatches through the
            # facade's sharded searcher (core/distributed.py shard_map)
            self.mode = "sharded"
        elif algorithm not in _ALGORITHMS:
            raise ValueError(
                f"unknown algorithm {algorithm!r}; one of "
                f"{tuple(_ALGORITHMS)}")
        self.graph = graph
        self.cfg = cfg
        self.algorithm = algorithm
        self._dist_fn = self._search = None
        if self.params is None:
            # legacy pipeline only — the facade path serves through
            # index.searcher and never touches these
            self._dist_fn = resolve_dist_fn(cfg, dist_fn)
            self.graph = with_kernel_tables(graph, cfg.dist_backend)
            self._search = _ALGORITHMS[algorithm]
            if (algorithm == "bfis" and self.index is not None
                    and self.index.hnsw is not None):
                # match AnnIndex.search: bfis on an hnsw-built index enters
                # via the greedy upper-level descent, not the base medoid
                hnsw = self.index.hnsw

                def _hnsw_bfis(g, q, c, dist_fn=None):
                    return hnsw_search_batch(hnsw._replace(base=g), q, c,
                                             dist_fn=dist_fn)
                self._search = _hnsw_bfis
        # device-resident remap table, uploaded ONCE per engine (it enters
        # every bucket's executable as a jit argument, like the graph)
        self._ofn = (jnp.asarray(self._old_from_new, jnp.int32)
                     if self._old_from_new is not None
                     else jnp.zeros((0,), jnp.int32))
        self._finish_init(bucket_sizes)

    def _finish_init(self, bucket_sizes: Sequence[int]):
        if not bucket_sizes:
            raise ValueError("bucket_sizes must be non-empty")
        self.bucket_sizes = tuple(sorted(set(int(b) for b in bucket_sizes)))
        if self.mode in ("sharded", "corpus"):
            # sharded dispatch splits the padded batch over the mesh's
            # data axis, so every bucket (every compiled shape) must divide
            data = _mesh_data_size(self.mesh)
            bad = [b for b in self.bucket_sizes if b % max(data, 1)]
            if bad:
                raise ValueError(
                    f"bucket sizes {bad} are not divisible by the mesh's "
                    f"data axis ({data}); sharded serving pads every batch "
                    "to a bucket, so each bucket must split evenly over "
                    "the query-sharding axis")
        self._jit_cache: Dict[int, object] = {}
        # serving counters
        self.queries_served = 0
        self.requests_served = 0
        self.padded_queries = 0
        self.cache_hits = 0
        self.cache_misses = 0
        # latency distributions live in bounded log-bucketed sketches (one
        # global, one per bucket): constant memory under sustained traffic,
        # mergeable across replicas, percentiles within LATENCY_REL_ERR
        self._latency_hist = LogHistogram(rel_err=LATENCY_REL_ERR)
        # per-chunk latency keyed by the bucket it ran in — how the
        # coalescing policy's batch-size choices show up in the tail
        self._bucket_hists: Dict[int, LogHistogram] = {}
        # convergence-telemetry label: which distance kernel served this
        # engine (per-backend registry histograms key on it)
        self._backend_label = str(
            getattr(self.cfg, "dist_backend", None) or "ref")
        self._recall_sum = 0.0
        self._recall_n = 0
        # traversal work totals over served (non-padding) lanes; the
        # uniq/dup split is SearchStats' first-toucher attribution — the
        # dup share is the gather traffic a dedup_gather backend saves
        self.dist_comps_total = 0
        self.uniq_comps_total = 0
        self.batch_dup_comps_total = 0

    # -- jit cache ---------------------------------------------------------

    @property
    def jit_cache_size(self) -> int:
        """Number of compiled entries — bounded by ``len(bucket_sizes)``."""
        return len(self._jit_cache)

    def _compiled(self, bucket: int):
        fn = self._jit_cache.get(bucket)
        if fn is None:
            self.cache_misses += 1
            if self.mode == "corpus":
                # one shard_map searcher; its inner jax.jit keys on the
                # padded batch shape, so cache accounting stays exact
                fn = self._corpus_fn
                self._jit_cache[bucket] = fn
                return fn
            if self.params is not None:
                # every bucket shares the index's ONE cached searcher (in
                # sharded mode the mesh rides along as part of the
                # searcher-cache key); its inner jax.jit keys on the padded
                # batch shape, so cache accounting per bucket stays exact
                fn = self.index.searcher(self.params, mesh=self.mesh)
                self._jit_cache[bucket] = fn
                return fn
            # the graph's arrays enter as jit ARGUMENTS, not closure
            # constants, so every bucket's executable shares the one
            # device-resident embedding table instead of baking its own copy
            search, cfg, dist_fn = self._search, self.cfg, self._dist_fn
            n_top, graph_cls = self.graph.n_top, type(self.graph)
            normalize = self._normalize
            has_remap = self._old_from_new is not None
            n_nodes = self.graph.n_nodes

            @jax.jit
            def jitted(nbrs, vectors, medoid, flat, codes, scales,
                       vector_tiles, code_tiles, ofn_arr, q):
                g = graph_cls(nbrs=nbrs, vectors=vectors, medoid=medoid,
                              n_top=n_top, flat=flat, codes=codes,
                              scales=scales, vector_tiles=vector_tiles,
                              code_tiles=code_tiles)
                q = q.astype(jnp.float32)
                if normalize:
                    q = normalize_queries(q)
                ids, dists, stats = search(g, q, cfg, dist_fn=dist_fn)
                if has_remap:
                    ids = remap_result_ids(ids, ofn_arr, n_nodes)
                return ids, dists, stats

            def fn(q, _j=jitted):
                gr = self.graph
                return _j(gr.nbrs, gr.vectors, gr.medoid, gr.flat,
                          gr.codes, gr.scales, gr.vector_tiles,
                          gr.code_tiles, self._ofn, q)
            self._jit_cache[bucket] = fn
        else:
            self.cache_hits += 1
        return fn

    def bucket_for(self, batch: int) -> int:
        """Smallest bucket >= batch (top bucket for oversize chunks)."""
        for b in self.bucket_sizes:
            if b >= batch:
                return b
        return self.bucket_sizes[-1]

    def warmup(self, dim: Optional[int] = None) -> Dict[int, float]:
        """Compile every bucket up front; returns per-bucket compile seconds.

        Warmup does not touch the serving counters, so post-warmup metrics
        reflect real traffic only.
        """
        dim = dim if dim is not None else self.graph.dim
        hits, misses = self.cache_hits, self.cache_misses
        out = {}
        for b in self.bucket_sizes:
            q = jnp.zeros((b, dim), jnp.float32)
            t0 = time.perf_counter()
            jax.block_until_ready(self._compiled(b)(q)[0])
            out[b] = time.perf_counter() - t0
        self.cache_hits, self.cache_misses = hits, misses
        self._bucket_hists = {}
        return out

    # -- serving -----------------------------------------------------------

    def _run_chunk(self, queries: jax.Array, record: bool
                   ) -> Tuple[tuple, int]:
        """Pad one chunk (chunk size <= top bucket) to its bucket and run.

        With ``record`` the chunk is synced (block_until_ready) and its wall
        time lands in the per-bucket latency distribution.  Multi-chunk
        requests pass ``record=False``: blocking between chunks would
        serialize their dispatch, so they stay pipelined and contribute to
        the request-level distribution only.
        """
        b = queries.shape[0]
        bucket = self.bucket_for(b)
        pad = bucket - b
        if pad:
            # pad with replicas of the first query: real topology, no risk
            # of a degenerate all-zeros search dominating the vmapped loop
            queries = jnp.concatenate(
                [queries, jnp.broadcast_to(queries[:1],
                                           (pad, queries.shape[1]))])
            self.padded_queries += pad
        obs = self.obs
        rerank_k = self.params.rerank_k if self.params is not None else 0
        # the rerank pass (params.rerank_k > 0) runs INSIDE this compiled
        # program, so it is part of the device_compute span, not a separate
        # host span — the span args record it for the trace reader
        with obs.tracer.span("device_compute", cat="engine",
                             args={"bucket": bucket, "pad": pad,
                                   "rerank_k": rerank_k}):
            with device_annotation(
                    f"ann_dispatch/bucket{bucket}", enabled=obs.profile):
                t0 = time.perf_counter()
                ids, dists, stats = self._compiled(bucket)(queries)
                if record:
                    jax.block_until_ready(ids)
                    hist = self._bucket_hists.get(bucket)
                    if hist is None:
                        hist = self._bucket_hists.setdefault(
                            bucket, LogHistogram(rel_err=LATENCY_REL_ERR))
                    hist.observe((time.perf_counter() - t0) * 1e3)
        out = (ids[:b], dists[:b],
               jax.tree.map(lambda t: t[:b], stats))
        return out, bucket

    def search(self, queries, gt_ids: Optional[np.ndarray] = None
               ) -> ServeResult:
        """Serve one request of (B, d) queries, any B >= 1.

        With ``gt_ids`` (B, >=k) the engine also folds recall@k into its
        running quality counters.
        """
        queries = jnp.asarray(queries)
        if queries.ndim != 2 or queries.shape[0] == 0:
            raise ValueError(
                f"queries must be (B, d) with B >= 1, got {queries.shape}")
        bsz = queries.shape[0]
        top = self.bucket_sizes[-1]
        obs = self.obs

        with obs.tracer.span("engine.search", cat="engine",
                             args={"batch": bsz}) as sp:
            t0 = time.perf_counter()
            chunks, buckets = [], []
            single_chunk = bsz <= top
            for lo in range(0, bsz, top):
                out, bucket = self._run_chunk(queries[lo:lo + top],
                                              record=single_chunk)
                chunks.append(out)
                buckets.append(bucket)
            if not single_chunk:
                jax.block_until_ready(chunks[-1][0])
            ms = (time.perf_counter() - t0) * 1e3
            sp.add_args(buckets=list(buckets), latency_ms=round(ms, 3))

            with obs.tracer.span("postprocess", cat="engine"):
                if len(chunks) == 1:
                    ids, dists, stats = chunks[0]
                else:
                    ids = jnp.concatenate([c[0] for c in chunks])
                    dists = jnp.concatenate([c[1] for c in chunks])
                    stats = jax.tree.map(
                        lambda *xs: jnp.concatenate(xs), *[c[2] for c in chunks])

                self.queries_served += bsz
                self.requests_served += 1
                self._latency_hist.observe(ms)
                self.dist_comps_total += int(
                    np.sum(np.asarray(stats.dist_comps)))
                self.uniq_comps_total += int(
                    np.sum(np.asarray(stats.uniq_comps)))
                self.batch_dup_comps_total += int(
                    np.sum(np.asarray(stats.batch_dup_comps)))
                if obs.metrics:
                    self._record_telemetry(stats, buckets, ms)
                ids_np = np.asarray(ids)
                if gt_ids is not None:
                    self._recall_sum += (
                        recall_at_k(ids_np, gt_ids, self.cfg.k) * bsz)
                    self._recall_n += bsz
        return ServeResult(ids_np, np.asarray(dists), stats, ms,
                           tuple(buckets))

    # -- observability -----------------------------------------------------

    def _record_telemetry(self, stats: SearchStats, buckets: Sequence[int],
                          request_ms: float) -> None:
        """Convergence telemetry: per-lane ``SearchStats`` leaves into
        registry histograms, labelled ``{backend, bucket}`` — the
        distribution view (steps-to-converge, dup ratios) that totals
        cannot give.  Only called when ``obs.metrics`` is on."""
        reg = self.obs.registry
        bucket = str(buckets[0]) if len(buckets) == 1 else "chunked"
        for field, values in telemetry_per_lane(stats).items():
            child = reg.histogram(
                f"ann_{field}",
                f"per-lane SearchStats.{field} over served queries",
            ).labels(backend=self._backend_label, bucket=bucket)
            for v in values:
                child.observe(v)
        reg.histogram(
            "serve_request_latency_ms",
            "engine wall-clock per request (all chunks)",
        ).labels(backend=self._backend_label).observe(request_ms)

    @staticmethod
    def _hist_summary(h: LogHistogram, prefix: str) -> Dict[str, float]:
        """mean/max exact; p50/p90/p95/p99 within ``LATENCY_REL_ERR``."""
        return {
            f"{prefix}mean_ms": h.mean,
            f"{prefix}p50_ms": h.quantile(0.50),
            f"{prefix}p90_ms": h.quantile(0.90),
            f"{prefix}p95_ms": h.quantile(0.95),
            f"{prefix}p99_ms": h.quantile(0.99),
            f"{prefix}max_ms": h.max,
        }

    def stats(self) -> Dict[str, float]:
        """Serving observability: traffic/jit-cache counters AND the
        latency distribution (mean, p50/p90/p95/p99, max) — globally per
        request AND per bucket size (``bucket{b}_*`` keys), so the effect
        of batch coalescing on the tail is visible from the stats alone.
        Per-bucket rows cover single-chunk requests only (oversize chunked
        requests stay pipelined, see ``_run_chunk``).

        Memory is bounded: latency samples land in log-bucketed sketches,
        so percentile keys are bucket-resolved (exact within
        ``LATENCY_REL_ERR`` = ±1%) while ``*_mean_ms``/``*_max_ms`` and
        every counter stay exact.

        Key order is stable and documented (docs/serving.md): global
        counters in the order below, then the global ``latency_*`` block,
        then per-bucket blocks in ascending bucket size
        (``bucket{b}_chunks`` first within each block), then
        ``recall_at_k`` last when ground truth was supplied."""
        out = {
            "queries_served": float(self.queries_served),
            "requests_served": float(self.requests_served),
            "padded_queries": float(self.padded_queries),
            "jit_cache_size": float(self.jit_cache_size),
            "cache_hits": float(self.cache_hits),
            "cache_misses": float(self.cache_misses),
            "dist_comps_total": float(self.dist_comps_total),
            "uniq_comps_total": float(self.uniq_comps_total),
            "batch_dup_comps_total": float(self.batch_dup_comps_total),
            # share of distance computations whose row gather a batch-dedup
            # backend skips (cross-lane frontier overlap of served traffic)
            "batch_dup_ratio": (
                self.batch_dup_comps_total / self.dist_comps_total
                if self.dist_comps_total else 0.0),
        }
        if self._latency_hist.count:
            out.update(self._hist_summary(self._latency_hist, "latency_"))
        for b in sorted(self._bucket_hists):
            bh = self._bucket_hists[b]
            out[f"bucket{b}_chunks"] = float(bh.count)
            out.update(self._hist_summary(bh, f"bucket{b}_"))
        if self._recall_n:
            out["recall_at_k"] = self._recall_sum / self._recall_n
        return out

    def metrics(self) -> Dict[str, float]:
        """Back-compat alias of :meth:`stats`."""
        return self.stats()

    def latency_histograms(self) -> Dict[str, LogHistogram]:
        """The live sketches behind :meth:`stats` — ``"request"`` plus one
        ``"bucket{b}"`` per served bucket.  Merge across replicas with
        ``LogHistogram.merge`` for fleet-wide percentiles."""
        out: Dict[str, LogHistogram] = {"request": self._latency_hist}
        for b in sorted(self._bucket_hists):
            out[f"bucket{b}"] = self._bucket_hists[b]
        return out
