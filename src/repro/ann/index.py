"""``AnnIndex`` — the one public API for vector search.

The paper separates the index (CSR topology + vectors, §3.2) from the search
algorithm (BFiS / top-M / Speed-ANN, Alg. 1–3); this class is that
separation as an object with a full lifecycle::

    from repro.ann import AnnIndex, IndexSpec, SearchParams

    index = AnnIndex.build(dataset, IndexSpec(metric="cosine", degree=24))
    index.save("/tmp/idx.npz")

    index = AnnIndex.load("/tmp/idx.npz")
    res = index.search(queries, SearchParams(algorithm="speedann", m_max=8))
    engine = index.serve(SearchParams(k=10))        # batched AnnEngine

Every algorithm in {bfis, topm, speedann, sharded} and every registered
distance backend serves every metric in {l2, ip, cosine}: metric handling
(query normalization for cosine, negative-inner-product kernels for ip) and
neighbor-grouping id remapping live HERE, so callers never hand-wire
``PaddedCSR`` + ``SearchConfig`` + ``resolve_dist_fn`` again.

Quantized storage (``repro.quant``) threads through the same lifecycle:
``IndexSpec(quant="int8"|"bf16")`` trains scales at build time and attaches
a codes table the quantized distance backends (``ref_int8`` |
``rowgather_int8`` | ``ref_bf16``) gather from, ``save``/``load`` round-trip
codes + scales, and ``SearchParams(rerank_k=...)`` turns any search into the
AQR-HNSW two-stage shape — quantized traversal over a widened pool, then
exact float32 re-ranking::

    spec = IndexSpec(metric="l2", quant="int8")
    index = AnnIndex.build(dataset, spec)
    res = index.search(queries, SearchParams(k=10, backend="ref_int8",
                                             rerank_k=30))

Searches are BATCH-MAJOR end to end: a (B, d) query batch advances through
one traversal loop with one distance-kernel launch per global step (see
``core.bfis``), so larger batches amortize per-step launch cost.  For
``metric="ip"``, ``IndexSpec(entry_policy="max_norm")`` seeds traversals at
the max-norm vertex instead of the centroid medoid (the MIPS entry
heuristic for skewed-norm distributions).
"""
from __future__ import annotations

import dataclasses
import json
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.ann.spec import IndexSpec, SearchParams
from repro.core.bfis import (bfis_search_batch, hnsw_search_batch,
                             search_topm_batch)
from repro.core.build import (HNSWIndex, build_hnsw, build_nsg, exact_knn,
                              insert_points, normalize_rows, repair_deleted)
from repro.core.graph import (PaddedCSR, compute_medoid, group_by_indegree,
                              remap_sentinels)
from repro.core.speedann import search_speedann_batch
from repro.kernels.registry import with_kernel_tables
from repro.quant import codec as quant_codec
from repro.quant.scheme import required_quant_dtype

# format 2 adds quantized storage: codes + scales arrays, and indices whose
# f32 vectors are not persisted (QuantSpec.keep_float=False) — readable only
# by code that knows to dequantize.  Format-1 files load unchanged.
# format 3 adds the tombstone array (incremental delete) — stamped only when
# at least one vertex is actually tombstoned, so add-only/static indices stay
# readable by format-2 readers.
_SAVE_FORMAT = 3


class SearchResult(NamedTuple):
    """One batched search: ids/dists (B, k) + per-query SearchStats."""
    ids: jax.Array
    dists: jax.Array
    stats: object


def default_search_mesh():
    """(data=1, model=n_devices) mesh for the "sharded" algorithm when the
    caller does not provide one.  On a single-device host this degenerates
    to one walker — the same code path, no special-casing."""
    from repro.core.distributed import make_search_mesh
    return make_search_mesh((1, len(jax.devices())), ("data", "model"))


def normalize_queries(q: jax.Array) -> jax.Array:
    """Unit-normalize a (B, d) query batch (cosine = ip on the unit
    sphere).  Shared by ``AnnIndex.searcher`` and the serving engine so the
    two paths cannot drift."""
    return q / jnp.maximum(jnp.linalg.norm(q, axis=-1, keepdims=True), 1e-12)


def remap_result_ids(ids: jax.Array, old_from_new: jax.Array,
                     n_nodes: int) -> jax.Array:
    """Map grouped (relabelled) result ids back to the caller's original id
    space; sentinel/invalid ids (>= n_nodes) pass through unchanged."""
    safe = jnp.minimum(ids, n_nodes - 1)
    return jnp.where(ids < n_nodes, old_from_new[safe], ids)


def exact_rerank(graph: PaddedCSR, q: jax.Array, ids: jax.Array, k: int,
                 metric: str):
    """Second stage of the two-stage search: exactly re-rank a (B, P)
    candidate pool against the float32 vectors and return the top k.

    Runs in INTERNAL (pre-remap) id space so the vector gather is direct;
    sentinel ids (>= N) re-rank to +inf and sink to the tail.  Ties break on
    id, so the result order is deterministic across backends.
    """
    n = graph.n_nodes
    safe = jnp.minimum(ids, n - 1)
    vecs = graph.vectors[safe].astype(jnp.float32)        # (B, P, d)
    qf = q.astype(jnp.float32)[:, None, :]
    if metric in ("ip", "cosine"):
        d = -jnp.sum(vecs * qf, axis=-1)
    else:
        d = jnp.sum((vecs - qf) ** 2, axis=-1)
    d = jnp.where(ids < n, d, jnp.inf)
    d, ids = jax.lax.sort((d, ids.astype(jnp.int32)), num_keys=2,
                          is_stable=True, dimension=-1)
    return ids[:, :k], d[:, :k]


def apply_entry_policy(graph: PaddedCSR, spec: IndexSpec) -> PaddedCSR:
    """Build-time traversal-entry selection (``IndexSpec.entry_policy``).

    ``"max_norm"`` replaces the medoid with the max-norm vertex — the MIPS
    seed heuristic: inner-product search converges to a region dominated by
    large-norm points, so seeding there skips the climb out of the centroid
    vertex's small-norm neighborhood.  Runs LAST in the build pipeline, on
    the stored (post-relabelling, post-quantization) vectors, so the entry
    id is in internal id space and consistent with what searches will see.
    """
    if spec.entry_policy != "max_norm":
        return graph
    norms = np.linalg.norm(np.asarray(graph.vectors, np.float32), axis=1)
    return graph._replace(
        medoid=jnp.asarray(int(np.argmax(norms)), jnp.int32))


def quantize_graph(graph: PaddedCSR, quant) -> PaddedCSR:
    """Attach a trained quantized table (codes + scales) to a built graph.

    Scales are calibrated on the STORED vectors — post-normalization (cosine)
    and post-relabelling (neighbor grouping) — so ``codes[i]`` always encodes
    ``vectors[i]``.

    With ``keep_float=False`` the exact f32 table is dropped HERE, already at
    build time: ``vectors`` (and the flattened hot-vertex blocks) become the
    dequantized codes, so an in-memory index and its save/load round-trip are
    bit-identical — persistence never changes search results."""
    if not quant.enabled:
        return graph
    scales = quant_codec.fit_scales(graph.vectors, quant)
    codes = quant_codec.quantize(graph.vectors, quant, scales)
    graph = graph._replace(codes=codes,
                           scales=jnp.asarray(scales, jnp.float32),
                           code_tiles=None)
    if not quant.keep_float:
        vectors = quant_codec.dequantize(codes, quant, graph.scales)
        flat = graph.flat
        if graph.n_top > 0:
            from repro.core.graph import _flatten_top
            flat = jnp.asarray(_flatten_top(
                np.asarray(graph.nbrs), np.asarray(vectors), graph.n_top))
        graph = graph._replace(vectors=vectors, flat=flat,
                               vector_tiles=None)
    return graph


class AnnIndex:
    """A built similarity-graph index + its :class:`IndexSpec`.

    Construct via :meth:`build` or :meth:`load`, never directly (the
    constructor is public only for internal wiring and tests).
    """

    def __init__(self, spec: IndexSpec, graph: PaddedCSR,
                 hnsw: Optional[HNSWIndex] = None,
                 old_from_new: Optional[np.ndarray] = None,
                 tombstone: Optional[np.ndarray] = None):
        self.spec = spec
        self.graph = graph
        self.hnsw = hnsw
        # neighbor grouping relabels vertices; old_from_new maps result ids
        # back to the caller's original ids (None when no relabelling)
        self.old_from_new = (None if old_from_new is None
                             else np.asarray(old_from_new, np.int64))
        # incremental delete: (N,) bool in INTERNAL id space; tombstoned
        # vertices stay in the graph as navigable waypoints but are masked
        # out of every search/exact result (None == nothing deleted)
        self.tombstone = (None if tombstone is None
                          else np.asarray(tombstone, bool))
        # device-resident remap table, uploaded once per index (it enters
        # every searcher's executable as a jit argument, like the graph)
        self._ofn = (jnp.asarray(self.old_from_new, jnp.int32)
                     if self.old_from_new is not None
                     else jnp.zeros((0,), jnp.int32))
        self._tomb = (jnp.asarray(self.tombstone)
                      if self.tombstone is not None
                      else jnp.zeros((0,), jnp.bool_))
        self._searcher_cache: Dict = {}
        self._kernel_graph: Optional[PaddedCSR] = None   # see _graph_for
        self._host_vectors: Optional[np.ndarray] = None  # exact() cache

    # -- introspection -----------------------------------------------------

    @property
    def n_nodes(self) -> int:
        return self.graph.n_nodes

    @property
    def dim(self) -> int:
        return self.graph.dim

    @property
    def metric(self) -> str:
        return self.spec.metric

    @property
    def n_alive(self) -> int:
        """Live (non-tombstoned) vertex count."""
        dead = 0 if self.tombstone is None else int(self.tombstone.sum())
        return self.n_nodes - dead

    def __repr__(self) -> str:
        return (f"AnnIndex(builder={self.spec.builder!r}, "
                f"metric={self.spec.metric!r}, n={self.n_nodes}, "
                f"d={self.dim}, degree={self.graph.degree})")

    # -- build -------------------------------------------------------------

    @classmethod
    def build(cls, data, spec: IndexSpec = IndexSpec()) -> "AnnIndex":
        """Build an index over ``data`` ((N, d) array-like, or anything with
        a ``.base`` attribute such as ``repro.data.VectorDataset``).

        For ``metric="cosine"`` the base vectors are unit-normalized here
        and stored normalized (cosine == inner product on the unit sphere);
        queries are normalized symmetrically at search time.
        """
        # unwrap dataset-like objects (e.g. repro.data.VectorDataset) — but
        # never raw arrays: np.ndarray itself exposes a ``.base`` attribute
        # (its memory owner), which must not be mistaken for a dataset field
        if not isinstance(data, (np.ndarray, jax.Array)) \
                and getattr(data, "base", None) is not None:
            data = data.base
        data = np.asarray(data, np.float32)
        if data.ndim != 2:
            raise ValueError(f"data must be (N, d), got {data.shape}")
        if spec.metric == "cosine":
            data = normalize_rows(data)
        build_metric = "l2" if spec.metric == "cosine" else spec.metric

        if spec.builder == "hnsw":
            hnsw = build_hnsw(data, degree=spec.degree,
                              upper_degree=spec.upper_degree,
                              seed=spec.seed, alpha=spec.alpha,
                              metric=build_metric,
                              build_batch=spec.build_batch,
                              build_backend=spec.build_backend)
            base = apply_entry_policy(
                quantize_graph(hnsw.base, spec.quant), spec)
            return cls(spec, base, hnsw=hnsw._replace(base=base))

        graph = build_nsg(data, degree=spec.degree,
                          knn_k=spec.resolved_knn_k, alpha=spec.alpha,
                          ef_construction=spec.resolved_ef, seed=spec.seed,
                          passes=spec.passes, metric=build_metric,
                          build_batch=spec.build_batch,
                          build_backend=spec.build_backend)
        old_from_new = None
        if spec.n_top_fraction > 0:
            graph, old_from_new = group_by_indegree(
                np.asarray(graph.nbrs), np.asarray(graph.vectors),
                medoid=int(graph.medoid),
                top_fraction=spec.n_top_fraction)
        graph = apply_entry_policy(quantize_graph(graph, spec.quant), spec)
        return cls(spec, graph, old_from_new=old_from_new)

    # -- incremental maintenance -------------------------------------------

    def _build_metric(self) -> str:
        return "l2" if self.spec.metric == "cosine" else self.spec.metric

    def _invalidate(self) -> None:
        """Drop every cache derived from the graph arrays (after mutation)."""
        self._searcher_cache = {}
        self._kernel_graph = None
        self._host_vectors = None

    def _graph_for(self, backend: str) -> PaddedCSR:
        """The graph a ``backend`` searcher reads: ``self.graph`` plus the
        row-tiled table that backend's Pallas kernel gathers from, laid out
        once per graph and shared by every searcher (nothing extra for the
        ``ref`` backends)."""
        if self._kernel_graph is None:
            self._kernel_graph = self.graph
        self._kernel_graph = with_kernel_tables(self._kernel_graph, backend)
        return self._kernel_graph

    def add(self, new_vectors) -> np.ndarray:
        """Insert new vectors into the live index without a rebuild.

        Runs the SAME batched insertion path as construction
        (:func:`repro.core.build.insert_points`) against the live graph:
        one candidate-search round through the jit engine, vectorized
        α-prune, deterministic reverse edges.  Cosine inputs are normalized
        here; quantized indices quantize the new rows consistently
        (per-vector scales are fit per new row, per-dim scales are reused so
        existing codes stay bit-identical); the flattened top level is
        rebuilt when present.  Returns the assigned ids in the caller's
        (original) id space.
        """
        if self.spec.builder == "hnsw":
            raise NotImplementedError(
                "incremental add() is supported for the nsg builder only "
                "(the hnsw upper levels would need re-sampling)")
        new = np.asarray(new_vectors, np.float32)
        if new.ndim == 1:
            new = new[None, :]
        if new.ndim != 2 or new.shape[1] != self.dim:
            raise ValueError(
                f"new vectors must be (K, {self.dim}), got {new.shape}")
        if new.shape[0] == 0:
            return np.zeros((0,), np.int64)
        if self.spec.metric == "cosine":
            new = normalize_rows(new)

        spec, quant = self.spec, self.spec.quant
        n_old = self.n_nodes
        n_new = n_old + new.shape[0]

        # grow the adjacency; the sentinel changes value with N, so the old
        # rows' padding must be rewritten BEFORE the table grows
        nbrs = np.full((n_new, self.graph.degree), n_new, np.int32)
        nbrs[:n_old] = remap_sentinels(
            np.asarray(self.graph.nbrs), n_old, n_new)

        vectors = np.asarray(self.graph.vectors, np.float32)
        codes = scales = None
        store_new = new
        if quant.enabled:
            if quant.dtype == "int8" and not quant.per_dim:
                # per-vector granularity: each row owns its scale, so new
                # rows calibrate independently and old codes are untouched
                s_new = quant_codec.fit_scales(new, quant)
                scales = jnp.concatenate(
                    [self.graph.scales, jnp.asarray(s_new, jnp.float32)])
            else:
                # per-dim (or bf16's placeholder): reuse the trained scales
                # — refitting would silently re-encode the whole table
                s_new = self.graph.scales
                scales = self.graph.scales
            c_new = quant_codec.quantize(new, quant, s_new)
            codes = jnp.concatenate([self.graph.codes, c_new])
            if not quant.keep_float:
                store_new = np.asarray(
                    quant_codec.dequantize(c_new, quant, s_new), np.float32)
        vectors = np.concatenate([vectors, store_new])

        new_ids = np.arange(n_old, n_new, dtype=np.int64)
        insert_points(
            nbrs, vectors, int(self.graph.medoid), new_ids, n_old,
            degree=spec.degree, alpha=spec.alpha, ef=spec.resolved_ef,
            metric=self._build_metric(), build_batch=spec.build_batch,
            build_backend=spec.build_backend)

        from repro.core.graph import _flatten_top
        flat = _flatten_top(nbrs, vectors, self.graph.n_top)
        self.graph = PaddedCSR(
            nbrs=jnp.asarray(nbrs), vectors=jnp.asarray(vectors),
            medoid=self.graph.medoid, n_top=self.graph.n_top,
            flat=jnp.asarray(flat), codes=codes, scales=scales)
        self.graph = apply_entry_policy(self.graph, spec)
        if self.old_from_new is not None:
            # new points keep identity labels past the grouped prefix
            self.old_from_new = np.concatenate(
                [self.old_from_new, new_ids])
            self._ofn = jnp.asarray(self.old_from_new, jnp.int32)
        if self.tombstone is not None:
            self.tombstone = np.concatenate(
                [self.tombstone, np.zeros(new_ids.shape[0], bool)])
            self._tomb = jnp.asarray(self.tombstone)
        self._invalidate()
        return new_ids

    def delete(self, ids) -> int:
        """Tombstone vertices and repair their neighborhoods in place.

        FreshDiskANN-style lazy delete: the rows stay in the graph as
        navigable waypoints (their out-edges survive), every live
        in-neighbor re-prunes over its survivors plus the deleted vertex's
        live out-edges (:func:`repro.core.build.repair_deleted`), and every
        search / ``exact`` call masks tombstoned ids from results.  Returns
        the number of newly deleted vertices; already-deleted and duplicate
        ids are ignored.  Deleting every remaining vertex is refused.
        """
        if self.spec.builder == "hnsw":
            raise NotImplementedError(
                "incremental delete() is supported for the nsg builder only")
        ids = np.unique(np.asarray(ids, np.int64).ravel())
        if ids.shape[0] == 0:
            return 0
        n = self.n_nodes
        if self.old_from_new is not None:
            # callers speak original ids; tombstones live in internal space
            new_from_old = np.empty(self.old_from_new.shape[0], np.int64)
            new_from_old[self.old_from_new] = np.arange(
                self.old_from_new.shape[0])
            if ids[0] < 0 or ids[-1] >= new_from_old.shape[0]:
                raise ValueError(f"ids out of range [0, "
                                 f"{new_from_old.shape[0]})")
            internal = new_from_old[ids]
        else:
            if ids[0] < 0 or ids[-1] >= n:
                raise ValueError(f"ids out of range [0, {n})")
            internal = ids
        tomb = (self.tombstone.copy() if self.tombstone is not None
                else np.zeros(n, bool))
        fresh = internal[~tomb[internal]]
        if fresh.shape[0] == 0:
            return 0
        if int(tomb.sum()) + fresh.shape[0] >= n:
            raise ValueError("delete() would tombstone every vertex; "
                             "drop the index instead")
        tomb[fresh] = True

        spec = self.spec
        nbrs = np.asarray(self.graph.nbrs).copy()
        vectors = np.asarray(self.graph.vectors, np.float32)
        repair_deleted(nbrs, vectors, tomb, degree=spec.degree,
                       alpha=spec.alpha, metric=self._build_metric())

        medoid = self.graph.medoid
        if tomb[int(medoid)]:
            # the entry vertex died: re-elect among survivors (the row
            # itself stays — it is still a fine navigable waypoint)
            if spec.entry_policy == "max_norm":
                norms = np.linalg.norm(vectors, axis=1)
                medoid = jnp.asarray(
                    int(np.argmax(np.where(tomb, -np.inf, norms))),
                    jnp.int32)
            else:
                medoid = jnp.asarray(
                    compute_medoid(vectors, metric=self._build_metric(),
                                   alive=~tomb), jnp.int32)

        from repro.core.graph import _flatten_top
        flat = _flatten_top(nbrs, np.asarray(self.graph.vectors),
                            self.graph.n_top)
        self.graph = self.graph._replace(
            nbrs=jnp.asarray(nbrs), medoid=medoid, flat=jnp.asarray(flat))
        self.tombstone = tomb
        self._tomb = jnp.asarray(tomb)
        self._invalidate()
        return int(fresh.shape[0])

    # -- persistence -------------------------------------------------------

    def save(self, path: str) -> str:
        """npz round-trip of CSR + flat layout + medoid + spec (+ HNSW
        levels + grouping permutation + quantized codes/scales).  Returns
        the actual path written (numpy appends ``.npz`` when missing).

        With quantization and ``keep_float=False`` the float32 vectors are
        NOT persisted — the vector payload shrinks 4x (int8) / 2x (bf16) and
        ``load`` rebuilds the f32 table by dequantizing, so exact() and
        re-ranking then reference the quantized values."""
        path = str(path)
        if not path.endswith(".npz"):
            path += ".npz"
        quant = self.spec.quant
        # default-valued NEW spec fields are stripped from the json so
        # artifacts that don't use them stay loadable by readers that
        # predate the field: unquantized artifacts stay format-1 END TO END
        # (format-1 stamp AND no quant key), and a default "medoid" entry
        # policy leaves no entry_policy key
        has_tomb = self.tombstone is not None and bool(self.tombstone.any())
        fmt = 1
        if self.graph.codes is not None:
            fmt = 2
        if has_tomb:
            fmt = _SAVE_FORMAT
        spec_dict = dataclasses.asdict(self.spec)
        if not quant.enabled:
            del spec_dict["quant"]
        if self.spec.entry_policy == "medoid":
            del spec_dict["entry_policy"]
        if self.spec.build_batch == 32:
            del spec_dict["build_batch"]
        if self.spec.build_backend == "ref":
            del spec_dict["build_backend"]
        arrays = dict(
            format=np.int64(fmt),
            spec=np.asarray(json.dumps(spec_dict)),
            nbrs=np.asarray(self.graph.nbrs),
            medoid=np.asarray(self.graph.medoid, np.int32),
            n_top=np.int64(self.graph.n_top),
            flat=np.asarray(self.graph.flat),
        )
        if not quant.enabled or quant.keep_float:
            arrays["vectors"] = np.asarray(self.graph.vectors)
        if self.graph.codes is not None:
            codes = np.asarray(self.graph.codes)
            if quant.dtype == "bf16":
                # npz has no bfloat16 descr; persist the raw bit pattern
                codes = codes.view(np.uint16)
            arrays["codes"] = codes
            arrays["scales"] = np.asarray(self.graph.scales, np.float32)
        if self.old_from_new is not None:
            arrays["old_from_new"] = self.old_from_new
        if has_tomb:
            arrays["tombstone"] = self.tombstone
        if self.hnsw is not None:
            arrays["hnsw_entry"] = np.int64(self.hnsw.entry)
            arrays["hnsw_num_levels"] = np.int64(len(self.hnsw.level_nbrs))
            for i, (ln, nn) in enumerate(zip(self.hnsw.level_nbrs,
                                             self.hnsw.level_nodes)):
                arrays[f"hnsw_level_nbrs_{i}"] = np.asarray(ln)
                arrays[f"hnsw_level_nodes_{i}"] = np.asarray(nn)
        np.savez(path, **arrays)
        return path

    @classmethod
    def load(cls, path: str) -> "AnnIndex":
        path = str(path)
        if not path.endswith(".npz"):
            path += ".npz"
        z = np.load(path, allow_pickle=False)
        fmt = int(z["format"])
        if fmt > _SAVE_FORMAT:
            raise ValueError(f"index file format {fmt} is newer than this "
                             f"code ({_SAVE_FORMAT})")
        spec = IndexSpec(**json.loads(str(z["spec"])))
        codes = scales = None
        if "codes" in z.files:
            raw = z["codes"]
            if spec.quant.dtype == "bf16":
                import ml_dtypes
                raw = raw.view(ml_dtypes.bfloat16)
            codes = jnp.asarray(raw)
            scales = jnp.asarray(z["scales"], jnp.float32)
        if "vectors" in z.files:
            vectors = jnp.asarray(z["vectors"])
        else:
            # keep_float=False artifact: the f32 table is the dequantized
            # codes (exact() / re-ranking reference the quantized values)
            vectors = quant_codec.dequantize(codes, spec.quant, scales)
        graph = PaddedCSR(
            nbrs=jnp.asarray(z["nbrs"]),
            vectors=vectors,
            medoid=jnp.asarray(z["medoid"], jnp.int32),
            n_top=int(z["n_top"]),
            flat=jnp.asarray(z["flat"]),
            codes=codes,
            scales=scales,
        )
        old_from_new = (np.asarray(z["old_from_new"])
                        if "old_from_new" in z.files else None)
        tombstone = (np.asarray(z["tombstone"], bool)
                     if "tombstone" in z.files else None)
        hnsw = None
        if "hnsw_entry" in z.files:
            n_levels = int(z["hnsw_num_levels"])
            hnsw = HNSWIndex(
                base=graph,
                level_nbrs=tuple(jnp.asarray(z[f"hnsw_level_nbrs_{i}"])
                                 for i in range(n_levels)),
                level_nodes=tuple(jnp.asarray(z[f"hnsw_level_nodes_{i}"])
                                  for i in range(n_levels)),
                entry=int(z["hnsw_entry"]),
            )
        return cls(spec, graph, hnsw=hnsw, old_from_new=old_from_new,
                   tombstone=tombstone)

    # -- search ------------------------------------------------------------

    def searcher(self, params: SearchParams = SearchParams(), *,
                 mesh=None):
        """A jit-ready batched callable ``fn(queries (B, d)) ->
        SearchResult``.

        The compiled executable takes the graph arrays as jit ARGUMENTS (not
        closure constants), so searchers for different params share one
        device-resident embedding table.  Query normalization (cosine) and
        grouping id-remap run inside the jitted function.  Searchers are
        cached per (params, mesh) — repeated ``search`` calls reuse them.
        ``fn.lower(queries)`` lowers the same executable for inspection.
        """
        key = (params, id(mesh) if mesh is not None else None)
        cached = self._searcher_cache.get(key)
        if cached is not None:
            return cached

        need = required_quant_dtype(params.backend)
        if need != "none" and self.spec.quant.dtype != need:
            raise ValueError(
                f"backend {params.backend!r} reads a {need} codes table; "
                f"this index has quant={self.spec.quant.dtype!r} — rebuild "
                f"with IndexSpec(quant={need!r}) or pick a matching backend")

        cfg = params.to_search_config(self.spec.metric)
        metric = self.spec.metric
        k, rerank_k = params.k, params.rerank_k
        if rerank_k > 0:
            # stage 1 traverses over a pool widened to max(k, rerank_k);
            # stage 2 re-ranks that pool exactly against the f32 vectors
            pool = max(k, rerank_k)
            cfg = cfg.with_(k=pool, queue_len=max(cfg.queue_len, pool))
        normalize = metric == "cosine"
        has_remap = self.old_from_new is not None
        has_tomb = self.tombstone is not None and bool(self.tombstone.any())
        ofn, tomb = self._ofn, self._tomb
        n_top, n_nodes = self.graph.n_top, self.graph.n_nodes
        algorithm = params.algorithm
        hnsw = self.hnsw

        if algorithm == "sharded":
            if need != "none":
                raise ValueError(
                    "quantized backends are not wired into the sharded "
                    "walker path; use a single-host algorithm "
                    "(bfis | topm | speedann) with backend "
                    f"{params.backend!r}")
            from repro.core.distributed import walker_sharded_search
            the_mesh = mesh if mesh is not None else default_search_mesh()

            def run(g, q):
                return walker_sharded_search(g, q, cfg, the_mesh)
        elif algorithm == "bfis" and hnsw is not None:
            # greedy upper-level descent, then Algorithm 1 at level 0; the
            # (small) upper-level tables ride along as closure constants
            def run(g, q):
                idx = hnsw._replace(base=g)
                return hnsw_search_batch(idx, q, cfg)
        elif algorithm == "bfis":
            def run(g, q):
                return bfis_search_batch(g, q, cfg)
        elif algorithm == "topm":
            def run(g, q):
                return search_topm_batch(g, q, cfg)
        elif algorithm == "speedann":
            def run(g, q):
                return search_speedann_batch(g, q, cfg)
        else:  # pragma: no cover - SearchParams validates
            raise ValueError(algorithm)

        @jax.jit
        def jitted(nbrs, vectors, medoid, flat, codes, scales, vector_tiles,
                   code_tiles, ofn_arr, tomb_arr, q):
            g = PaddedCSR(nbrs=nbrs, vectors=vectors, medoid=medoid,
                          n_top=n_top, flat=flat, codes=codes, scales=scales,
                          vector_tiles=vector_tiles, code_tiles=code_tiles)
            q = q.astype(jnp.float32)
            if normalize:
                q = normalize_queries(q)
            ids, dists, stats = run(g, q)
            if has_tomb:
                # tombstoned vertices are waypoints, never answers: mask
                # them to the sentinel (their slot distance to +inf) and
                # stable-sort live results to the front — BEFORE re-ranking
                # (which treats sentinels as +inf) and the grouping remap
                safe = jnp.minimum(ids, n_nodes - 1)
                dead = tomb_arr[safe] & (ids < n_nodes)
                dists = jnp.where(dead, jnp.inf, dists)
                ids = jnp.where(dead, n_nodes, ids).astype(jnp.int32)
                if rerank_k == 0:
                    dists, ids = jax.lax.sort(
                        (dists, ids), num_keys=2, is_stable=True,
                        dimension=-1)
            if rerank_k > 0:
                # the AQR-HNSW two-stage shape: quantized (or plain) best-
                # first traversal, then exact f32 re-ranking of the pool —
                # in internal id space, BEFORE the grouping remap
                with jax.named_scope("ann.rerank"):
                    ids, dists = exact_rerank(g, q, ids, k, metric)
            if has_remap:
                ids = remap_result_ids(ids, ofn_arr, n_nodes)
            return ids, dists, stats

        graph = self._graph_for(params.backend)

        def fn(queries) -> SearchResult:
            q = jnp.asarray(queries)
            if q.ndim != 2:
                raise ValueError(f"queries must be (B, d), got {q.shape}")
            out = jitted(graph.nbrs, graph.vectors, graph.medoid,
                         graph.flat, graph.codes, graph.scales,
                         graph.vector_tiles, graph.code_tiles, ofn, tomb,
                         q)
            return SearchResult(*out)

        def lower(queries):
            """The search's ``jax.stages.Lowered`` for ``queries``: its
            ``.compile().as_text()`` is the HLO that runs, each
            instruction's ``op_name`` naming its ``ann.*`` scope."""
            return jitted.lower(graph.nbrs, graph.vectors, graph.medoid,
                                graph.flat, graph.codes, graph.scales,
                                graph.vector_tiles, graph.code_tiles, ofn,
                                tomb, jnp.asarray(queries))

        fn.lower = lower
        self._searcher_cache[key] = fn
        return fn

    def search(self, queries, params: SearchParams = SearchParams(), *,
               mesh=None) -> SearchResult:
        """Search a (B, d) query batch; dispatches to ``params.algorithm``
        (including the ``shard_map`` walker path for "sharded")."""
        return self.searcher(params, mesh=mesh)(queries)

    # -- ground truth ------------------------------------------------------

    def exact(self, queries, k: int) -> Tuple[np.ndarray, np.ndarray]:
        """Metric-aware exact kNN over the indexed vectors (brute force) —
        the recall reference for this index.  Returns original ids even for
        grouped (relabelled) indices."""
        if self._host_vectors is None:
            # one device->host copy per index, not per call (serving loops
            # compute per-batch ground truth); stored vectors are already
            # normalized for cosine, so "ip" gives identical distances
            # without re-normalizing the table every call
            self._host_vectors = np.asarray(self.graph.vectors, np.float32)
        q = np.asarray(queries, np.float32)
        metric = self.spec.metric
        if metric == "cosine":
            q = q / np.maximum(
                np.linalg.norm(q, axis=1, keepdims=True), 1e-12)
            metric = "ip"
        has_tomb = self.tombstone is not None and bool(self.tombstone.any())
        if has_tomb:
            # over-fetch so k live results survive the tombstone filter
            kk = min(k + int(self.tombstone.sum()), self.n_nodes)
            ids, dists = exact_knn(self._host_vectors, q, kk, metric=metric)
            dead = self.tombstone[ids]
            order = np.argsort(dead, axis=1, kind="stable")
            ids = np.take_along_axis(ids, order, axis=1)[:, :k]
            dists = np.take_along_axis(dists, order, axis=1)[:, :k]
        else:
            ids, dists = exact_knn(self._host_vectors, q, k, metric=metric)
        if self.old_from_new is not None:
            ids = self.old_from_new[ids].astype(np.int32)
        return ids, dists

    # -- serving -----------------------------------------------------------

    def serve(self, params: SearchParams = SearchParams(), *, mesh=None,
              obs=None, **engine_kw):
        """A bucketed, jit-cached :class:`repro.serve.AnnEngine` over this
        index (``engine_kw`` forwards e.g. ``bucket_sizes``).

        The engine serves the single-host algorithms (bfis | topm |
        speedann) and, with ``SearchParams(algorithm="sharded")``, the
        multi-device walker path — one Speed-ANN walker per device along
        ``mesh``'s ``model`` axis (``mesh=None``: the default
        (1, n_devices) search mesh).

        ``obs`` takes a :class:`repro.obs.Observability` bundle to enable
        request-scoped tracing + convergence telemetry (None: the no-op
        ``NULL_OBS`` — zero instrumentation cost).  See
        docs/observability.md."""
        from repro.serve.ann_engine import AnnEngine
        return AnnEngine(self, params, mesh=mesh, obs=obs, **engine_kw)

    def serve_async(self, params: SearchParams = SearchParams(), *,
                    max_batch: Optional[int] = None,
                    max_wait_ms: float = 2.0,
                    default_deadline_ms: Optional[float] = None,
                    mesh=None, start: bool = True, obs=None,
                    cache=None, admission=None, clock=None, **engine_kw):
        """An async coalescing front-end (:class:`repro.serve.coalescer.
        AsyncAnnEngine`) over :meth:`serve`: single queries with
        per-request deadlines in, bucketed batches through the jit cache,
        per-request futures back.

        ``max_batch`` defaults to the engine's top bucket so a full flush
        exactly fills the biggest compiled executable.  The wrapped batched
        engine stays reachable as ``.engine``.  One ``obs`` bundle covers
        both layers: the coalescer inherits the engine's.

        The serving-tier knobs pass straight through: ``cache`` (a
        ``repro.serve.CachePolicy`` or ready ``ResultCache``) replays
        repeated queries from their quantized-code key, ``admission`` (an
        ``AdmissionPolicy`` or ``AdmissionController``) sheds by priority
        class at queue-depth watermarks, and ``clock`` injects a virtual
        clock for deterministic tests (pair with ``start=False``).
        """
        from repro.serve.coalescer import AsyncAnnEngine, CoalescePolicy
        engine = self.serve(params, mesh=mesh, obs=obs, **engine_kw)
        policy = CoalescePolicy(
            max_batch=max_batch if max_batch is not None
            else engine.bucket_sizes[-1],
            max_wait_ms=max_wait_ms,
            default_deadline_ms=default_deadline_ms)
        return AsyncAnnEngine(engine, policy, start=start, cache=cache,
                              admission=admission, clock=clock)
