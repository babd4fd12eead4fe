"""Batch-major traversal engine: parity with the per-query path.

The batch-major engine (one ``lax.while_loop`` over batch-leading state,
one distance launch per global step) replaced per-query searches under
``jax.vmap``.  Its contract is BIT-IDENTITY: for every algorithm × backend
× metric × quantization, ``search_*_batch(graph, Q)`` must equal
``jax.vmap(search_*)(Q)`` exactly — ids, dists, AND every SearchStats
counter (converged lanes are masked no-ops, so per-query counters cannot
drift).  Batch composition must also be invisible: a query's result cannot
depend on which other queries share its batch.

The one sanctioned exception is ``SearchStats.BATCH_RELATIVE``
(``uniq_comps`` / ``batch_dup_comps``): those are DEFINED relative to the
batch (first-toucher attribution across the step's flattened lanes), so the
vmapped per-query run yields the B=1 values, not the cross-query ones.
They still obey hard invariants checked here — ``uniq + dup == dist_comps``
per lane, batched uniq <= per-query uniq — and stay exact under
front-slicing and batch permutation.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ann import AnnIndex, IndexSpec, SearchParams
from repro.ann.index import quantize_graph
from repro.core import build_nsg, recall_at_k
from repro.core.bfis import search_topm, search_topm_batch
from repro.core.config import SearchConfig
from repro.core.metrics import SearchStats, batch_unique_counts
from repro.core.speedann import search_speedann, search_speedann_batch
from repro.data import make_vector_dataset
from repro.kernels.registry import with_kernel_tables
from repro.quant.scheme import QuantSpec

K = 10


@pytest.fixture(scope="module")
def ds():
    return make_vector_dataset("deep", n=900, n_queries=8, k=K, dim=16,
                               n_clusters=8, seed=11)


@pytest.fixture(scope="module")
def graph(ds):
    # with the row-tiled table the Pallas backends read
    return with_kernel_tables(
        build_nsg(ds.base, degree=12, knn_k=12, ef_construction=24,
                  passes=1), "rowgather")


def quantized(graph, dtype):
    return with_kernel_tables(quantize_graph(graph, QuantSpec(dtype=dtype)),
                              "rowgather_int8")


BASE = SearchConfig(k=K, queue_len=32, m_max=3, staged=False, max_steps=96)
SPEED = BASE.with_(m_max=4, num_walkers=4, staged=True, local_steps=4)


def assert_batch_matches_vmap(batch_fn, single_fn, graph, queries, cfg):
    """The acceptance bar: batched == vmapped per-query, bit for bit
    (batch-relative overlap counters verify their invariants instead)."""
    ids_b, d_b, st_b = batch_fn(graph, queries, cfg)
    ids_v, d_v, st_v = jax.vmap(
        lambda q: single_fn(graph, q, cfg))(queries)
    np.testing.assert_array_equal(np.asarray(ids_b), np.asarray(ids_v))
    np.testing.assert_array_equal(np.asarray(d_b), np.asarray(d_v))
    for field in st_b._fields:
        if field in SearchStats.BATCH_RELATIVE:
            continue
        np.testing.assert_array_equal(
            np.asarray(getattr(st_b, field)),
            np.asarray(getattr(st_v, field)),
            err_msg=f"stats field {field!r} drifted")
    for st in (st_b, st_v):
        u, dup, dc = (np.asarray(st.uniq_comps),
                      np.asarray(st.batch_dup_comps),
                      np.asarray(st.dist_comps))
        np.testing.assert_array_equal(u + dup, dc)
        assert (u >= 0).all() and (dup >= 0).all()
    # a wider batch can only add first-touchers AHEAD of a lane
    assert (np.asarray(st_b.uniq_comps)
            <= np.asarray(st_v.uniq_comps)).all()
    return ids_b


@pytest.mark.parametrize("backend", ["ref", "rowgather", "dma"])
def test_topm_batch_bit_identical_fp32_backends(ds, graph, backend):
    q = jnp.asarray(ds.queries)
    ids = assert_batch_matches_vmap(
        search_topm_batch, search_topm, graph, q,
        BASE.with_(dist_backend=backend))
    assert recall_at_k(np.asarray(ids), ds.gt_ids, K) >= 0.9


@pytest.mark.parametrize("backend", ["ref", "dma"])
def test_speedann_batch_bit_identical(ds, graph, backend):
    q = jnp.asarray(ds.queries)
    ids = assert_batch_matches_vmap(
        search_speedann_batch, search_speedann, graph, q,
        SPEED.with_(dist_backend=backend))
    assert recall_at_k(np.asarray(ids), ds.gt_ids, K) >= 0.9


@pytest.mark.parametrize("backend,dtype", [
    ("ref_int8", "int8"), ("rowgather_int8", "int8"), ("ref_bf16", "bf16")])
def test_batch_bit_identical_quant_backends(ds, graph, backend, dtype):
    gq = quantized(graph, dtype)
    q = jnp.asarray(ds.queries)
    assert_batch_matches_vmap(
        search_topm_batch, search_topm, gq, q,
        BASE.with_(dist_backend=backend))


@pytest.mark.parametrize("metric", ["ip", "cosine"])
def test_batch_bit_identical_across_metrics(ds, metric):
    base = np.asarray(ds.base, np.float32)
    if metric == "cosine":
        base = base / np.maximum(
            np.linalg.norm(base, axis=1, keepdims=True), 1e-12)
    g = build_nsg(base, degree=12, knn_k=12, ef_construction=24, passes=1,
                  metric="l2" if metric == "cosine" else metric)
    q = jnp.asarray(ds.queries)
    if metric == "cosine":
        q = q / jnp.maximum(jnp.linalg.norm(q, axis=1, keepdims=True), 1e-12)
    assert_batch_matches_vmap(search_topm_batch, search_topm, g, q,
                              BASE.with_(metric=metric))
    assert_batch_matches_vmap(search_speedann_batch, search_speedann, g, q,
                              SPEED.with_(metric=metric))


def test_batch_composition_is_invisible(ds, graph):
    """A query's result must not depend on its batch mates: lanes that
    converge early are exact no-ops while stragglers keep looping."""
    q = jnp.asarray(ds.queries)
    ids_all, d_all, st_all = search_topm_batch(graph, q, BASE)
    # front slice of the batch vs the same queries in a smaller batch
    ids_sub, d_sub, st_sub = search_topm_batch(graph, q[:3], BASE)
    np.testing.assert_array_equal(np.asarray(ids_all)[:3],
                                  np.asarray(ids_sub))
    np.testing.assert_array_equal(np.asarray(d_all)[:3], np.asarray(d_sub))
    for field in st_all._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(st_all, field))[:3],
            np.asarray(getattr(st_sub, field)))
    # B=1 wrapper == the corresponding batch row
    ids_1, d_1, st_1 = search_topm(graph, q[5], BASE)
    np.testing.assert_array_equal(np.asarray(ids_all)[5], np.asarray(ids_1))
    np.testing.assert_array_equal(np.asarray(d_all)[5], np.asarray(d_1))
    assert int(st_1.steps) == int(np.asarray(st_all.steps)[5])


def test_facade_batch_parity_with_rerank(ds):
    """The two-stage (quantized traverse + exact re-rank) facade search is
    batch-major end to end and batch-composition invariant."""
    index = AnnIndex.build(ds.base, IndexSpec(degree=12, passes=1,
                                              quant="int8"))
    params = SearchParams(k=K, queue_len=32, max_steps=96,
                          backend="ref_int8", rerank_k=2 * K)
    q = np.asarray(ds.queries)
    full = index.search(q, params)
    sub = index.search(q[:3], params)
    np.testing.assert_array_equal(np.asarray(full.ids)[:3],
                                  np.asarray(sub.ids))
    np.testing.assert_array_equal(np.asarray(full.dists)[:3],
                                  np.asarray(sub.dists))
    assert recall_at_k(np.asarray(full.ids), ds.gt_ids, K) >= 0.9


def test_engine_inherits_batch_major_path(ds):
    """AnnEngine serves through the index's batch-major searchers: padded
    bucket execution is bit-identical to direct AnnIndex.search."""
    index = AnnIndex.build(ds.base, IndexSpec(degree=12, passes=1))
    params = SearchParams(k=K, queue_len=32, max_steps=96,
                          algorithm="speedann", num_walkers=2)
    engine = index.serve(params, bucket_sizes=(2, 4, 8))
    direct = index.search(ds.queries, params)
    for bsz in (1, 3, 8):
        res = engine.search(ds.queries[:bsz])
        np.testing.assert_array_equal(res.ids,
                                      np.asarray(direct.ids)[:bsz])
        np.testing.assert_array_equal(res.dists,
                                      np.asarray(direct.dists)[:bsz])
    assert engine.jit_cache_size <= 3


def test_max_norm_entry_policy_mips(ds, tmp_path):
    """IndexSpec(entry_policy='max_norm') seeds ip traversals at the
    max-norm vertex, reaches reference recall, and round-trips."""
    rng = np.random.RandomState(3)
    base = np.asarray(ds.base, np.float32) \
        * np.exp(rng.randn(ds.base.shape[0], 1) * 0.6).astype(np.float32)
    spec = IndexSpec(metric="ip", degree=12, passes=1,
                     entry_policy="max_norm")
    index = AnnIndex.build(base, spec)
    norms = np.linalg.norm(base, axis=1)
    assert int(index.graph.medoid) == int(np.argmax(norms))
    gt, _ = index.exact(ds.queries, K)
    res = index.search(ds.queries, SearchParams(k=K, queue_len=64,
                                                max_steps=128))
    assert recall_at_k(np.asarray(res.ids), gt, K) >= 0.9
    # the policy is build-time state: persisted with the spec + medoid
    path = index.save(str(tmp_path / "maxnorm"))
    loaded = AnnIndex.load(path)
    assert loaded.spec.entry_policy == "max_norm"
    assert int(loaded.graph.medoid) == int(index.graph.medoid)
    # default-policy artifacts must NOT carry the key: readers predating
    # entry_policy construct IndexSpec(**spec_json) and would crash on it
    default_index = AnnIndex.build(base, IndexSpec(metric="ip", degree=12,
                                                   passes=1))
    dpath = default_index.save(str(tmp_path / "default"))
    import json as _json
    spec_json = _json.loads(str(np.load(dpath)["spec"]))
    assert "entry_policy" not in spec_json
    assert AnnIndex.load(dpath).spec.entry_policy == "medoid"
    # ...and validated at construction
    with pytest.raises(ValueError, match="max_norm"):
        IndexSpec(metric="l2", entry_policy="max_norm")
    with pytest.raises(ValueError, match="entry_policy"):
        IndexSpec(entry_policy="bogus")


# -- cross-query overlap counters (SearchStats.BATCH_RELATIVE) --------------

def test_batch_unique_counts_numpy_recount():
    """The counting primitive matches a transparent pure-NumPy first-toucher
    recount on recorded candidate grids (ids + counted masks exactly as the
    engines hand them over: per-lane distinct, dead lanes masked out)."""
    rng = np.random.RandomState(7)
    for b, c, idmax in [(1, 6, 9), (4, 8, 12), (8, 5, 400), (6, 7, 7)]:
        ids = rng.randint(0, idmax, size=(b, c)).astype(np.int32)
        counted = rng.rand(b, c) > 0.25
        for lane in range(b):           # enforce per-lane distinctness
            _, first_idx = np.unique(ids[lane], return_index=True)
            keep = np.zeros(c, bool)
            keep[first_idx] = True
            counted[lane] &= keep
        got = np.asarray(batch_unique_counts(jnp.asarray(ids),
                                             jnp.asarray(counted)))
        seen, want = set(), np.zeros(b, np.int64)
        for lane in range(b):
            for slot in range(c):
                if counted[lane, slot] and int(ids[lane, slot]) not in seen:
                    seen.add(int(ids[lane, slot]))
                    want[lane] += 1
        np.testing.assert_array_equal(got, want)
        assert got.sum() == len(seen)


@pytest.mark.parametrize("algo,cfg", [("topm", BASE), ("speedann", SPEED)])
def test_overlap_counters_search_invariants(ds, graph, algo, cfg):
    """Search-level exactness: uniq + dup == dist_comps per lane, an
    identical-queries batch charges every gather to lane 0, and a topm
    B=1 run is all-unique."""
    fn = search_topm_batch if algo == "topm" else search_speedann_batch
    q = jnp.asarray(ds.queries)
    _, _, st = fn(graph, q, cfg)
    u, dup, dc = (np.asarray(st.uniq_comps), np.asarray(st.batch_dup_comps),
                  np.asarray(st.dist_comps))
    np.testing.assert_array_equal(u + dup, dc)
    # degenerate all-duplicates batch: identical lanes -> lane 0 first-
    # touches EVERY computation, later lanes are pure reuse
    q_same = jnp.broadcast_to(q[:1], q.shape)
    _, _, st_same = fn(graph, q_same, cfg)
    u, dup, dc = (np.asarray(st_same.uniq_comps),
                  np.asarray(st_same.batch_dup_comps),
                  np.asarray(st_same.dist_comps))
    assert u[0] == dc[0] if algo == "topm" else u[0] <= dc[0]
    np.testing.assert_array_equal(u[1:], 0)
    np.testing.assert_array_equal(dup[1:], dc[1:])
    if algo == "topm":
        # B=1: no other lane exists, every computation is a first touch
        _, _, st1 = fn(graph, q[:1], cfg)
        np.testing.assert_array_equal(np.asarray(st1.uniq_comps),
                                      np.asarray(st1.dist_comps))
        np.testing.assert_array_equal(np.asarray(st1.batch_dup_comps), 0)


@pytest.mark.parametrize("algo,cfg", [("topm", BASE), ("speedann", SPEED)])
def test_overlap_counters_permutation_invariant(ds, graph, algo, cfg):
    """Batch-composition invariance for the overlap counters: per-lane
    attribution follows lane order (first-toucher), but the batch TOTALS —
    how many gathers a dedup backend runs — are permutation invariant, and
    every non-batch-relative counter permutes exactly with its query."""
    fn = search_topm_batch if algo == "topm" else search_speedann_batch
    q = jnp.asarray(ds.queries)
    perm = np.random.RandomState(0).permutation(q.shape[0])
    _, _, st = fn(graph, q, cfg)
    _, _, st_p = fn(graph, q[jnp.asarray(perm)], cfg)
    for field in st._fields:
        a = np.asarray(getattr(st, field))
        b = np.asarray(getattr(st_p, field))
        if field in SearchStats.BATCH_RELATIVE:
            assert a.sum() == b.sum(), field
        else:
            np.testing.assert_array_equal(a[perm], b, err_msg=field)
