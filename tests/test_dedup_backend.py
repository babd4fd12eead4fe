"""Parity matrix for the batch-dedup distance backends.

``dedup_gather`` reorganizes the step's gather traffic (each distinct row
fetched once for the whole batch) without changing WHAT is computed, so its
contract is equality with the per-lane backends:

* vs ``rowgather`` / ``rowgather_int8`` / ``ref_int8`` — BIT-IDENTICAL
  (same per-pair op order; the int8 path's integer accumulation is exact).
* vs the f32 ``ref`` backend — identical traversals (ids and every
  SearchStats counter bit-equal) with distances equal to float tolerance:
  XLA fuses the pure-jnp (B, C, d) reduction with a different f32
  accumulation order than the Pallas kernels' per-pair (d,) sums, a
  last-ulp reassociation the repo's kernel tests have always allowed
  (see tests/test_kernels.py tolerances).

Covers topm|speedann x l2|ip|cosine x B in {1, 8, 64}, plus the degenerate
all-duplicates batch (every lane expands the same vertices) and the
no-overlap batch (kernel-level, where disjoint lanes can be constructed).
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ann.index import quantize_graph
from repro.core import build_nsg
from repro.core.bfis import search_topm_batch
from repro.core.config import SearchConfig
from repro.core.speedann import search_speedann_batch
from repro.data import make_vector_dataset
from repro.kernels.dedup import (dedupdist, dedupdist_int8,
                                 make_dedup_int8_dist_fn, unique_ids_inverse)
from repro.kernels.l2dist import l2dist_rowgather
from repro.kernels.platform import row_tiles
from repro.kernels.ref import dist_ref
from repro.kernels.registry import available_backends, with_kernel_tables
from repro.quant.codec import fit_scales, quantize
from repro.quant.scheme import QuantSpec, required_quant_dtype

K = 10
BASE = SearchConfig(k=K, queue_len=32, m_max=3, staged=False, max_steps=96)
SPEED = BASE.with_(m_max=4, num_walkers=4, staged=True, local_steps=4)
ALGOS = {"topm": search_topm_batch, "speedann": search_speedann_batch}


@pytest.fixture(scope="module")
def ds():
    return make_vector_dataset("deep", n=600, n_queries=64, k=K, dim=16,
                               n_clusters=8, seed=11)


@pytest.fixture(scope="module")
def graphs(ds):
    """One graph per metric (cosine = l2 build on normalized vectors)."""
    out = {"l2": build_nsg(ds.base, degree=10, knn_k=10, ef_construction=20,
                           passes=1)}
    base = np.asarray(ds.base, np.float32)
    out["ip"] = build_nsg(base, degree=10, knn_k=10, ef_construction=20,
                          passes=1, metric="ip")
    normed = base / np.maximum(
        np.linalg.norm(base, axis=1, keepdims=True), 1e-12)
    out["cosine"] = build_nsg(normed, degree=10, knn_k=10,
                              ef_construction=20, passes=1)
    # with the row-tiled tables the Pallas backends read
    return {m: with_kernel_tables(g, "rowgather") for m, g in out.items()}


def queries_for(ds, metric, b):
    q = jnp.asarray(ds.queries[:b])
    if metric == "cosine":
        q = q / jnp.maximum(jnp.linalg.norm(q, axis=1, keepdims=True), 1e-12)
    return q


def assert_search_parity(fn, graph, q, cfg):
    """dedup_gather == rowgather bit for bit; == ref up to f32 fusion."""
    i_d, d_d, s_d = fn(graph, q, cfg.with_(dist_backend="dedup_gather"))
    i_r, d_r, s_r = fn(graph, q, cfg.with_(dist_backend="rowgather"))
    np.testing.assert_array_equal(np.asarray(i_d), np.asarray(i_r))
    np.testing.assert_array_equal(np.asarray(d_d), np.asarray(d_r))
    for f in s_d._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(s_d, f)), np.asarray(getattr(s_r, f)),
            err_msg=f"stats field {f!r} drifted vs rowgather")
    i_f, d_f, s_f = fn(graph, q, cfg.with_(dist_backend="ref"))
    np.testing.assert_array_equal(np.asarray(i_d), np.asarray(i_f))
    np.testing.assert_allclose(np.asarray(d_d), np.asarray(d_f),
                               rtol=1e-5, atol=1e-5)
    for f in s_d._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(s_d, f)), np.asarray(getattr(s_f, f)),
            err_msg=f"stats field {f!r} drifted vs ref")
    return i_d, d_d, s_d


def test_backends_registered():
    have = available_backends()
    assert "dedup_gather" in have and "dedup_gather_int8" in have
    # the facade's quant validation picks the codes table up from the name
    assert required_quant_dtype("dedup_gather_int8") == "int8"
    assert required_quant_dtype("dedup_gather") == "none"


@pytest.mark.parametrize("algo", ["topm", "speedann"])
@pytest.mark.parametrize("metric", ["l2", "ip", "cosine"])
def test_search_parity_matrix(ds, graphs, algo, metric):
    cfg = (BASE if algo == "topm" else SPEED).with_(metric=metric)
    assert_search_parity(ALGOS[algo], graphs[metric], queries_for(ds, metric, 8),
                         cfg)


@pytest.mark.parametrize("algo", ["topm", "speedann"])
@pytest.mark.parametrize("b", [1, 64])
def test_search_parity_batch_sizes(ds, graphs, algo, b):
    """B=1 (no cross-query overlap at all) and the wide batch; l2 keeps the
    matrix affordable — the metric axis is covered at B=8 above."""
    cfg = BASE if algo == "topm" else SPEED
    assert_search_parity(ALGOS[algo], graphs["l2"], queries_for(ds, "l2", b),
                         cfg)


def test_all_duplicates_batch(ds, graphs):
    """Every lane expands the same vertices: identical queries make the
    degenerate maximal-overlap batch.  First-toucher attribution charges
    lane 0 with every gather; the dedup backend still matches ref."""
    q = jnp.broadcast_to(jnp.asarray(ds.queries[:1]), (8, ds.queries.shape[1]))
    ids, dists, stats = assert_search_parity(search_topm_batch, graphs["l2"],
                                             q, BASE)
    # all lanes identical -> ids identical across the batch
    np.testing.assert_array_equal(np.asarray(ids),
                                  np.broadcast_to(np.asarray(ids)[:1],
                                                  ids.shape))
    u = np.asarray(stats.uniq_comps)
    d = np.asarray(stats.dist_comps)
    dup = np.asarray(stats.batch_dup_comps)
    assert (u + dup == d).all()
    np.testing.assert_array_equal(u[1:], 0)        # lane 0 first-touches all
    assert u[0] == d[0]
    np.testing.assert_array_equal(dup[1:], d[1:])


@pytest.mark.parametrize("metric", ["l2", "ip"])
def test_kernel_no_overlap_and_all_dup(metric):
    """Kernel-level degenerate batches (disjoint lanes are constructible
    here, unlike in a traversal that shares the entry point)."""
    rng = np.random.RandomState(3)
    n, d, b, c = 64, 16, 4, 8
    table = jnp.asarray(rng.randn(n, d), jnp.float32)
    q = jnp.asarray(rng.randn(b, d), jnp.float32)
    # no overlap: every lane's ids disjoint -> T holds all B*C of them
    ids = jnp.arange(b * c, dtype=jnp.int32).reshape(b, c)
    np.testing.assert_array_equal(
        np.asarray(dedupdist(row_tiles(table), ids, q, metric=metric)),
        np.asarray(l2dist_rowgather(row_tiles(table), ids, q, metric=metric)))
    _, _, n_uniq = unique_ids_inverse(ids, n)
    assert int(n_uniq) == b * c
    # all duplicates: one id everywhere -> a single real gather
    ids = jnp.full((b, c), 7, jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(dedupdist(row_tiles(table), ids, q, metric=metric)),
        np.asarray(l2dist_rowgather(row_tiles(table), ids, q, metric=metric)))
    _, _, n_uniq = unique_ids_inverse(ids, n)
    assert int(n_uniq) == 1


@pytest.mark.parametrize("metric", ["l2", "ip"])
def test_kernel_matches_ref_with_padding(metric):
    rng = np.random.RandomState(0)
    n, d, b, c = 50, 16, 6, 9
    table = jnp.asarray(rng.randn(n, d), jnp.float32)
    q = jnp.asarray(rng.randn(b, d), jnp.float32)
    ids = jnp.asarray(rng.randint(0, n + 1, size=(b, c)), jnp.int32)
    got = np.asarray(dedupdist(row_tiles(table), ids, q, metric=metric))
    np.testing.assert_array_equal(
        got, np.asarray(l2dist_rowgather(row_tiles(table), ids, q, metric=metric)))
    np.testing.assert_allclose(
        got, np.asarray(dist_ref(table, ids, q, metric=metric)),
        rtol=1e-5, atol=1e-5)
    assert np.isinf(got[np.asarray(ids) >= n]).all()


# -- int8 variant -----------------------------------------------------------

def quantized(graph, dtype="int8"):
    return with_kernel_tables(quantize_graph(graph, QuantSpec(dtype=dtype)),
                              "rowgather_int8")


@pytest.mark.parametrize("metric", ["l2", "ip", "cosine"])
@pytest.mark.parametrize("b", [1, 8])
def test_int8_search_bit_identity(ds, graphs, metric, b):
    """dedup_gather_int8 == ref_int8 == rowgather_int8 BIT-identically: the
    integer dot is exact, so no fusion reassociation can leak in."""
    gq = quantized(graphs[metric])
    q = queries_for(ds, metric, b)
    cfg = BASE.with_(metric=metric)
    i_d, d_d, s_d = search_topm_batch(gq, q,
                                      cfg.with_(dist_backend="dedup_gather_int8"))
    for other in ("ref_int8", "rowgather_int8"):
        i_o, d_o, s_o = search_topm_batch(gq, q, cfg.with_(dist_backend=other))
        np.testing.assert_array_equal(np.asarray(i_d), np.asarray(i_o),
                                      err_msg=other)
        np.testing.assert_array_equal(np.asarray(d_d), np.asarray(d_o),
                                      err_msg=other)
        for f in s_d._fields:
            np.testing.assert_array_equal(
                np.asarray(getattr(s_d, f)), np.asarray(getattr(s_o, f)),
                err_msg=f"{other}:{f}")


def test_int8_kernel_all_dup_batch64(graphs):
    """Wide-batch int8 kernel parity on a high-overlap id grid."""
    gq = quantized(graphs["l2"])
    rng = np.random.RandomState(1)
    b, c = 64, 8
    ids = jnp.asarray(rng.randint(0, 12, size=(b, c)), jnp.int32)  # heavy dup
    q = jnp.asarray(rng.randn(b, gq.vectors.shape[1]), jnp.float32)
    from repro.quant.kernels import int8dist_rowgather
    np.testing.assert_array_equal(
        np.asarray(dedupdist_int8(gq.code_tiles, gq.scales, ids, q)),
        np.asarray(int8dist_rowgather(gq.code_tiles, gq.scales, ids, q)))


def test_int8_per_dim_scales_rejected(graphs):
    g = graphs["l2"]
    spec = QuantSpec(dtype="int8", per_dim=True)
    scales = fit_scales(g.vectors, spec)
    gq = g._replace(codes=quantize(g.vectors, spec, scales),
                    scales=jnp.asarray(scales, jnp.float32))
    fn = make_dedup_int8_dist_fn()
    with pytest.raises(NotImplementedError, match="per-vector"):
        fn(gq, jnp.zeros((1, 1), jnp.int32), jnp.zeros((1, 1, 4), jnp.int32),
           jnp.zeros((1, gq.vectors.shape[1]), jnp.float32))
