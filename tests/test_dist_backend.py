"""The pluggable distance-backend seam (SearchConfig.dist_backend).

Asserts the acceptance bar for the kernel-backed hot path: inside a *full*
``search_topm`` run the Pallas backends must retrace the reference search —
same result ids, same recall — and the DMA tile padding must be transparent
for candidate counts not divisible by the tile size.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import SearchConfig
from repro.core import (build_nsg, recall_at_k, resolve_dist_fn,
                        search_speedann_batch, search_topm_batch)
from repro.data import make_vector_dataset
from repro.kernels import (available_backends, l2dist, make_dist_fn,
                           pad_ids_to_tile, resolve_backend)
from repro.kernels import ref as kref
from repro.kernels.platform import row_tiles
from repro.kernels.registry import with_kernel_tables


@pytest.fixture(scope="module")
def ds():
    return make_vector_dataset("deep", n=1200, n_queries=16, k=10, dim=24,
                               n_clusters=12, seed=7)


@pytest.fixture(scope="module")
def graph(ds):
    # degree chosen so M*R is NOT a multiple of the DMA tile (see below);
    # with the row-tiled table the Pallas backends read
    return with_kernel_tables(
        build_nsg(ds.base, degree=12, knn_k=12, ef_construction=24,
                  passes=1), "rowgather")


# m_max=3, degree=12 -> C = 36 candidates per expansion, 36 % 8 != 0:
# every "dma" expansion exercises the tile-padding path.
BASE = SearchConfig(k=10, queue_len=48, m_max=3, staged=False, max_steps=128)


def test_registry_exposes_builtin_backends():
    assert set(available_backends()) >= {"ref", "rowgather", "dma"}
    with pytest.raises(ValueError, match="unknown dist_backend"):
        resolve_backend(BASE.with_(dist_backend="nope"))


def test_explicit_dist_fn_overrides_config():
    sentinel = make_dist_fn("rowgather")
    assert resolve_dist_fn(BASE.with_(dist_backend="dma"),
                           sentinel) is sentinel


@pytest.fixture(scope="module")
def ref_run(ds, graph):
    q = jnp.asarray(ds.queries)
    ids, dists, stats = search_topm_batch(
        graph, q, BASE.with_(dist_backend="ref"))
    return np.asarray(ids), np.asarray(dists), stats


@pytest.mark.parametrize("backend", ["rowgather", "dma"])
def test_backend_parity_inside_search_topm(ds, graph, ref_run, backend):
    """Kernel backends retrace the reference search: same ids, same recall."""
    ids_ref, d_ref, _ = ref_run
    ids, dists, _ = search_topm_batch(
        graph, jnp.asarray(ds.queries), BASE.with_(dist_backend=backend))
    ids, dists = np.asarray(ids), np.asarray(dists)
    np.testing.assert_array_equal(ids, ids_ref)
    assert recall_at_k(ids, ds.gt_ids, 10) == \
        recall_at_k(ids_ref, ds.gt_ids, 10)
    fin = np.isfinite(d_ref)
    np.testing.assert_allclose(dists[fin], d_ref[fin], rtol=1e-4, atol=1e-4)


def test_rowgather_distances_bitwise_equal(ds, graph, ref_run):
    """rowgather computes the same diff-and-square reduction as ref —
    distances must match bit for bit, not just approximately."""
    _, d_ref, _ = ref_run
    _, dists, _ = search_topm_batch(
        graph, jnp.asarray(ds.queries), BASE.with_(dist_backend="rowgather"))
    np.testing.assert_array_equal(np.asarray(dists), d_ref)


def test_backend_parity_inside_speedann(ds, graph):
    """Algorithm 3 (private walkers + lazy sync) is also kernel-backed."""
    q = jnp.asarray(ds.queries)
    cfg = BASE.with_(m_max=4, num_walkers=4, staged=True, local_steps=4)
    ids_ref, _, _ = search_speedann_batch(graph, q,
                                          cfg.with_(dist_backend="ref"))
    ids_dma, _, _ = search_speedann_batch(graph, q,
                                          cfg.with_(dist_backend="dma"))
    r_ref = recall_at_k(np.asarray(ids_ref), ds.gt_ids, 10)
    r_dma = recall_at_k(np.asarray(ids_dma), ds.gt_ids, 10)
    assert r_ref >= 0.9
    assert r_dma == r_ref
    np.testing.assert_array_equal(np.asarray(ids_dma), np.asarray(ids_ref))


def test_dma_padding_edge_case_kernel_level():
    """C not divisible by the tile: padded ids are sentinels, distances for
    the real candidates are unaffected, padding slots report +inf."""
    rng = np.random.RandomState(0)
    n, d, c, g = 200, 16, 13, 8          # 13 % 8 != 0
    table = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    ids = jnp.asarray(rng.randint(0, n, size=(c,)).astype(np.int32))
    q = jnp.asarray(rng.normal(size=(1, d)).astype(np.float32))

    padded = pad_ids_to_tile(ids, g, n)
    assert padded.shape[0] == 16
    assert int(padded.shape[0]) % g == 0
    np.testing.assert_array_equal(np.asarray(padded[:c]), np.asarray(ids))
    assert (np.asarray(padded[c:]) == n).all()

    got = l2dist(row_tiles(table), padded[None, :], q, impl="dma", g=g)
    want = kref.l2dist_ref(table, ids[None, :], q)
    np.testing.assert_allclose(np.asarray(got)[0, :c], np.asarray(want)[0],
                               rtol=1e-5, atol=1e-5)
    assert np.isinf(np.asarray(got)[0, c:]).all()


def test_pad_ids_noop_when_aligned():
    ids = jnp.arange(16, dtype=jnp.int32)
    assert pad_ids_to_tile(ids, 8, 100) is ids
