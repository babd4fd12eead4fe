"""Pallas-kernel validation: shape/dtype sweeps + hypothesis properties,
all against the pure-jnp oracles in kernels/ref.py (interpret mode on CPU).
"""
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.kernels import l2dist, sort_pairs, topl_merge
from repro.kernels import ref as kref
from repro.kernels.platform import row_tiles
from repro.core import queue as fq


def _mk(n, d, b, c, dtype, seed=0):
    rng = np.random.RandomState(seed)
    table = rng.normal(size=(n, d)).astype(dtype)
    ids = rng.randint(0, n + 1, size=(b, c)).astype(np.int32)  # incl. padding
    q = rng.normal(size=(b, d)).astype(dtype)
    return jnp.asarray(table), jnp.asarray(ids), jnp.asarray(q)


@pytest.mark.parametrize("impl", ["rowgather", "dma"])
@pytest.mark.parametrize("n,d,b,c", [
    (64, 8, 2, 16),
    (128, 128, 1, 32),
    (257, 96, 3, 8),     # non-power-of-two N, DEEP dims
    (50, 960, 1, 8),     # GIST dims
])
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_l2dist_matches_ref(impl, n, d, b, c, dtype):
    table, ids, q = _mk(n, d, b, c, dtype)
    got = l2dist(row_tiles(table), ids, q, impl=impl)
    want = kref.l2dist_ref(table, ids, q)
    tol = 1e-5 if dtype == np.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=tol, atol=tol)


@given(
    n=st.integers(4, 300),
    c=st.sampled_from([8, 16, 24]),
    seed=st.integers(0, 2**31 - 1),
)
@settings(max_examples=10, deadline=None)
def test_l2dist_property_padding_and_nonneg(n, c, seed):
    table, ids, q = _mk(n, 16, 2, c, np.float32, seed=seed % 1000)
    got = np.asarray(l2dist(row_tiles(table), ids, q, impl="rowgather"))
    # padding ids -> +inf; real ids -> finite, non-negative
    assert np.isinf(got[np.asarray(ids) >= n]).all()
    real = got[np.asarray(ids) < n]
    assert (real >= -1e-4).all() and np.isfinite(real).all()


@pytest.mark.parametrize("n", [8, 64, 256, 1024])
def test_bitonic_sort_matches_lax_sort(n):
    rng = np.random.RandomState(n)
    b = 3
    keys = rng.normal(size=(b, n)).astype(np.float32)
    keys[0, :3] = np.inf                      # inf handling
    keys[1, 1] = keys[1, 2] = keys[1, 3]      # ties -> payload order
    p0 = rng.randint(0, 2**30, size=(b, n)).astype(np.int32)
    p1 = rng.randint(0, 4, size=(b, n)).astype(np.int32)
    ks, p0s, p1s = sort_pairs(jnp.asarray(keys), jnp.asarray(p0),
                              jnp.asarray(p1))
    wk, wp0, wp1 = kref.sort_pairs_ref(jnp.asarray(keys), jnp.asarray(p0),
                                       jnp.asarray(p1))
    np.testing.assert_array_equal(np.asarray(ks), np.asarray(wk))
    np.testing.assert_array_equal(np.asarray(p0s), np.asarray(wp0))
    # p1 may differ only where (key, p0) has full ties
    tie = np.asarray(wk[:, 1:] == wk[:, :-1]) & np.asarray(
        wp0[:, 1:] == wp0[:, :-1])
    if not tie.any():
        np.testing.assert_array_equal(np.asarray(p1s), np.asarray(wp1))


@given(seed=st.integers(0, 10_000))
@settings(max_examples=10, deadline=None)
def test_bitonic_is_permutation_and_sorted(seed):
    rng = np.random.RandomState(seed)
    keys = rng.normal(size=(2, 128)).astype(np.float32)
    p0 = rng.permutation(128).astype(np.int32)[None, :].repeat(2, 0)
    p1 = np.zeros((2, 128), np.int32)
    ks, p0s, _ = sort_pairs(jnp.asarray(keys), jnp.asarray(p0),
                            jnp.asarray(p1))
    ks = np.asarray(ks)
    assert (np.diff(ks, axis=1) >= 0).all()
    for r in range(2):
        assert sorted(np.asarray(p0s)[r].tolist()) == sorted(p0[r].tolist())


def _random_frontier_batch(rng, b, l):
    """Random sorted frontiers with some empty slots."""
    dists = np.sort(rng.uniform(0.0, 10.0, size=(b, l)).astype(np.float32), 1)
    ids = np.zeros((b, l), np.int32)
    for r in range(b):
        ids[r] = rng.choice(10_000, size=l, replace=False).astype(np.int32)
    meta = rng.randint(0, 2, size=(b, l)).astype(np.int32)
    n_empty = rng.randint(0, l // 2)
    if n_empty:
        dists[:, l - n_empty:] = np.inf
        ids[:, l - n_empty:] = 2**31 - 1
        meta[:, l - n_empty:] = 1
    return dists, ids, meta


@given(seed=st.integers(0, 10_000))
@settings(max_examples=15, deadline=None)
def test_topl_merge_matches_queue_insert(seed):
    """The bitonic merge is semantically identical to core.queue.insert."""
    rng = np.random.RandomState(seed)
    b, l, c = 2, 16, 12
    qd, qi, qm = _random_frontier_batch(rng, b, l)
    cd = rng.uniform(0.0, 10.0, size=(b, c)).astype(np.float32)
    ci = rng.choice(10_000, size=(b, c)).astype(np.int32)
    # make some candidates duplicates of queue entries (same dist!)
    for r in range(b):
        for j in range(3):
            src = rng.randint(0, l)
            if qi[r, src] != 2**31 - 1:
                ci[r, j] = qi[r, src]
                cd[r, j] = qd[r, src]

    d2, i2, m2, up = topl_merge(
        jnp.asarray(qd), jnp.asarray(qi), jnp.asarray(qm),
        jnp.asarray(cd), jnp.asarray(ci))

    for r in range(b):
        f = fq.Frontier(ids=jnp.asarray(qi[r]), dists=jnp.asarray(qd[r]),
                        checked=jnp.asarray(qm[r] == 1))
        f2, up_ref, _ = fq.insert(f, jnp.asarray(ci[r]), jnp.asarray(cd[r]))
        np.testing.assert_array_equal(np.asarray(i2[r]), np.asarray(f2.ids))
        np.testing.assert_allclose(np.asarray(d2[r]), np.asarray(f2.dists))
        assert int(up[r]) == int(up_ref)
        got_checked = np.asarray(m2[r] == 1) | (np.asarray(i2[r]) == 2**31 - 1)
        np.testing.assert_array_equal(got_checked, np.asarray(f2.checked))


def test_search_with_pallas_dist_fn_matches_default():
    """End-to-end: BFiS with the Pallas distance kernel == jnp reference."""
    from repro.config import SearchConfig
    from repro.core import bfis_search_batch, build_nsg
    from repro.data import make_vector_dataset
    from repro.kernels import make_dist_fn
    from repro.kernels.registry import with_kernel_tables

    ds = make_vector_dataset("deep", n=800, n_queries=8, k=10, dim=24,
                             n_clusters=8, seed=3)
    g = build_nsg(ds.base, degree=12, knn_k=12, ef_construction=24, passes=1)
    cfg = SearchConfig(k=10, queue_len=32, max_steps=128)
    q = jnp.asarray(ds.queries)
    ids_ref, d_ref, _ = bfis_search_batch(g, q, cfg)
    ids_pal, d_pal, _ = bfis_search_batch(
        with_kernel_tables(g, "rowgather"), q, cfg,
        dist_fn=make_dist_fn("rowgather"))
    np.testing.assert_array_equal(np.asarray(ids_ref), np.asarray(ids_pal))
    np.testing.assert_allclose(np.asarray(d_ref), np.asarray(d_pal),
                               rtol=1e-5, atol=1e-5)


# -- pad_ids_to_tile + dedup unique-pass edge cases -------------------------

def test_pad_ids_to_tile_edges():
    from repro.kernels.registry import pad_ids_to_tile

    # exact tile boundary: returned array IS the input (no copy, no pad)
    ids = jnp.arange(16, dtype=jnp.int32).reshape(2, 8)
    assert pad_ids_to_tile(ids, 8, 100) is ids
    # ragged: padded with the n_nodes sentinel on the last axis only
    ids = jnp.arange(10, dtype=jnp.int32).reshape(2, 5)
    out = pad_ids_to_tile(ids, 8, 100)
    assert out.shape == (2, 8)
    np.testing.assert_array_equal(np.asarray(out)[:, :5], np.asarray(ids))
    np.testing.assert_array_equal(np.asarray(out)[:, 5:], 100)
    # 1D buffers (the dedup unique buffer) pad the same way
    out1 = pad_ids_to_tile(jnp.arange(3, dtype=jnp.int32), 8, 7)
    assert out1.shape == (8,)
    np.testing.assert_array_equal(np.asarray(out1)[3:], 7)


def test_dedup_unique_empty_after_masking():
    """All-padding candidate grids (every id >= n_nodes) leave an EMPTY
    unique set: the buffer is pure sentinel and distances all +inf."""
    from repro.kernels.dedup import dedupdist, unique_ids_inverse

    n, d, b, c = 20, 8, 3, 5
    ids = jnp.full((b, c), n + 2, jnp.int32)
    uniq, inv, n_uniq = unique_ids_inverse(ids, n)
    assert int(n_uniq) == 0
    assert (np.asarray(uniq) >= n).all()
    table = jnp.asarray(np.random.RandomState(0).randn(n, d), jnp.float32)
    q = jnp.asarray(np.random.RandomState(1).randn(b, d), jnp.float32)
    assert np.isinf(np.asarray(dedupdist(row_tiles(table), ids, q))).all()


def test_dedup_unique_count_on_tile_boundary():
    """Unique count exactly at a tile multiple: no sentinel slot is added
    beyond the buffer's fixed size, and the buffer stays tile-aligned."""
    from repro.kernels.dedup import unique_ids_inverse

    ids = jnp.arange(8, dtype=jnp.int32).reshape(1, 8)      # 8 distinct
    uniq, inv, n_uniq = unique_ids_inverse(ids, 100, tile=8)
    assert int(n_uniq) == 8 and uniq.shape == (8,)
    np.testing.assert_array_equal(np.asarray(uniq), np.arange(8))
    np.testing.assert_array_equal(np.asarray(inv), np.arange(8)[None, :])


def test_dedup_n_nodes_smaller_than_tile():
    """n_nodes < tile: clamping in the kernel index_map and sentinel
    padding still agree with the reference."""
    from repro.kernels.dedup import dedupdist
    from repro.kernels.l2dist import l2dist_rowgather

    rng = np.random.RandomState(2)
    n, d, b, c = 3, 8, 2, 5                                # n < TILE=8
    table = jnp.asarray(rng.randn(n, d), jnp.float32)
    q = jnp.asarray(rng.randn(b, d), jnp.float32)
    ids = jnp.asarray(rng.randint(0, n + 2, size=(b, c)), jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(dedupdist(row_tiles(table), ids, q)),
        np.asarray(l2dist_rowgather(row_tiles(table), ids, q)))
