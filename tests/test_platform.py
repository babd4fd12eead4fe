"""The row-tiled kernel table layout, where it is made, and the
compile-cache placement."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.graph import make_padded_csr
from repro.kernels.platform import interprets, row_tiles
from repro.kernels.registry import make_dist_fn, with_kernel_tables
from repro.launch import compile_cache


@pytest.mark.parametrize("d,k", [(24, 1), (128, 1), (200, 2), (960, 8)])
def test_row_tiles_pads_rows_to_whole_lane_tiles(d, k):
    x = np.random.RandomState(0).randn(5, d).astype(np.float32)
    t = np.asarray(row_tiles(jnp.asarray(x)))
    assert t.shape == (5, k, 128)
    flat = t.reshape(5, -1)
    np.testing.assert_array_equal(flat[:, :d], x)
    assert not flat[:, d:].any()


def test_padded_csr_carries_vector_tiles():
    # only a Pallas backend's searcher gives the graph its tiled table
    x = np.random.RandomState(1).randn(10, 24).astype(np.float32)
    nbrs = np.tile(np.arange(4, dtype=np.int32), (10, 1))
    g = make_padded_csr(nbrs, x)
    assert g.vector_tiles is None and g.code_tiles is None
    assert with_kernel_tables(g, "ref") is g
    gt = with_kernel_tables(g, "rowgather")
    np.testing.assert_array_equal(np.asarray(gt.vector_tiles),
                                  np.asarray(row_tiles(g.vectors)))
    assert gt.code_tiles is None
    assert with_kernel_tables(gt, "dedup_gather") is gt    # laid out once


def test_pallas_backend_without_tiles_raises():
    x = np.random.RandomState(2).randn(10, 24).astype(np.float32)
    nbrs = np.tile(np.arange(4, dtype=np.int32), (10, 1))
    g = make_padded_csr(nbrs, x)
    dist_fn = make_dist_fn("rowgather")
    with pytest.raises(ValueError, match="with_kernel_tables"):
        dist_fn(g, None, jnp.zeros((1, 1, 4), jnp.int32), jnp.asarray(x[:1]))


def test_row_tiles_keeps_leading_axes():
    x = np.random.RandomState(3).randn(2, 5, 200).astype(np.float32)
    t = np.asarray(row_tiles(jnp.asarray(x)))
    assert t.shape == (2, 5, 2, 128)
    np.testing.assert_array_equal(t[1], np.asarray(row_tiles(x[1])))


def test_interpret_mode_follows_the_platform():
    assert interprets() == (jax.default_backend() == "cpu")


@pytest.fixture
def restore_cache_dir():
    old = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", old)


def test_compile_cache_env_var_wins(monkeypatch, restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.use_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_a_fixed_checkout_path(
        monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.use_compile_cache()
    assert path == str(compile_cache.CHECKOUT_CACHE)
    assert compile_cache.CHECKOUT_CACHE.name == ".jax_cache"
    assert (compile_cache.CHECKOUT_CACHE.parent / "chip_smoke.py").is_file()
    assert jax.config.jax_compilation_cache_dir == path
    assert compile_cache.use_compile_cache() == path      # same every call


@pytest.mark.parametrize("kernel", ["rowgather", "dedup_gather"])
def test_split_batch_launches_match_one_launch(monkeypatch, kernel):
    from repro.kernels import platform
    from repro.kernels.dedup import dedupdist
    from repro.kernels.l2dist import l2dist_rowgather
    from repro.kernels.ref import dist_ref
    fn = {"rowgather": l2dist_rowgather, "dedup_gather": dedupdist}[kernel]
    rng = np.random.RandomState(4)
    n, d, b, c = 50, 16, 5, 8
    table = jnp.asarray(rng.randn(n, d), jnp.float32)
    ids = jnp.asarray(rng.randint(0, n + 2, size=(b, c)), jnp.int32)
    q = jnp.asarray(rng.randn(b, d), jnp.float32)
    whole = np.asarray(fn(row_tiles(table), ids, q))
    monkeypatch.setattr(platform, "MAX_PAIRS", 2 * c)   # 3 launches
    split = np.asarray(fn(row_tiles(table), ids, q))
    np.testing.assert_array_equal(split, whole)
    np.testing.assert_allclose(whole, np.asarray(dist_ref(table, ids, q)),
                               rtol=1e-5, atol=1e-5)
