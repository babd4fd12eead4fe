"""Properties of the quantization subsystem (repro.quant).

Two layers of guarantees:

* codec: ``dequantize(quantize(x))`` reconstruction error is bounded by the
  scheme's own ``max_error_bound`` (half a quantization step for int8, 2^-8
  relative for bf16) for random vectors — the hypothesis sweep;
* kernels: the quantized distance backends agree with exact f32 arithmetic
  ON THE DEQUANTIZED values to float tolerance (the int32-accumulate +
  rescale path is exact, not an approximation of its own), and with the true
  f32 distances within the analytic error bound, across all three metrics.
"""
import jax.numpy as jnp
import numpy as np
import pytest

try:  # the randomized codec sweeps want hypothesis (requirements-dev, like
    # tests/test_property.py); the kernel parity tests below run without it
    from hypothesis import given, settings, strategies as st
except ImportError:  # pragma: no cover - exercised on bare installs
    class _NoStrategy:
        def __getattr__(self, name):
            return lambda *a, **k: None
    st = _NoStrategy()

    def given(**kw):
        return lambda f: pytest.mark.skip(
            reason="hypothesis not installed")(f)

    def settings(**kw):
        return lambda f: f

from repro.ann.index import quantize_graph  # noqa: E402
from repro.core.graph import make_padded_csr  # noqa: E402
from repro.kernels import resolve_backend  # noqa: E402
from repro.kernels.registry import with_kernel_tables  # noqa: E402
from repro.config import SearchConfig  # noqa: E402
from repro.quant import (QuantSpec, dequantize, fit_scales,  # noqa: E402
                         max_error_bound, quantize, quantize_query)
from repro.quant.kernels import int8dist_rowgather  # noqa: E402

METRICS = ("l2", "ip", "cosine")


def random_vectors(seed, n=64, d=16, scale=3.0):
    rng = np.random.RandomState(seed)
    return (rng.randn(n, d) * scale).astype(np.float32)


# -- codec: reconstruction error bounded by the scheme -----------------------

@given(seed=st.integers(0, 10_000), per_dim=st.booleans(),
       scale=st.sampled_from([1e-3, 1.0, 50.0]))
@settings(max_examples=15, deadline=None)
def test_int8_roundtrip_error_bounded(seed, per_dim, scale):
    x = random_vectors(seed, scale=scale)
    spec = QuantSpec(dtype="int8", per_dim=per_dim)
    scales = fit_scales(x, spec)
    x_hat = np.asarray(dequantize(quantize(x, spec, scales), spec, scales))
    bound = np.asarray(max_error_bound(spec, scales))
    assert np.all(np.abs(x_hat - x) <= bound + 1e-6 * np.abs(x))
    # scales have the documented granularity
    assert scales.shape == ((1, x.shape[1]) if per_dim else (x.shape[0], 1))


@given(seed=st.integers(0, 10_000))
@settings(max_examples=10, deadline=None)
def test_bf16_roundtrip_relative_error_bounded(seed):
    x = random_vectors(seed)
    spec = QuantSpec(dtype="bf16")
    x_hat = np.asarray(dequantize(quantize(x, spec), spec))
    rel = float(np.asarray(max_error_bound(spec, None)))
    assert np.all(np.abs(x_hat - x) <= rel * np.abs(x) + 1e-12)


@given(seed=st.integers(0, 10_000))
@settings(max_examples=10, deadline=None)
def test_query_quantization_error_bounded(seed):
    q = random_vectors(seed, n=3)
    codes, scale = quantize_query(q)
    q_hat = np.asarray(codes, np.float32) * np.asarray(scale)
    assert np.all(np.abs(q_hat - q) <= 0.5 * np.asarray(scale) + 1e-7)


def test_zero_vectors_quantize_cleanly():
    x = np.zeros((4, 8), np.float32)
    spec = QuantSpec(dtype="int8")
    scales = fit_scales(x, spec)
    assert np.all(np.isfinite(np.asarray(scales)))
    assert np.array_equal(np.asarray(quantize(x, spec, scales)),
                          np.zeros((4, 8), np.int8))


# -- kernels: quantized distances vs exact -----------------------------------

def quantized_graph(x, spec):
    n = x.shape[0]
    nbrs = np.tile(np.arange(n, dtype=np.int32)[None, :8], (n, 1))
    return with_kernel_tables(quantize_graph(make_padded_csr(nbrs, x), spec),
                              "rowgather_int8")


def exact_dist(x, q, metric):
    if metric in ("ip", "cosine"):
        return -(x @ q)
    return ((x - q[None, :]) ** 2).sum(axis=1)


@pytest.mark.parametrize("metric", METRICS)
@pytest.mark.parametrize("per_dim", (False, True))
def test_int8_distances_within_scheme_tolerance(metric, per_dim):
    """ref_int8 == exact f32 math on the dequantized table/query (tight),
    and within the analytic quantization bound of the TRUE distances."""
    x = random_vectors(11, n=40, d=16)
    q = random_vectors(12, n=1, d=16)[0]
    spec = QuantSpec(dtype="int8", per_dim=per_dim)
    g = quantized_graph(x, spec)
    dist_fn = resolve_backend(SearchConfig(metric=metric,
                                           dist_backend="ref_int8"))
    # batch-major DistFn contract: (B, M, R) ids, (B, d) queries
    nbr_ids = jnp.arange(40, dtype=jnp.int32).reshape(1, 4, 10)
    got = np.asarray(dist_fn(g, jnp.zeros((1, 4), jnp.int32), nbr_ids,
                             jnp.asarray(q)[None, :])).reshape(-1)

    x_hat = np.asarray(dequantize(g.codes, spec, g.scales))
    if per_dim:
        q_hat = q  # per-dim path keeps the query exact
    else:
        qc, qs = quantize_query(jnp.asarray(q))
        q_hat = np.asarray(qc, np.float32) * float(np.asarray(qs)[0])
    if metric == "l2" and not per_dim:
        # the kernel uses the EXACT ||q||^2 term
        want = (x_hat ** 2).sum(1) - 2 * (x_hat @ q_hat) + (q ** 2).sum()
        want = np.maximum(want, 0.0)
    else:
        want = exact_dist(x_hat, q_hat, metric)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)

    # analytic bound vs TRUE distances: elementwise errors <= s/2 propagate
    # linearly through the dot/norm terms
    true = exact_dist(x, q, metric)
    ex = np.abs(x_hat - x).max()
    eq = np.abs(q_hat - q).max()
    d = x.shape[1]
    big = np.abs(x).max() + np.abs(q).max() + ex + eq
    bound = d * big * (ex + eq) * 4 + 1e-3
    assert np.all(np.abs(got - true) <= bound)


@pytest.mark.parametrize("metric", METRICS)
def test_rowgather_int8_matches_ref_int8(metric):
    """The Pallas scalar-prefetch kernel computes the identical int32
    accumulation + rescale as the jnp reference backend."""
    x = random_vectors(21, n=40, d=16)
    q = random_vectors(22, n=2, d=16)
    spec = QuantSpec(dtype="int8")
    g = quantized_graph(x, spec)
    ids = jnp.asarray(
        np.random.RandomState(5).randint(0, 44, size=(2, 12)), jnp.int32)
    got = np.asarray(int8dist_rowgather(g.code_tiles, g.scales, ids,
                                        jnp.asarray(q), metric=metric))
    ref_fn = resolve_backend(SearchConfig(metric=metric,
                                          dist_backend="ref_int8"))
    for b in range(2):
        want = np.asarray(ref_fn(g, jnp.zeros((1, 1), jnp.int32),
                                 ids[b].reshape(1, 1, -1),
                                 jnp.asarray(q[b])[None, :])).reshape(-1)
        np.testing.assert_allclose(got[b], want, rtol=1e-5, atol=1e-5)
    # padded ids (>= N) are +inf in both
    assert np.all(np.isinf(got[np.asarray(ids) >= 40]))


def test_bf16_distances_close_to_exact():
    x = random_vectors(31, n=40, d=16)
    q = random_vectors(32, n=1, d=16)[0]
    spec = QuantSpec(dtype="bf16")
    g = quantized_graph(x, spec)
    dist_fn = resolve_backend(SearchConfig(metric="l2",
                                           dist_backend="ref_bf16"))
    got = np.asarray(dist_fn(
        g, jnp.zeros((1, 4), jnp.int32),
        jnp.arange(40, dtype=jnp.int32).reshape(1, 4, 10),
        jnp.asarray(q)[None, :])).reshape(-1)
    np.testing.assert_allclose(got, exact_dist(x, q, "l2"), rtol=2e-2,
                               atol=2e-2)
