import numpy as np
import pytest

from bench import byname, check, corpus, reference

GEN = byname.load("generators", "clustered_gaussian")


def brute(base, q, k, metric):
    b, q = base.astype(np.float64), q.astype(np.float64)
    if metric == "cosine":
        b = b / np.linalg.norm(b, axis=1, keepdims=True)
        q = q / np.linalg.norm(q, axis=1, keepdims=True)
    if metric == "l2":
        d = ((q[:, None, :] - b[None, :, :]) ** 2).sum(-1)
    else:
        d = -(q @ b.T)
    ids = np.argsort(d, axis=1, kind="stable")[:, :k]
    return ids, np.take_along_axis(d, ids, axis=1)


@pytest.fixture(scope="module")
def data():
    cfg = {"n": 700, "dim": 32,
           "data": {"generator": "clustered_gaussian", "n_clusters": 8,
                    "center_scale": 4.0, "seed": 5}}
    d = corpus.config_corpus(cfg)
    q = corpus.Stream(d, 9, corpus.QUERIES).take(50)
    return d.base, q


@pytest.mark.parametrize("metric", reference.METRICS)
def test_reference_matches_numpy_brute_force(data, metric):
    base, q = data
    ids, dists = reference.knn(base, q, 10, metric=metric)
    want_ids, want_d = brute(base, q, 10, metric)
    assert np.array_equal(ids, want_ids)
    np.testing.assert_allclose(dists, want_d, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(reference.exact_dists(base, q, ids, metric),
                               want_d, rtol=1e-12)
    bad = ids.copy()
    bad[0, 0] = base.shape[0]
    assert np.isnan(reference.exact_dists(base, q, bad, metric)[0, 0])
    r = check.readings(base, q, ids, dists, 10, metric)
    assert r["bad_rows"] == 0 and r["dist_gap"] < check.DIST_GAP_LIMIT
    assert np.all(r["recall_per_query"] == 1.0)


def test_reference_refuses_a_metric_it_does_not_implement(data):
    base, q = data
    with pytest.raises(ValueError):
        reference.knn(base, q, 10, metric="hamming")
    with pytest.raises(ValueError):
        reference.exact_dists(base, q, np.zeros((50, 10), np.int64),
                              "hamming")


def test_bf16_control_is_lower_precision(data):
    base, q = data
    ids, d = reference.knn(base, q, 10, "bf16")
    err = np.max(np.abs(reference.exact_dists(base, q, ids) - d)
                 / reference.exact_dists(base, q, ids))
    assert err > 1e-5


def test_corpus_equals_the_programs_generator():
    from repro.data import make_vector_dataset
    ds = make_vector_dataset("sift", n=500, n_queries=4, k=10,
                             n_clusters=8, seed=21)
    base, centers = GEN.corpus(500, 128, n_clusters=8, center_scale=4.0,
                               seed=21)
    assert np.array_equal(base, ds.base)
    assert np.array_equal(centers, ds.centers)


def test_unknown_pieces_are_refused():
    with pytest.raises(SystemExit):
        byname.load("generators", "uniform_cube")
    with pytest.raises(SystemExit):
        byname.load("traffic", "../run")
