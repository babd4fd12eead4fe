"""Batched graph construction: parity, determinism, incremental add/delete.

The contract under test (core/build.py): ``build_batch`` and
``build_backend`` are COMPUTE TILES, never semantics — the built graph is
bit-identical to the scalar per-point reference builder for every batch
size, every within-batch permutation, and across repeated runs.  On top of
that, the incremental paths (``AnnIndex.add`` / ``.delete``) must keep the
index consistent end to end: recall within 0.02 of a from-scratch rebuild,
tombstoned ids excluded from every search/exact result, quant codes/scales
and the npz round-trip intact after mutation.
"""
import numpy as np
import pytest

from repro.ann import AnnIndex, IndexSpec, SearchParams
from repro.core.build import (_upper_level_ids, build_nsg, build_nsg_serial,
                              connect_from, exact_knn, knn_graph, reachable)
from repro.data import make_vector_dataset
from repro.core.graph import remap_sentinels

DEGREE = 8
EF = 16
N = 160
DIM = 12


def _data(n=N, dim=DIM, seed=0):
    return np.random.RandomState(seed).randn(n, dim).astype(np.float32)


def _graph_bytes(g):
    return np.asarray(g.nbrs).tobytes() + np.asarray(g.medoid).tobytes()


def _recall(index, queries, params, k=5):
    res = index.search(queries, params)
    gt, _ = index.exact(queries, k)
    ids = np.asarray(res.ids)
    return sum(len(set(r) & set(g))
               for r, g in zip(ids.tolist(), gt.tolist())) / gt.size


# ---------------------------------------------------------------------------
# bit-parity + determinism of the batched builder
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("metric", ["l2", "ip"])
@pytest.mark.parametrize("passes", [1, 2])
def test_batch1_matches_serial_reference(metric, passes):
    data = _data()
    kw = dict(degree=DEGREE, ef_construction=EF, alpha=1.2, seed=0,
              passes=passes, metric=metric)
    serial = build_nsg_serial(data, **kw)
    batched = build_nsg(data, build_batch=1, **kw)
    assert _graph_bytes(batched) == _graph_bytes(serial)


def test_batch_size_and_permutation_invariance():
    data = _data()
    kw = dict(degree=DEGREE, ef_construction=EF, alpha=1.2, seed=0,
              passes=2)
    ref = _graph_bytes(build_nsg(data, build_batch=1, **kw))
    for batch in (7, 64):
        assert _graph_bytes(build_nsg(data, build_batch=batch, **kw)) == ref
    # permuting every search chunk must not change a bit (per-lane
    # independence of the batch-major engine), nor may a re-run
    assert _graph_bytes(build_nsg(data, build_batch=32, batch_perm=3,
                                  **kw)) == ref
    assert _graph_bytes(build_nsg(data, build_batch=32, **kw)) == ref


def test_built_graph_recall():
    data = _data(n=300)
    index = AnnIndex.build(data, IndexSpec(degree=12, ef_construction=24))
    r = _recall(index, data[:32], SearchParams(k=5, queue_len=32,
                                               max_steps=64))
    assert r >= 0.9, f"batched build recall {r}"


# ---------------------------------------------------------------------------
# incremental add
# ---------------------------------------------------------------------------

def test_add_recall_close_to_rebuild():
    rng = np.random.RandomState(1)
    data = rng.randn(320, DIM).astype(np.float32)
    extra = rng.randn(40, DIM).astype(np.float32)
    full = np.concatenate([data, extra])
    spec = IndexSpec(degree=DEGREE, ef_construction=2 * EF)
    params = SearchParams(k=5, queue_len=32, max_steps=64)

    inc = AnnIndex.build(data, spec)
    new_ids = inc.add(extra)
    assert new_ids.tolist() == list(range(320, 360))
    assert inc.n_nodes == 360 and inc.n_alive == 360

    rebuilt = AnnIndex.build(full, spec)
    r_inc = _recall(inc, full[:48], params)
    r_full = _recall(rebuilt, full[:48], params)
    assert r_inc >= r_full - 0.02, (r_inc, r_full)

    # added vectors must be findable as their own nearest neighbor
    res = inc.search(extra[:16], params)
    found = np.asarray(res.ids)[:, 0]
    assert (found == np.arange(320, 336)).mean() >= 0.8


def test_add_cosine_normalizes():
    rng = np.random.RandomState(2)
    data = rng.randn(200, DIM).astype(np.float32)
    extra = 50.0 * rng.randn(10, DIM).astype(np.float32)  # wild norms
    index = AnnIndex.build(data, IndexSpec(degree=DEGREE, metric="cosine",
                                           ef_construction=EF))
    index.add(extra)
    norms = np.linalg.norm(np.asarray(index.graph.vectors)[200:], axis=1)
    np.testing.assert_allclose(norms, 1.0, atol=1e-5)


def test_add_quant_preserves_existing_codes_and_roundtrips(tmp_path):
    rng = np.random.RandomState(3)
    data = rng.randn(240, DIM).astype(np.float32)
    extra = rng.randn(24, DIM).astype(np.float32)
    spec = IndexSpec(degree=DEGREE, ef_construction=EF, quant="int8")
    index = AnnIndex.build(data, spec)
    before = np.asarray(index.graph.codes).copy()
    index.add(extra)
    # per-vector scales: old rows' codes must be bit-untouched
    np.testing.assert_array_equal(np.asarray(index.graph.codes)[:240],
                                  before)
    assert index.graph.codes.shape == (264, DIM)
    assert index.graph.scales.shape == (264, 1)

    params = SearchParams(k=5, queue_len=32, max_steps=64,
                          backend="ref_int8", rerank_k=16)
    path = index.save(str(tmp_path / "inc_quant"))
    loaded = AnnIndex.load(path)
    q = data[:8]
    np.testing.assert_array_equal(np.asarray(index.search(q, params).ids),
                                  np.asarray(loaded.search(q, params).ids))


def test_add_rejects_hnsw_and_bad_shapes():
    data = _data(n=120)
    hn = AnnIndex.build(data, IndexSpec(builder="hnsw", degree=DEGREE))
    with pytest.raises(NotImplementedError):
        hn.add(data[:2])
    index = AnnIndex.build(data, IndexSpec(degree=DEGREE,
                                           ef_construction=EF))
    with pytest.raises(ValueError):
        index.add(np.zeros((2, DIM + 1), np.float32))
    assert index.add(np.zeros((0, DIM), np.float32)).shape == (0,)


# ---------------------------------------------------------------------------
# incremental delete
# ---------------------------------------------------------------------------

def test_delete_excludes_tombstoned_ids():
    data = _data(n=300, seed=4)
    index = AnnIndex.build(data, IndexSpec(degree=12, ef_construction=24))
    params = SearchParams(k=5, queue_len=48, max_steps=96)
    queries = data[:16]
    dead = np.unique(np.asarray(index.exact(queries, 2)[0]).ravel())
    assert index.delete(dead) == dead.shape[0]
    assert index.n_alive == 300 - dead.shape[0]
    # idempotent: deleting again is a no-op
    assert index.delete(dead) == 0

    ids = np.asarray(index.search(queries, params).ids)
    assert not np.isin(ids, dead).any()
    gt, _ = index.exact(queries, 5)
    assert not np.isin(gt, dead).any()
    # the graph stays navigable around the holes
    r = _recall(index, queries, params)
    assert r >= 0.85, f"post-delete recall {r}"


def test_delete_medoid_reelects_entry(tmp_path):
    data = _data(n=200, seed=5)
    index = AnnIndex.build(data, IndexSpec(degree=DEGREE,
                                           ef_construction=EF))
    params = SearchParams(k=5, queue_len=32, max_steps=64)
    med = int(index.graph.medoid)
    index.delete([med])
    assert int(index.graph.medoid) != med
    ids = np.asarray(index.search(data[:8], params).ids)
    assert not np.isin(ids, [med]).any()

    # tombstones survive the npz round-trip (format 3)
    path = index.save(str(tmp_path / "tomb"))
    loaded = AnnIndex.load(path)
    assert loaded.n_alive == index.n_alive
    np.testing.assert_array_equal(
        np.asarray(loaded.search(data[:8], params).ids), ids)


def test_delete_refuses_everything():
    data = _data(n=50, seed=6)
    index = AnnIndex.build(data, IndexSpec(degree=DEGREE,
                                           ef_construction=EF))
    with pytest.raises(ValueError):
        index.delete(np.arange(50))


# ---------------------------------------------------------------------------
# satellites: knn_graph vectorization, sentinel remapping, hnsw upper ids
# ---------------------------------------------------------------------------

# ---------------------------------------------------------------------------
# connectivity from the navigating node (NSG's tree-spanning step)
# ---------------------------------------------------------------------------

def _islands(n_islands=4, size=10, degree=3):
    """Rings of ``size`` vertices, each vertex linked to its next
    ``degree`` ring mates: full rows, no edge between islands."""
    ids = np.arange(n_islands * size).reshape(n_islands, size)
    nbrs = np.stack([np.roll(ids, -j, axis=1).reshape(-1)
                     for j in range(1, degree + 1)], axis=1)
    rng = np.random.RandomState(0)
    centers = rng.randn(n_islands, DIM).astype(np.float32) * 20
    data = (np.repeat(centers, size, axis=0)
            + rng.randn(n_islands * size, DIM).astype(np.float32))
    return nbrs.astype(np.int32), data


def test_reachable_follows_out_edges():
    nbrs, _ = _islands()
    n = nbrs.shape[0]
    assert reachable(nbrs, [0]).sum() == 10
    assert reachable(nbrs, [0, 25]).sum() == 20
    padded = np.full_like(nbrs, n)
    assert reachable(padded, [3]).tolist() == [i == 3 for i in range(n)]


def test_connect_from_links_every_island():
    nbrs, data = _islands()
    n, degree = nbrs.shape
    before = nbrs.copy()
    added = connect_from(nbrs, data, 0, ef=EF, metric="l2")
    assert added == 3                       # one link per unreached island
    assert reachable(nbrs, [0]).all()
    assert nbrs.shape == (n, degree) and (nbrs < n).all()
    assert (nbrs != np.arange(n)[:, None]).all()
    assert (nbrs != before).sum() == added  # each link took one slot
    again = nbrs.copy()
    assert connect_from(nbrs, data, 0, ef=EF, metric="l2") == 0
    np.testing.assert_array_equal(nbrs, again)


def test_nsg_build_reaches_every_vertex_from_the_medoid():
    # well-separated clusters: degree-capped pruning fills each row inside
    # its own cluster, so only the connectivity step links the clusters
    ds = make_vector_dataset("deep", n=1200, n_queries=16, k=10, dim=24,
                             n_clusters=12, seed=7)
    g = build_nsg(ds.base, degree=12, knn_k=12, ef_construction=24,
                  passes=2)
    assert reachable(np.asarray(g.nbrs), [int(g.medoid)]).all()


def test_knn_graph_matches_loop_reference():
    data = _data(n=90, seed=7)
    k = 6
    got = knn_graph(data, k)
    ids, _ = exact_knn(data, data, k + 1)
    n = data.shape[0]
    want = np.full((n, k), n, np.int32)
    for i in range(n):
        row = [j for j in ids[i] if j != i][:k]
        want[i, :len(row)] = row
    np.testing.assert_array_equal(got, want)


def test_remap_sentinels():
    nbrs = np.asarray([[0, 5, 3], [2, -1, 9]], np.int32)
    got = remap_sentinels(nbrs, old_n=5, new_n=8)
    np.testing.assert_array_equal(
        got, np.asarray([[0, 8, 3], [2, 8, 8]], np.int32))


def test_upper_level_ids_sentinel_never_aliases():
    members = np.asarray([4, 9, 17], np.int32)
    sub_knn = np.asarray([[1, 2, 3], [0, 3, 3]], np.int32)  # 3 == sub-sentinel
    got = _upper_level_ids(sub_knn, members, n=20)
    np.testing.assert_array_equal(
        got, np.asarray([[9, 17, 20], [4, 20, 20]], np.int32))
