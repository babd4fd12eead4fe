"""Hypothesis property tests on the system's invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core import queue as fq
from repro.core import visited as vs
from repro.core.distributed import _reduce_visited
from repro.core.metrics import batch_unique_counts, recall_at_k
from repro.kernels.dedup import dedupdist, unique_ids_inverse
from repro.kernels.l2dist import l2dist_rowgather
from repro.kernels.platform import row_tiles

INVALID = 2**31 - 1


def random_inserts(rng, rounds, cap, idmax=1000):
    f = fq.make_frontier(cap)
    inserted = {}
    for _ in range(rounds):
        n = rng.randint(1, 6)
        ids = rng.choice(idmax, size=n)
        dists = rng.uniform(0, 10, size=n).astype(np.float32)
        for i, d in zip(ids, dists):
            if int(i) not in inserted:
                inserted[int(i)] = float(d)
        # same id must present the same distance (as in real search)
        dists = np.asarray([inserted[int(i)] for i in ids], np.float32)
        f, _, _ = fq.insert(f, jnp.asarray(ids, jnp.int32),
                            jnp.asarray(dists))
    return f, inserted


@given(seed=st.integers(0, 10_000), cap=st.sampled_from([4, 8, 16]))
@settings(max_examples=20, deadline=None)
def test_frontier_always_holds_global_topL(seed, cap):
    """After any insert sequence the frontier == top-L of everything seen."""
    rng = np.random.RandomState(seed)
    f, inserted = random_inserts(rng, rounds=6, cap=cap)
    ids = np.asarray(f.ids)
    dists = np.asarray(f.dists)
    want = sorted(inserted.items(), key=lambda kv: (kv[1], kv[0]))[:cap]
    got = [(int(i), float(d)) for i, d in zip(ids, dists) if i != INVALID]
    assert len(got) == min(len(inserted), cap)
    for (gi, gd), (wi, wd) in zip(got, want):
        assert gi == wi and abs(gd - wd) < 1e-5
    # sorted ascending (finite prefix; inf-padded tail), no duplicate ids
    finite = dists[np.isfinite(dists)]
    assert (np.diff(finite) >= -1e-6).all()
    assert np.isfinite(dists[:len(finite)]).all()
    real = ids[ids != INVALID]
    assert len(set(real.tolist())) == len(real)


@given(seed=st.integers(0, 10_000))
@settings(max_examples=20, deadline=None)
def test_select_never_returns_checked_or_invalid(seed):
    rng = np.random.RandomState(seed)
    f, _ = random_inserts(rng, rounds=4, cap=16)
    for _ in range(5):
        m = rng.randint(1, 4)
        before = ~np.asarray(f.checked)
        f, active, valid = fq.select_unchecked(f, 4, m=jnp.int32(m))
        a, v = np.asarray(active), np.asarray(valid)
        assert v.sum() <= m
        assert (a[~v] == INVALID).all()
        assert (a[v] != INVALID).all()
    # eventually everything is checked
    for _ in range(16):
        f, _, _ = fq.select_unchecked(f, 4)
    assert not bool(fq.has_unchecked(f))


@given(seed=st.integers(0, 10_000), w=st.sampled_from([2, 3, 4]))
@settings(max_examples=15, deadline=None)
def test_scatter_merge_preserves_content(seed, w):
    """scatter -> merge loses nothing and re-checks nothing."""
    rng = np.random.RandomState(seed)
    f, _ = random_inserts(rng, rounds=5, cap=16)
    f, _, _ = fq.select_unchecked(f, 4)
    before_ids = set(np.asarray(f.ids)[np.asarray(f.ids) != INVALID].tolist())
    before_checked = {int(i) for i, c in zip(np.asarray(f.ids),
                                             np.asarray(f.checked))
                      if i != INVALID and c}
    ls = fq.scatter_round_robin(f, w)
    merged, _ = fq.merge_frontiers(ls)
    after_ids = set(np.asarray(merged.ids)[
        np.asarray(merged.ids) != INVALID].tolist())
    after_checked = {int(i) for i, c in zip(np.asarray(merged.ids),
                                            np.asarray(merged.checked))
                     if i != INVALID and c}
    assert after_ids == before_ids
    assert after_checked == before_checked


@given(seed=st.integers(0, 10_000),
       mode=st.sampled_from(["bitmap", "hash"]))
@settings(max_examples=15, deadline=None)
def test_visited_never_false_positive(seed, mode):
    """A fresh=False verdict implies the id really was seen before (bitmap);
    hash mode may duplicate (benign) but must never lose recall-critical
    inserts silently: a fresh id is queryable afterwards."""
    rng = np.random.RandomState(seed)
    v = vs.make_visited(mode, 500, hash_bits=10)
    seen = set()
    for _ in range(6):
        ids = rng.choice(500, size=8).astype(np.int32)
        valid = rng.rand(8) > 0.2
        v, fresh = vs.check_and_insert(v, jnp.asarray(ids),
                                       jnp.asarray(valid))
        fresh = np.asarray(fresh)
        for i, (id_, ok, fr) in enumerate(zip(ids, valid, fresh)):
            if not ok:
                assert not fr
            elif not fr and mode == "bitmap":
                # claimed already-visited -> must actually have been seen
                assert int(id_) in seen or id_ in ids[:i][valid[:i]]
            if ok and fr:
                seen.add(int(id_))
    # everything marked fresh is now definitely visited (no forgetting)
    if mode == "bitmap":
        ids = jnp.asarray(sorted(seen), jnp.int32)
        if len(seen):
            v2, fresh2 = vs.check_and_insert(
                v, ids, jnp.ones((len(seen),), bool))
            assert not np.asarray(fresh2).any()


@pytest.mark.parametrize("n", [500, 513, 512])
@given(seed=st.integers(0, 10_000))
@settings(max_examples=10, deadline=None)
def test_bitmap_matches_set_model(n, seed):
    """The packed bitmap (one bit per vertex in uint32 words) against a
    Python set: ``fresh`` exactly, the unpacked table and ``popcount`` after
    every call, and ``merge_visited`` and the walker-sharded path's
    cross-device OR as the union of walkers' maps.  Half the ids fall in
    the last (partial, for N not a multiple of 32) word."""
    rng = np.random.RandomState(seed)
    walkers = []
    for _ in range(3):
        v = vs.make_visited("bitmap", n)
        assert v.table.dtype == jnp.uint32 and v.table.shape == (-(-n // 32),)
        seen = set()
        for _ in range(5):
            ids = np.where(rng.rand(12) < 0.5, rng.randint(0, n, 12),
                           rng.randint(max(n - 40, 0), n, 12)).astype(np.int32)
            valid = rng.rand(12) > 0.2
            v, fresh = vs.check_and_insert(v, jnp.asarray(ids),
                                           jnp.asarray(valid))
            want = []
            for id_, ok in zip(ids.tolist(), valid.tolist()):
                want.append(ok and id_ not in seen)
                if ok:
                    seen.add(id_)
            np.testing.assert_array_equal(np.asarray(fresh), want)
            mask = np.asarray(vs.unpack(v, n))
            assert set(np.flatnonzero(mask).tolist()) == seen
            # no bit past N in the last word
            assert int(vs.popcount(v)) == len(seen) == mask.sum()
        walkers.append((v, seen))
    stacked = vs.Visited(jnp.stack([v.table for v, _ in walkers]), True, 0)
    merged = vs.merge_visited(stacked)
    union = set().union(*(s for _, s in walkers))
    # the walker-sharded path ORs the walkers' maps across a mesh axis
    reduced = jax.vmap(
        lambda t: _reduce_visited(vs.Visited(t, True, 0), "w").table,
        axis_name="w")(stacked.table)
    for table in (merged.table, reduced):
        for row in np.asarray(vs.unpack(vs.Visited(table, True, 0), n)):
            assert set(np.flatnonzero(row).tolist()) == union
    assert int(vs.popcount(merged)) == len(union)


@given(seed=st.integers(0, 10_000),
       b=st.sampled_from([1, 3, 8]),
       c=st.sampled_from([4, 8, 11]),
       idmax=st.sampled_from([5, 40, 200]))
@settings(max_examples=20, deadline=None)
def test_dedup_gather_scatter_extensional(seed, b, c, idmax):
    """For random id multisets (including sentinel/padding ids) the
    dedup-gather-scatter pipeline is extensionally equal to the direct
    per-lane gather, and its unique buffer is a faithful factorization."""
    rng = np.random.RandomState(seed)
    n, d = idmax, 8
    table = jnp.asarray(rng.randn(n, d), np.float32)
    q = jnp.asarray(rng.randn(b, d), np.float32)
    # idmax+3 head-room -> some draws are padding ids (>= n)
    ids = jnp.asarray(rng.randint(0, n + 3, size=(b, c)), jnp.int32)
    got = np.asarray(dedupdist(row_tiles(table), ids, q))
    want = np.asarray(l2dist_rowgather(row_tiles(table), ids, q))
    np.testing.assert_array_equal(got, want)
    uniq, inv, n_uniq = unique_ids_inverse(ids, n)
    uniq, inv = np.asarray(uniq), np.asarray(inv)
    ids_np = np.asarray(ids)
    # the factorization folds back exactly (padding folded to the sentinel)
    np.testing.assert_array_equal(uniq[inv], np.minimum(ids_np, n))
    real = uniq[uniq < n]
    assert len(real) == len(set(real.tolist())) == int(n_uniq)
    assert set(real.tolist()) == set(ids_np[ids_np < n].ravel().tolist())
    # tile-padded tail is all sentinel
    assert uniq.shape[0] % 8 == 0 and (uniq[len(real):] >= n).all()


@given(seed=st.integers(0, 10_000),
       b=st.sampled_from([1, 4, 7]),
       c=st.sampled_from([3, 8]),
       idmax=st.sampled_from([4, 30, 500]))
@settings(max_examples=25, deadline=None)
def test_first_toucher_counts_bound_and_exact(seed, b, c, idmax):
    """uniq <= counted per lane, with equality iff the lane's counted ids
    are disjoint from every LOWER lane's; matches a pure-Python recount."""
    rng = np.random.RandomState(seed)
    ids = jnp.asarray(rng.randint(0, idmax, size=(b, c)), jnp.int32)
    counted = jnp.asarray(rng.rand(b, c) > 0.3)
    # in-lane candidates are id-distinct in real traversals (visited dedups
    # first); enforce it so the first-toucher contract's premise holds
    ids_np = np.asarray(ids)
    for lane in range(b):
        _, idx = np.unique(ids_np[lane], return_index=True)
        keep = np.zeros(c, bool)
        keep[idx] = True
        counted = counted.at[lane].set(jnp.asarray(keep)
                                       & counted[lane])
    got = np.asarray(batch_unique_counts(ids, counted))
    counted_np = np.asarray(counted)
    seen, want = set(), np.zeros(b, np.int64)
    for lane in range(b):
        for slot in range(c):
            if counted_np[lane, slot] and int(ids_np[lane, slot]) not in seen:
                seen.add(int(ids_np[lane, slot]))
                want[lane] += 1
    np.testing.assert_array_equal(got, want)
    per_lane = counted_np.sum(axis=1)
    assert (got <= per_lane).all()
    assert got.sum() == len(seen)
    # equality iff all counted ids are pairwise distinct across the batch
    all_counted = ids_np[counted_np]
    if len(set(all_counted.tolist())) == len(all_counted):
        np.testing.assert_array_equal(got, per_lane)
    else:
        assert (got < per_lane).any()


@given(seed=st.integers(0, 1000))
@settings(max_examples=10, deadline=None)
def test_recall_bounds(seed):
    rng = np.random.RandomState(seed)
    gt = rng.choice(1000, size=(4, 10), replace=False)
    assert recall_at_k(gt, gt, 10) == 1.0
    other = gt + 5000
    assert recall_at_k(other, gt, 10) == 0.0
    assert 0.0 <= recall_at_k(rng.randint(0, 50, (4, 10)), gt, 10) <= 1.0
