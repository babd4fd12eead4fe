"""Golden parity of the searches and the build across visited-map layouts.

``tests/data/visited_golden.npz`` was recorded while the ``bitmap`` visited
map still stored one bool per vertex.  The map now stores one bit per vertex
in uint32 words; the traversal it drives must not change by a bit: the
built graph, every search's ids, distances and ``SearchStats`` counters,
and the (B, N) visited mask the build reads as its prune pool.  ``hash`` and
``loose`` ride along to pin that they are untouched.

Regenerate (only when a change is MEANT to alter the traversal):
``PYTHONPATH=src JAX_PLATFORMS=cpu python tests/test_visited_packed.py``.
"""
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import build_nsg
from repro.core.bfis import search_topm_batch, search_topm_batch_visited
from repro.core.config import SearchConfig
from repro.core.speedann import search_speedann_batch
from repro.data import make_vector_dataset

GOLDEN = Path(__file__).parent / "data" / "visited_golden.npz"

# N = 900 leaves a partial last word (900 = 28 * 32 + 4)
N, B = 900, 8
TOPM = SearchConfig(k=10, queue_len=32, m_max=4, staged=True, max_steps=96)
SPEED = TOPM.with_(num_walkers=4, local_steps=4)
CASES = {
    "speedann_w4": (search_speedann_batch, SPEED),
    "speedann_w8": (search_speedann_batch, SPEED.with_(num_walkers=8,
                                                       m_max=8)),
    "speedann_w4_hash": (search_speedann_batch,
                         SPEED.with_(visited_mode="hash", hash_bits=10)),
    "speedann_w4_loose": (search_speedann_batch,
                          SPEED.with_(visited_mode="loose")),
    "topm": (search_topm_batch, TOPM),
    "topm_fixed_m1": (search_topm_batch, TOPM.with_(m_max=1,
                                                    staged=False)),
}


def _dataset():
    return make_vector_dataset("deep", n=N, n_queries=B, k=10, dim=16,
                               n_clusters=8, seed=11)


def _graph(ds):
    return build_nsg(ds.base, degree=12, knn_k=12, ef_construction=24,
                     passes=2)


def _run(name, graph, queries):
    fn, cfg = CASES[name]
    ids, dists, stats = fn(graph, queries, cfg)
    out = {"ids": np.asarray(ids), "dists": np.asarray(dists)}
    for field in stats._fields:
        out[f"stats.{field}"] = np.asarray(getattr(stats, field))
    return out


def _visited_mask(graph, queries):
    *_, mask = search_topm_batch_visited(graph, queries, TOPM)
    return np.asarray(mask)


def record() -> dict:
    ds = _dataset()
    graph = _graph(ds)
    q = jnp.asarray(ds.queries)
    rec = {"graph.nbrs": np.asarray(graph.nbrs)}
    for name in CASES:
        for key, val in _run(name, graph, q).items():
            rec[f"{name}.{key}"] = val
    rec["visited_mask"] = _visited_mask(graph, q)
    return rec


@pytest.fixture(scope="module")
def golden():
    with np.load(GOLDEN) as z:
        return {k: z[k] for k in z.files}


@pytest.fixture(scope="module")
def built():
    ds = _dataset()
    return _graph(ds), jnp.asarray(ds.queries)


def test_built_graph_unchanged(golden, built):
    graph, _ = built
    np.testing.assert_array_equal(np.asarray(graph.nbrs),
                                  golden["graph.nbrs"])


@pytest.mark.parametrize("name", sorted(CASES))
def test_search_matches_golden(golden, built, name):
    graph, q = built
    got = _run(name, graph, q)
    assert sorted(got) == sorted(k.split(".", 1)[1] for k in golden
                                 if k.startswith(name + "."))
    for key, val in got.items():
        want = golden[f"{name}.{key}"]
        assert val.dtype == want.dtype and val.shape == want.shape, key
        if key == "dists":
            np.testing.assert_allclose(val, want, rtol=0, atol=0)
        else:
            np.testing.assert_array_equal(val, want, err_msg=key)


def test_visited_mask_matches_golden(golden, built):
    graph, q = built
    mask = _visited_mask(graph, q)
    assert mask.dtype == np.bool_ and mask.shape == (B, N)
    np.testing.assert_array_equal(mask, golden["visited_mask"])


if __name__ == "__main__":
    GOLDEN.parent.mkdir(exist_ok=True)
    np.savez_compressed(GOLDEN, **record())
    print(f"wrote {GOLDEN}")
