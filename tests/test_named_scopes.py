"""Named scopes in the compiled search: every phase of a step carries its
``ann.*`` scope in the HLO's ``op_name`` metadata, and the scopes change
nothing else — not an op, a shape or a fusion of the compiled program, nor
a bit of its ids, distances and ``SearchStats``."""
import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ann import AnnIndex, IndexSpec, SearchParams
from repro.core.bfis import search_topm_batch
from repro.core.speedann import search_speedann_batch
from repro.data import make_vector_dataset

STEP_SCOPES = ("ann.select", "ann.neighbors", "ann.visited", "ann.distance",
               "ann.queue", "ann.counters", "ann.loop")
PARAMS = SearchParams(k=10, queue_len=32, m_max=4, num_walkers=4,
                      max_steps=64, local_steps=4)


@pytest.fixture(scope="module")
def ds():
    return make_vector_dataset("deep", n=600, n_queries=8, k=10, dim=16,
                               n_clusters=6, seed=0)


@pytest.fixture(scope="module")
def index(ds):
    return AnnIndex.build(ds, IndexSpec(degree=8, passes=1))


def _scopes(text: str) -> set:
    return set(re.findall(r"ann\.[a-z]+(?=/)", text))


def _without_metadata(hlo: str) -> str:
    """Compiled HLO text less what the scopes may change: each
    instruction's metadata and the stack-frame tables after the module."""
    return re.sub(r", metadata=\{[^}]*\}", "", hlo).split("\nFileNames")[0]


def _compile(search, graph, cfg, queries, scoped: bool):
    """(lowered, compiled) search over ``queries``; unscoped, every
    ``jax.named_scope`` the search opens is a no-op."""
    real = jax.named_scope
    if not scoped:
        jax.named_scope = lambda name: contextlib.nullcontext()
    try:
        lowered = jax.jit(lambda q: search(graph, q, cfg)).lower(queries)
        return lowered, lowered.compile()
    finally:
        jax.named_scope = real


@pytest.mark.parametrize("search", [search_speedann_batch, search_topm_batch],
                         ids=["speedann", "topm"])
def test_step_scopes_in_lowered_hlo(ds, index, search):
    q = jnp.asarray(ds.queries)
    lowered, compiled = _compile(search, index.graph,
                                 PARAMS.to_search_config("l2"), q, True)
    assert _scopes(lowered.as_text(debug_info=True)) >= set(STEP_SCOPES)
    # the compiled program keeps them in its op_name metadata
    assert _scopes(compiled.as_text()) >= set(STEP_SCOPES)


@pytest.mark.parametrize("search", [search_speedann_batch, search_topm_batch],
                         ids=["speedann", "topm"])
def test_scopes_change_no_op_and_no_bit(ds, index, search):
    q = jnp.asarray(ds.queries)
    cfg = PARAMS.to_search_config("l2")
    low1, with_scopes = _compile(search, index.graph, cfg, q, True)
    low0, without = _compile(search, index.graph, cfg, q, False)
    assert not _scopes(low0.as_text(debug_info=True))
    assert _without_metadata(with_scopes.as_text()) \
        == _without_metadata(without.as_text())
    a, b = with_scopes(q), without(q)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b), strict=True):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_facade_rerank_scope(ds, index):
    search = index.searcher(PARAMS.with_(rerank_k=20))
    text = jax.jit(lambda q: search(q).ids).lower(
        jnp.asarray(ds.queries)).as_text(debug_info=True)
    assert _scopes(text) >= set(STEP_SCOPES) | {"ann.rerank"}
