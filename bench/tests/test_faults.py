"""A run with the timed path broken underneath must come out not correct,
for each fault the cell can have; the same run unbroken comes out correct.
One chip, so there is no exchange between chips to leave out."""
import numpy as np
import pytest

from bench.tests.conftest import run_tiny, tiny_cell

QUERY = dict(in_flight=8)      # batches of up to 8: room to drop half


def no_steps(engine):
    """The traversal returns its state unchanged: no step is taken."""
    engine.params = engine.params.with_(max_steps=0)


def half_batch(engine):
    """Only the first half of each batch is searched; the rest get the
    answers of the first half."""
    inner = engine.search

    def search(q, *a, **kw):
        h = max(1, len(q) // 2)
        res = inner(q[:h], *a, **kw)
        pick = np.arange(len(q)) % h
        return res._replace(ids=res.ids[pick], dists=res.dists[pick])
    engine.search = search


def altered_answer(engine):
    """The nearest id of every answer is changed where it is produced."""
    inner = engine.search

    def search(q, *a, **kw):
        res = inner(q, *a, **kw)
        ids = res.ids.copy()
        ids[:, 0] = (ids[:, 0] + 1) % engine.index.n_nodes
        return res._replace(ids=ids)
    engine.search = search


def test_sound_query_run_is_correct():
    out = run_tiny(tiny_cell("sift128", "saturate", **QUERY))
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0


@pytest.mark.parametrize("plant", [no_steps, half_batch, altered_answer])
def test_broken_query_path_is_not_correct(plant):
    out = run_tiny(tiny_cell("sift128", "saturate", **QUERY), plant=plant)
    assert not out["correct"], out["checks"]


def test_sound_run_with_repeated_queries_is_correct():
    cell = tiny_cell("sift128", "saturate",
                     queries={"repeat": "zipf", "distinct": 300, "s": 1.1},
                     serve={"cache": {"capacity": 256}}, **QUERY)
    out = run_tiny(cell)
    assert out["correct"], out["checks"]


@pytest.mark.parametrize("metric", ["ip", "cosine"])
def test_sound_run_under_each_metric_is_correct(metric):
    """The reference agrees with the program under every metric it
    implements; a wrong answer is still caught."""
    cell = tiny_cell("sift128", "saturate", **QUERY)
    cfg = dict(cell.config, metric=metric, name=f"tiny-{metric}",
               index=dict(cell.config["index"], metric=metric))
    out = run_tiny(cell._replace(config=cfg))
    assert out["correct"], out["checks"]
    out = run_tiny(cell._replace(config=cfg), plant=altered_answer)
    assert not out["correct"], out["checks"]

