from concurrent.futures import Future

import numpy as np
import pytest

from bench import corpus, loadgen


def done_future(value=None):
    f = Future()
    f.set_result(value)
    return f


def test_closed_loop_sends_each_request_once_in_order():
    sent = []

    def send(i):
        sent.append(i)
        return done_future(i)

    log = loadgen.closed_loop(send, 4, 0.05)
    assert sent == list(range(len(sent)))
    assert len(log.due) == len(sent)
    assert log.ok.all()
    assert [r for r in log.results] == sent


def tiny_corpus(seed=0):
    cfg = {"n": 100, "dim": 16,
           "data": {"generator": "clustered_gaussian", "n_clusters": 4,
                    "center_scale": 4.0, "seed": seed}}
    return corpus.config_corpus(cfg)


def test_streams_never_replay_a_point():
    data = tiny_corpus()
    s = corpus.Stream(data, 12345678901, corpus.QUERIES)
    pts = s.take(2 * corpus.BLOCK + 5)
    assert len(np.unique(pts, axis=0)) == len(pts)
    # point j depends on (seed, tag, j) only, not on how it is taken
    s2 = corpus.Stream(data, 12345678901, corpus.QUERIES)
    assert np.array_equal(s2.rows(np.array([0, corpus.BLOCK + 3])),
                          pts[[0, corpus.BLOCK + 3]])
    other = corpus.Stream(data, 12345678901, corpus.WARMUP).take(10)
    assert not (other[:, None, :] == pts[None, :10, :]).all(-1).any()


def test_default_plan_sends_each_point_once():
    plan = corpus.QueryPlan(None, 2**31 + 7)
    idx = plan.indices(range(3 * corpus.BLOCK))
    assert np.array_equal(idx, np.arange(3 * corpus.BLOCK))
    assert plan.points_needed(100) == 100


def test_zipf_plan_repeats_from_a_pool_drawn_from_the_seed():
    spec = {"repeat": "zipf", "distinct": 500, "s": 1.1}
    plan = corpus.QueryPlan(spec, 2**31 + 7)
    idx = plan.indices(range(2 * corpus.BLOCK))
    assert idx.min() >= 0 and idx.max() < 500
    counts = np.bincount(idx, minlength=500)
    assert counts.max() > 20 * np.median(counts[counts > 0])  # a hot head
    assert plan.points_needed(10) == 500
    again = corpus.QueryPlan(spec, 2**31 + 7)
    assert np.array_equal(again.indices(range(2 * corpus.BLOCK)[::-1]),
                          idx[::-1])
    other = corpus.QueryPlan(spec, 2**31 + 8).indices(range(1000))
    assert not np.array_equal(other, idx[:1000])


def test_plan_refuses_what_it_does_not_know():
    with pytest.raises(ValueError):
        corpus.QueryPlan({"repeat": "uniform"}, 1)
    with pytest.raises(ValueError):
        corpus.QueryPlan({"repeat": "zipf", "distinct": 5, "s": 1.0,
                          "hot": 2}, 1)


def test_open_loop_times_from_when_due():
    """A generator that falls behind (here: one send that blocks 0.5 s)
    charges the wait to every request due meanwhile."""
    t = [0.0]
    clock = lambda: t[0]

    def sleep(s):
        t[0] += s

    def send(i):
        if i == 1:
            t[0] += 0.5          # the generator is stuck in this send
        return done_future(i)

    offsets = np.array([0.0, 0.1, 0.2, 0.3])
    log = loadgen.open_loop(send, offsets, 1.0, clock=clock, sleep=sleep)
    lat = log.latency_ms()
    assert np.allclose(log.due - log.t0, offsets)
    assert lat[0] == 0.0
    # due at 0.2 and 0.3, sent at 0.6: 400 and 300 ms from when due
    assert np.allclose(lat[2:], [400.0, 300.0])
    assert np.isclose(log.late_ms_max, 400.0)


def test_arrivals_poisson_and_bursts():
    a = loadgen.arrivals(1000.0, 10.0, seed=3)
    assert abs(len(a) - 10_000) < 400
    assert np.all(np.diff(a) >= 0) and a.max() < 10.0
    b = loadgen.arrivals(1000.0, 10.0, seed=3,
                         burst={"on_s": 1.0, "off_s": 1.0})
    assert np.all(np.mod(b, 2.0) < 1.0)
    assert abs(len(b) - 5_000) < 300
    assert np.array_equal(a, loadgen.arrivals(1000.0, 10.0, seed=3))
