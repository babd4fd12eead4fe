import threading
import time
from concurrent.futures import Future

import numpy as np
import pytest

from bench import loadgen, rates


def test_percentile_nearest_rank():
    v = np.arange(1, 101)
    assert rates.percentile(v, 99) == 99
    assert rates.percentile(v, 100) == 100
    assert rates.percentile(v, 50) == 50
    assert rates.percentile([7.0], 99) == 7.0
    with pytest.raises(ValueError):
        rates.percentile([], 99)


def test_rate():
    assert rates.rate(300, 20.0) == 15.0
    with pytest.raises(ValueError):
        rates.rate(1, 0.0)


class FakeServer:
    """Answers each request after ``service_s`` on one worker thread; the
    worker pauses ``stall_s`` once, ``stall_at`` seconds after the first
    request."""

    def __init__(self, service_s, stall_at=None, stall_s=0.0):
        self.service_s, self.stall_at, self.stall_s = \
            service_s, stall_at, stall_s
        self.q, self.cv, self.stop = [], threading.Condition(), False
        self.t0 = None
        self.worker = threading.Thread(target=self._run, daemon=True)
        self.worker.start()

    def send(self, i):
        f = Future()
        with self.cv:
            if self.t0 is None:
                self.t0 = time.perf_counter()
            self.q.append(f)
            self.cv.notify()
        return f

    def _run(self):
        while True:
            with self.cv:
                while not self.q and not self.stop:
                    self.cv.wait()
                if self.stop:
                    return
                f = self.q.pop(0)
            if (self.stall_at is not None
                    and time.perf_counter() - self.t0 >= self.stall_at):
                time.sleep(self.stall_s)
                self.stall_at = None
            time.sleep(self.service_s)
            f.set_running_or_notify_cancel()
            f.set_result(None)

    def close(self):
        with self.cv:
            self.stop = True
            self.cv.notify()
        self.worker.join(timeout=5)
        assert not self.worker.is_alive()


def run(stall_s):
    srv = FakeServer(0.002, stall_at=0.3, stall_s=stall_s)
    try:
        log = loadgen.closed_loop(srv.send, 1, 1.0)
    finally:
        srv.close()
    return (rates.rate(log.completed_in_window(), 1.0),
            rates.percentile(log.latency_ms(), 99), log)


def test_a_stall_in_the_window_moves_rate_and_tail():
    qps0, p99_0, log0 = run(0.0)
    qps1, p99_1, log1 = run(0.3)
    # the rate is over the whole window: 0.3 s of 1 s lost
    assert qps1 < 0.85 * qps0
    # the stalled request is one of all requests, and sets the tail's top
    assert log1.latency_ms().max() >= 300
    assert rates.percentile(log1.latency_ms(), 100) >= 300
    assert p99_0 < 50
    # every request of the window is counted, none dropped
    assert log1.ok.all() and len(log1.due) == log1.ok.sum()
