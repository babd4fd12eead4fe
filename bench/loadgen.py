"""The one request generator, for traffic kinds that send requests.  A
traffic mix is a data file under ``bench/traffic/`` whose parameters its
kind passes here:

* ``"loop": "closed"`` with ``"in_flight": C`` — C clients, each sending its
  next request the moment its previous one completes.  A request is due when
  it is sent.
* ``"loop": "open"`` with ``"rate_per_s": r`` — requests due on a Poisson
  schedule drawn from the seed, sent when due whether or not earlier ones have
  finished; ``"burst": {"on_s": a, "off_s": b}`` sends only during the first
  ``a`` seconds of every ``a + b``.  How late the generator ran is reported.

Every request is timed from when it was due to when the client saw its
answer.  A request that raises, or that has not come back ``GRACE_S`` after the
window closed, is failed.  Requests are numbered in the order they are due;
what request ``i`` carries is the caller's (``bench.corpus.QueryPlan``).
"""
from __future__ import annotations

import time
from concurrent.futures import FIRST_COMPLETED, Future, wait
from typing import Callable, NamedTuple, Optional

import numpy as np

GRACE_S = 60.0


class Log(NamedTuple):
    """Per-request record of a window, indexed by request number."""
    due: np.ndarray        # host seconds (perf_counter) when due
    done: np.ndarray       # when the client saw the answer; NaN if none
    ok: np.ndarray         # bool: answered without an exception
    results: list          # the answer, or the exception, per request
    t0: float              # window start
    t_end: float           # window end (t0 + seconds)
    late_ms_max: float     # open loop: the most the generator was late

    def completed_in_window(self) -> int:
        """Requests answered by the window's end."""
        return int((self.ok & (self.done <= self.t_end)).sum())

    def latency_ms(self) -> np.ndarray:
        """Due-to-answer time of every answered request, in request order."""
        return (self.done[self.ok] - self.due[self.ok]) * 1e3


def closed_loop(send: Callable[[int], Future], in_flight: int,
                seconds: float, clock=time.perf_counter,
                at: Optional[dict] = None) -> Log:
    """``send(i)`` submits request i and returns its future.  ``at`` maps a
    window offset in seconds to a callable run once the client passes it
    (the traced window's start and stop hooks)."""
    hooks = sorted((at or {}).items())
    due, done, ok, results = [], [], [], []
    pending: dict[Future, int] = {}

    def launch(t):
        i = len(due)
        due.append(t)
        done.append(np.nan)
        ok.append(False)
        results.append(None)
        pending[send(i)] = i

    t0 = clock()
    t_end = t0 + seconds
    for _ in range(in_flight):
        launch(t0)
    while pending:
        now = clock()
        while hooks and now >= t0 + hooks[0][0]:
            hooks.pop(0)[1]()
            now = clock()
        timeout = (t_end if now < t_end else t_end + GRACE_S) - now
        if hooks:
            timeout = min(timeout, t0 + hooks[0][0] - now)
        finished, _ = wait(list(pending), timeout=max(timeout, 0.0),
                           return_when=FIRST_COMPLETED)
        now = clock()
        if not finished and now >= t_end + GRACE_S:
            break
        for f in finished:
            i = pending.pop(f)
            done[i] = now
            exc = f.exception()
            ok[i] = exc is None
            results[i] = exc if exc is not None else f.result()
            if now < t_end:
                launch(now)
    for f in list(pending):         # never came back: failed, not waited on
        f.cancel()
    for hook in hooks:
        hook[1]()
    return Log(np.asarray(due), np.asarray(done), np.asarray(ok, bool),
               results, t0, t_end, 0.0)


def arrivals(rate_per_s: float, seconds: float, seed: int,
             burst: Optional[dict] = None) -> np.ndarray:
    """Due offsets (seconds from the window start) of an open loop: Poisson
    at ``rate_per_s``, only inside the on-phases of ``burst``."""
    rng = np.random.default_rng([5, seed])
    on = burst["on_s"] if burst else seconds
    period = on + (burst["off_s"] if burst else 0.0)
    out, t_on = [], 0.0          # t_on: seconds of on-time elapsed
    while True:
        t_on += rng.exponential(1.0 / rate_per_s)
        cycles, into = divmod(t_on, on)
        t = cycles * period + into
        if t >= seconds:
            return np.asarray(out)
        out.append(t)


def open_loop(send: Callable[[int], Future], offsets: np.ndarray,
              seconds: float, clock=time.perf_counter,
              sleep=time.sleep, at: Optional[dict] = None) -> Log:
    """Send request i at ``t0 + offsets[i]``; time it from then."""
    hooks = sorted((at or {}).items())
    n = len(offsets)
    done = np.full(n, np.nan)
    ok = np.zeros(n, bool)
    results: list = [None] * n
    futures = []
    t0 = clock()
    due = t0 + np.asarray(offsets, np.float64)
    late = 0.0

    def on_done(i):
        def cb(f):
            if f.cancelled():
                return
            done[i] = clock()
            exc = f.exception()
            ok[i] = exc is None
            results[i] = exc if exc is not None else f.result()
        return cb

    for i in range(n):
        while hooks and due[i] >= t0 + hooks[0][0]:
            hooks.pop(0)[1]()
        wait_s = due[i] - clock()
        if wait_s > 0:
            sleep(wait_s)
        late = max(late, clock() - due[i])
        f = send(i)
        f.add_done_callback(on_done(i))
        futures.append(f)
    t_end = t0 + seconds
    wait(futures, timeout=max(t_end + GRACE_S - clock(), 0.0))
    for f in futures:
        f.cancel()
    for hook in hooks:
        hook[1]()
    return Log(due, done, ok, results, t0, t_end, late * 1e3)
