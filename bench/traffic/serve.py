"""Traffic kind ``serve``: single queries through the program's async
front end, ``AnnIndex.serve_async(...).submit``, from the one generator
``bench.loadgen``.

A mix of this kind (``bench/traffic/<mix>.json``) gives:

* ``"loop": "closed"`` and ``"in_flight": C``, or ``"loop": "open"`` and
  ``"rate_per_s"`` (with an optional ``"burst"``): see ``bench.loadgen``;
* ``"queries"`` (optional): which point each request carries
  (``bench.corpus.QueryPlan``); every request a fresh query by default;
* ``"serve"`` (optional): entries laid over the configuration's ``serve``
  and passed to ``serve_async``; ``"cache"`` is a ``CachePolicy``'s fields.

Set-up warms every batch size the traffic forms.  The window's answers are
returned for the check; its end-to-end metrics are ``qps`` (answered by the
window's end / window seconds), ``latency_p99_ms`` (over every answered
request, from when it was due) and ``setup_s``.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from bench import corpus, harness, loadgen, rates


def serve_kwargs(cfg: dict, mix: dict) -> dict:
    from repro.serve.cache import CachePolicy
    kw = dict(cfg.get("serve", {}), **mix.get("serve", {}))
    if "cache" in kw:
        kw["cache"] = CachePolicy(**kw["cache"])
    return kw


def window(run: harness.Run) -> dict:
    from repro.obs import Observability
    cfg, mix = run.cell.config, run.cell.traffic
    # device annotations name the dispatches in a trace; with no profiler
    # running they cost nothing, so traced and untraced runs take one path
    obs = Observability(tracing=False, metrics=False, profile=True)
    srv = run.index.serve_async(harness.search_params(cfg), obs=obs,
                                **serve_kwargs(cfg, mix))
    engine = srv.engine
    batches = harness.BatchLog(engine)
    if run.plant is not None:
        run.plant(engine)
    top = engine.bucket_sizes[-1]
    in_flight = mix.get("in_flight", top)
    largest = min(in_flight, top) if mix["loop"] == "closed" else top
    warm = corpus.Stream(run.data, run.seed, corpus.WARMUP)
    t0 = time.perf_counter()
    for b in range(1, largest + 1):       # every batch size the traffic forms
        engine.search(warm.take(b))
    srv.submit(warm.take(1)[0]).result()
    harness.log("warmup", batch_sizes=f"1..{largest}",
                seconds=time.perf_counter() - t0)

    plan = corpus.QueryPlan(mix.get("queries"), run.seed)
    stream = corpus.Stream(run.data, run.seed, corpus.QUERIES)
    plan.prefetch(8 * corpus.BLOCK)
    stream.prefetch(plan.points_needed(8 * corpus.BLOCK))
    before = srv.stats()
    first = len(batches.rows)
    gc.collect()        # every run starts the window with the same heap
    compiles0 = run.counter.n
    setup_s = time.perf_counter() - run.t_setup

    def send(i):
        return srv.submit(stream.point(plan.index(i)))

    hooks = run.tracer.hooks(run.seconds)
    if mix["loop"] == "closed":
        wl = loadgen.closed_loop(send, in_flight, run.seconds, at=hooks)
    else:
        offsets = loadgen.arrivals(mix["rate_per_s"], run.seconds, run.seed,
                                   mix.get("burst"))
        wl = loadgen.open_loop(send, offsets, run.seconds, at=hooks)
    compiles = run.counter.n - compiles0
    after = srv.stats()
    rows = batches.rows[first:]
    peak = (run.device.memory_stats() or {}).get("peak_bytes_in_use")
    srv.close()
    harness.log("window", requests=len(wl.due), compiles_in_window=compiles,
                generator_late_ms_max=wl.late_ms_max)

    served = after["served"] - before["served"]
    batches_n = after["batches_dispatched"] - before["batches_dispatched"]
    qw = (after.get("queue_wait_mean_ms", 0.0) * after["served"]
          - before.get("queue_wait_mean_ms", 0.0) * before["served"])
    lanes = sum(r[2] for r in rows)
    layer = {
        "queue_wait_ms": qw / served if served else None,
        "batch_size_mean": served / batches_n if batches_n else None,
        "steps_per_query": (sum(r[4] for r in rows) / lanes
                            if lanes else None),
        "compiles_in_window": compiles,
    }
    answered = np.flatnonzero(wl.ok)
    k = cfg["k"]
    ids = np.stack([wl.results[i].ids for i in answered]) if answered.size \
        else np.zeros((0, k), np.int32)
    dists = np.stack([wl.results[i].dists for i in answered]) \
        if answered.size else np.zeros((0, k), np.float32)
    lat_ms = wl.latency_ms()
    e2e = {
        "qps": rates.rate(wl.completed_in_window(), run.seconds),
        "latency_p99_ms": (rates.percentile(lat_ms, 99) if lat_ms.size
                           else None),
        "setup_s": setup_s,
    }
    harness.log("latency", requests=len(wl.due), answered=int(answered.size),
                p50_ms=rates.percentile(lat_ms, 50) if lat_ms.size else None,
                p99_ms=e2e["latency_p99_ms"])
    del srv, engine
    return {
        "attempted": int(len(wl.due)),
        "failed": int(len(wl.due) - answered.size),
        "e2e": e2e, "layer": layer, "peak": peak, "batches": rows,
        "answers": [{"corpus": run.data.base,
                     "queries": stream.rows(plan.indices(answered)),
                     "ids": ids, "dists": dists}],
    }
