"""Latency-under-load sweep: client-observed percentiles vs offered QPS.

``PYTHONPATH=src python -m benchmarks.run --sweep-serve`` (full ladder) or
``PYTHONPATH=src python -m benchmarks.serve_load --qps 200 --cache
--priority-mix 0.5 --duration 2`` (one point, serving-tier knobs on —
the CI smoke invocation).

An open-loop load generator offers single-query requests at Poisson arrival
times (exponential inter-arrivals at each target QPS) to the async
coalescing front-end (``AnnIndex.serve_async``), which batches them under
the max-batch / max-wait policy and dispatches through the bucketed jit
cache.  Each request's latency is CLIENT-OBSERVED — submit to future
resolution, so queueing + coalescing wait + batch execution — which is the
number a caller of a serving system actually sees, and the one where
coalescing trades a little p50 for a lot of throughput.

The serving tier adds three sweep axes, all part of the row key:

* ``--cache`` — quantized-code result cache in front of the queue; the
  query pool is finite, so repeats hit and the row records the hit count;
* ``--priority-mix F`` + ``--admission TW,CW`` — an F fraction of requests
  in the critical class, the rest throughput-class; admission sheds
  throughput first at the watermarks, and the row carries PER-CLASS p50/p99
  (the overload claim — critical p99 lower WITH admission than without —
  is read off two rows differing only in ``admission``);
* ``--replicas N`` — a :class:`~repro.serve.ReplicaRouter` spreading
  dispatch over N data-parallel engine replicas.

``BENCH_serve.json`` is a TRAJECTORY with the same append semantics as
``BENCH_dist_backend.json``: each sweep APPENDS rows, replacing only rows
with the same (mode, backend, host, interpret, qps_offered, cache,
priority_mix, replicas, admission) key, so interpret-mode CPU numbers,
serving-tier variants, and future compiled Mosaic/TPU numbers accumulate
side by side.  Row schema is documented in docs/benchmarks.md.

On this CPU container absolute latencies measure single-core interpret-mode
execution — the shape of the latency-vs-load curve (flat until saturation,
then queueing blow-up) is the meaningful output, not the milliseconds.
"""
from __future__ import annotations

import argparse
import json
import platform
import time
from concurrent.futures import wait as futures_wait
from typing import Dict, Optional, Sequence

import jax
import numpy as np

from benchmarks.common import dataset, merge_trajectory_rows, nsg_index
from repro.ann import SearchParams
from repro.kernels.platform import interprets
from repro.serve import (AdmissionPolicy, AdmissionRejected, CachePolicy,
                         ReplicaRouter, RouterPolicy)
from repro.serve.coalescer import DeadlineExceeded

K = 10
PARAMS = SearchParams(k=K, queue_len=64, m_max=6, num_walkers=4,
                      max_steps=256, local_steps=4, sync_ratio=0.8)
BUCKETS = (1, 2, 4, 8, 16, 32)
QPS_LADDER = (25, 50, 100, 200)


def _row_key(row: Dict) -> tuple:
    """Identity of a trajectory row: same key ⇒ newer run supersedes.
    Serving-tier axes default to their pre-tier values so rows written
    before those axes existed merge as (no cache, all-critical, 1 replica,
    no admission)."""
    return (row.get("mode"), row.get("backend"),
            row.get("host", "<unknown>"), row.get("interpret"),
            row.get("qps_offered"), row.get("cache", False),
            row.get("priority_mix", 1.0), row.get("replicas", 1),
            row.get("admission", False))


def offered_load(srv, queries: np.ndarray, qps: float, duration_s: float,
                 seed: int = 0, deadline_ms: Optional[float] = None,
                 priority_mix: float = 1.0) -> Dict:
    """Open-loop Poisson arrivals at ``qps`` for ``duration_s`` seconds.

    Open loop means arrivals do NOT wait for completions — exactly the
    regime where queueing delay compounds and coalescing pays.  A
    ``priority_mix`` fraction of requests (rng-assigned, reproducible from
    ``seed``) is submitted in the critical class, the rest throughput-class.
    Returns client-observed latency percentiles — overall and per class —
    and throughput actually achieved.  Completion times come from
    ``AsyncServeResult.done_t``, stamped by the dispatcher at resolution —
    done-callbacks run AFTER waiters wake, so clocking them here would race.
    """
    rng = np.random.RandomState(seed)
    arrivals, t = [], 0.0
    while True:
        t += rng.exponential(1.0 / qps)
        if t >= duration_s:
            break
        arrivals.append(t)
    if not arrivals:
        arrivals = [0.0]
    classes = ["critical" if rng.random_sample() < priority_mix
               else "throughput" for _ in arrivals]

    futs = []
    t0 = time.perf_counter()
    for i, at in enumerate(arrivals):
        sleep = t0 + at - time.perf_counter()
        if sleep > 0:
            time.sleep(sleep)
        fut = srv.submit(queries[i % queries.shape[0]],
                         deadline_ms=deadline_ms, priority=classes[i])
        futs.append((time.perf_counter(), fut))
    futures_wait([f for _, f in futs])
    wall_s = time.perf_counter() - t0

    lats, by_class = [], {"critical": [], "throughput": []}
    rejected = shed = cache_hits = 0
    for (submit_t, fut), cls in zip(futs, classes):
        err = fut.exception()
        if err is not None:
            rejected += isinstance(err, DeadlineExceeded)
            shed += isinstance(err, AdmissionRejected)
            continue
        res = fut.result()
        cache_hits += res.batch_size == 0.0      # replayed, never queued
        # a cache hit resolves INSIDE submit(), before the client stamps
        # submit_t — clamp the ~µs negative difference to zero
        ms = max(0.0, (res.done_t - submit_t) * 1e3)
        lats.append(ms)
        by_class[cls].append(ms)
    lat = np.asarray(lats, np.float64)
    out = {
        "qps_offered": float(qps),
        "qps_achieved": float(len(lats) / wall_s),
        "requests": len(arrivals),
        "served": len(lats),
        "served_cache": int(cache_hits),
        "rejected_deadline": int(rejected),
        "rejected_admission": int(shed),
        "duration_s": float(wall_s),
    }
    if lat.size:
        out.update(
            latency_mean_ms=float(lat.mean()),
            latency_p50_ms=float(np.percentile(lat, 50)),
            latency_p95_ms=float(np.percentile(lat, 95)),
            latency_p99_ms=float(np.percentile(lat, 99)),
            latency_max_ms=float(lat.max()),
        )
    for cls, ms in by_class.items():
        if ms and 0.0 < priority_mix < 1.0:      # mixed traffic only
            arr = np.asarray(ms, np.float64)
            out[f"{cls}_served"] = len(ms)
            out[f"{cls}_p50_ms"] = float(np.percentile(arr, 50))
            out[f"{cls}_p99_ms"] = float(np.percentile(arr, 99))
    return out


def sweep(out_path: str = "BENCH_serve.json", n: int = 2000, q: int = 32,
          qps_ladder: Sequence[float] = QPS_LADDER,
          duration_s: float = 1.5, backend: str = "ref",
          max_wait_ms: float = 2.0,
          trace_out: Optional[str] = None,
          cache: Optional[CachePolicy] = None,
          admission: Optional[AdmissionPolicy] = None,
          priority_mix: float = 1.0, replicas: int = 1,
          registry_out: Optional[str] = None) -> Dict:
    """One row per offered-QPS point; appends to the JSON trajectory.

    With ``trace_out`` the HIGHEST-QPS sweep point runs with request-scoped
    tracing on and dumps its Chrome-trace/Perfetto JSON there — the point
    where coalescing actually forms multi-request batches, so the trace
    shows nested batch_formation → dispatch → device_compute spans.
    Tracing stays off for every other point (and entirely without
    ``trace_out``), so the sweep's latency numbers are untraced.

    With ``registry_out`` every point records metrics into ONE shared
    registry, dumped as JSON at the end — cache hit/miss, admission
    decisions, coalescer outcomes — the counters the CI serve-tier smoke
    gates on.
    """
    from repro.obs import MetricsRegistry, Observability

    ds = dataset(n=n, q=q)
    index = nsg_index(ds, degree=16)
    params = PARAMS.with_(backend=backend)
    host = platform.node() or platform.machine()
    queries = np.asarray(ds.queries, np.float32)
    traced_qps = max(qps_ladder) if trace_out else None
    shared_registry = MetricsRegistry() if registry_out else None

    rows = []
    for qps in qps_ladder:
        tracing = qps == traced_qps
        if tracing or shared_registry is not None:
            obs = Observability(tracing=tracing,
                                metrics=shared_registry is not None,
                                registry=shared_registry)
        else:
            obs = None
        if replicas > 1:
            engines = [index.serve(params, bucket_sizes=BUCKETS, obs=obs)
                       for _ in range(replicas)]
            for eng in engines:
                eng.warmup(queries.shape[1])
            router = ReplicaRouter(engines, policy=RouterPolicy(), obs=obs)
            srv_engine = router
        else:
            router = None
            srv_engine = index.serve(params, bucket_sizes=BUCKETS, obs=obs)
            srv_engine.warmup(queries.shape[1])  # compiles outside the clock
        from repro.serve import AsyncAnnEngine, CoalescePolicy
        srv = AsyncAnnEngine(
            srv_engine,
            CoalescePolicy(max_batch=BUCKETS[-1], max_wait_ms=max_wait_ms),
            obs=obs, cache=cache, admission=admission)
        try:
            load = offered_load(srv, queries, qps, duration_s,
                                priority_mix=priority_mix)
        finally:
            srv.close()
            if router is not None:
                router.close()
        if obs is not None and tracing:
            obs.write_trace(trace_out)
            print(f"# wrote {trace_out} "
                  f"({obs.tracer.n_events} trace events at qps={qps:g})")
        cstats = srv.stats()
        estats = srv.engine.stats()
        row = {
            "mode": "async_coalesced",
            "backend": backend,
            "quant": "none",
            "algorithm": params.algorithm,
            "host": host,
            "interpret": interprets(),
            "n": n,
            "k": K,
            "max_batch": srv.policy.max_batch,
            "max_wait_ms": max_wait_ms,
            # serving-tier axes (all in the row key)
            "cache": cache is not None,
            "priority_mix": float(priority_mix),
            "replicas": int(replicas),
            "admission": admission is not None,
            "batch_size_mean": cstats.get("batch_size_mean", 1.0),
            # the tail DECOMPOSED: time queued before dispatch vs. engine
            # wall clock per dispatched batch — the split that says whether
            # a fat p99 is a queueing problem or a compute problem
            "queue_wait_p99_ms": cstats.get("queue_wait_p99_ms", 0.0),
            "compute_p99_ms": estats.get(
                "latency_p99_ms", estats.get("replica0_p99_ms", 0.0)),
            "unix_time": time.time(),
            **load,
        }
        rows.append(row)
        print(f"bench_serve_qps{qps:g},"
              f"{row.get('latency_p50_ms', float('nan')):.1f},"
              f"p95={row.get('latency_p95_ms', float('nan')):.1f};"
              f"p99={row.get('latency_p99_ms', float('nan')):.1f};"
              f"qwait_p99={row['queue_wait_p99_ms']:.1f};"
              f"compute_p99={row['compute_p99_ms']:.1f};"
              f"achieved={row['qps_achieved']:.0f}qps;"
              f"batch_mean={row['batch_size_mean']:.1f};"
              f"cache_hits={row['served_cache']};"
              f"shed={row['rejected_admission']}")

    if registry_out and shared_registry is not None:
        with open(registry_out, "w") as f:
            f.write(shared_registry.to_json(indent=2))
        print(f"# wrote {registry_out}")

    all_rows = merge_trajectory_rows(out_path, rows, _row_key)
    payload = {
        "bench": "serve",
        "config": {"n": n, "q": q, "k": K, "buckets": list(BUCKETS),
                   "duration_s": duration_s, "max_wait_ms": max_wait_ms,
                   "queue_len": PARAMS.queue_len, "m_max": PARAMS.m_max},
        "platform": platform.machine(),
        "jax": jax.__version__,
        "unix_time": time.time(),
        "rows": all_rows,
    }
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"# wrote {out_path} ({len(rows)} new rows, "
          f"{len(all_rows)} total in trajectory)")
    return payload


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="latency-under-load sweep with serving-tier knobs")
    ap.add_argument("--qps", type=float, action="append", default=None,
                    help="offered QPS point; repeatable (default: the "
                         f"ladder {QPS_LADDER})")
    ap.add_argument("--duration", type=float, default=1.5,
                    help="seconds of offered load per point")
    ap.add_argument("--n", type=int, default=2000, help="corpus size")
    ap.add_argument("--backend", default="ref")
    ap.add_argument("--max-wait-ms", type=float, default=2.0)
    ap.add_argument("--cache", action="store_true",
                    help="enable the quantized-code result cache")
    ap.add_argument("--cache-capacity", type=int, default=4096)
    ap.add_argument("--priority-mix", type=float, default=1.0,
                    help="fraction of requests in the critical class "
                         "(rest throughput-class)")
    ap.add_argument("--admission", default=None, metavar="TW,CW",
                    help="admission watermarks: throughput,critical "
                         "queue depths (e.g. 4,16); absent = no admission "
                         "control")
    ap.add_argument("--replicas", type=int, default=1,
                    help="route over N data-parallel engine replicas")
    ap.add_argument("--out", default="BENCH_serve.json")
    ap.add_argument("--trace-out", default=None,
                    help="Chrome-trace JSON of the highest-QPS point")
    ap.add_argument("--registry-out", default=None,
                    help="dump the shared metrics registry JSON here "
                         "(cache/admission/coalescer counters)")
    args = ap.parse_args(argv)
    cache = (CachePolicy(capacity=args.cache_capacity)
             if args.cache else None)
    admission = None
    if args.admission:
        tw, cw = (int(x) for x in args.admission.split(","))
        admission = AdmissionPolicy(throughput_watermark=tw,
                                    critical_watermark=cw)
    sweep(out_path=args.out, n=args.n,
          qps_ladder=tuple(args.qps) if args.qps else QPS_LADDER,
          duration_s=args.duration, backend=args.backend,
          max_wait_ms=args.max_wait_ms, trace_out=args.trace_out,
          cache=cache, admission=admission,
          priority_mix=args.priority_mix, replicas=args.replicas,
          registry_out=args.registry_out)


if __name__ == "__main__":
    main()
