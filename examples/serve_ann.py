"""END-TO-END DRIVER: batched ANN serving (the paper's kind is search
serving, so this is the production-shaped example).

Builds an index, then serves query traffic through the serving stack
(docs/serving.md).  Two client models:

* default — *variable-size batched* traffic through ``repro.serve.
  AnnEngine``: batches are quantized to a fixed bucket ladder so the jit
  cache stays bounded and warm while traffic sizes fluctuate;
* ``--async-client`` — *single-query* traffic with per-request deadlines at
  a Poisson arrival rate (``--qps``), coalesced into batches by
  ``AsyncAnnEngine`` under the max-batch / max-wait policy and dispatched
  through the same bucketed jit cache.

Underneath, the full Speed-ANN stack (staged parallel expansion, adaptive
synchronization, bounded budgets) runs with the distance backend picked by
``--dist-backend``; ``--sharded`` dispatches every bucket through the
``shard_map`` walker path (one walker per device on this host's mesh).

    PYTHONPATH=src python examples/serve_ann.py [--batches 20] \
        [--max-batch 32] [--dist-backend ref|rowgather|dma|ref_int8|...] \
        [--metric l2|ip|cosine] [--quant none|int8|bf16] [--rerank-k 30] \
        [--async-client --qps 50 --deadline-ms 200] [--sharded] \
        [--cache] [--priority-mix 0.5 --admission 4,16] [--replicas 2] \
        [--trace-out trace.json]

The serving-tier flags (all ``--async-client``): ``--cache`` puts the
quantized-code result cache in front of the queue (clients draw from a
finite query pool, so repeats replay for free); ``--priority-mix F`` sends
an F fraction of requests latency-critical and the rest throughput-class,
with ``--admission TW,CW`` shedding throughput-class first at those queue
depths; ``--replicas N`` routes dispatch over N data-parallel engine
replicas with latency-aware replica selection.

``--quant int8 --dist-backend ref_int8 --rerank-k 30`` serves the two-stage
quantized configuration: int8 traversal, exact f32 re-ranking — the engine
inherits it all from the facade, and ``engine.stats()`` shows where the
tail latency lands.
"""
import argparse
import time

import numpy as np

from repro.ann import AnnIndex, IndexSpec, SearchParams
from repro.data import make_vector_dataset
from repro.kernels.registry import available_backends
from repro.launch.compile_cache import use_compile_cache


def main():
    ap = argparse.ArgumentParser()
    def positive_int(v):
        n = int(v)
        if n < 1:
            raise argparse.ArgumentTypeError("must be >= 1")
        return n

    ap.add_argument("--batches", type=positive_int, default=10)
    ap.add_argument("--max-batch", type=int, default=32)
    ap.add_argument("--n", type=int, default=8000)
    ap.add_argument("--recall-target", type=float, default=0.9)
    ap.add_argument("--dist-backend", default="ref",
                    choices=tuple(available_backends()))
    ap.add_argument("--metric", default="l2",
                    choices=("l2", "ip", "cosine"))
    ap.add_argument("--quant", default="none",
                    choices=("none", "int8", "bf16"))
    ap.add_argument("--rerank-k", type=int, default=0,
                    help="two-stage search: exact f32 re-rank of this many "
                         "stage-1 candidates (0 disables)")
    ap.add_argument("--sharded", action="store_true",
                    help="dispatch every bucket through the shard_map "
                         "walker path (one walker per device)")
    ap.add_argument("--async-client", action="store_true",
                    help="simulate single-query clients: Poisson arrivals "
                         "with deadlines through the coalescing queue")
    ap.add_argument("--qps", type=float, default=50.0,
                    help="offered arrival rate for --async-client")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request deadline for --async-client "
                         "(default: none)")
    ap.add_argument("--max-wait-ms", type=float, default=2.0,
                    help="coalescer max-wait flush for --async-client")
    ap.add_argument("--cache", action="store_true",
                    help="with --async-client: quantized-code result cache "
                         "in front of the coalescing queue")
    ap.add_argument("--priority-mix", type=float, default=1.0,
                    help="with --async-client: fraction of requests in the "
                         "critical class (rest throughput-class)")
    ap.add_argument("--admission", default=None, metavar="TW,CW",
                    help="with --async-client: admission watermarks "
                         "(throughput,critical queue depths, e.g. 4,16)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="with --async-client: route over N data-parallel "
                         "engine replicas")
    ap.add_argument("--trace-out", default=None,
                    help="record request-scoped spans and write "
                         "Chrome-trace/Perfetto JSON here (open in "
                         "ui.perfetto.dev); also prints the metrics "
                         "registry (docs/observability.md)")
    args = ap.parse_args()
    use_compile_cache()

    print("== Speed-ANN serving driver ==")
    ds = make_vector_dataset("deep", n=args.n, n_queries=args.max_batch,
                             k=10, dim=48)
    index = AnnIndex.build(ds, IndexSpec(
        builder="nsg", metric=args.metric, degree=32, ef_construction=96,
        quant=args.quant))
    params = SearchParams(k=10, queue_len=128, m_max=8, num_walkers=8,
                          max_steps=512, local_steps=8, sync_ratio=0.8,
                          backend=args.dist_backend,
                          rerank_k=args.rerank_k)
    if args.sharded:
        params = params.with_(algorithm="sharded", global_rounds=16)

    buckets = tuple(b for b in (1, 2, 4, 8, 16, 32, 64, 128)
                    if b <= args.max_batch)
    obs = None
    if args.trace_out:
        from repro.obs import Observability
        obs = Observability(tracing=True, metrics=True)
    if args.async_client:
        return serve_async_clients(index, params, buckets, args, obs)
    engine = index.serve(params, bucket_sizes=buckets, obs=obs)
    compile_s = engine.warmup(ds.base.shape[1])
    print(f"warmed {len(compile_s)} buckets "
          f"({', '.join(f'{b}:{s:.1f}s' for b, s in compile_s.items())})")

    rng = np.random.RandomState(0)
    for i in range(args.batches):
        # fresh query traffic each batch, drawn from the corpus's own
        # generative process (cluster center + unit noise) — with the
        # batch size itself fluctuating like online traffic
        bsz = int(rng.randint(1, args.max_batch + 1))
        c_ids = rng.randint(0, ds.centers.shape[0], size=bsz)
        queries = (ds.centers[c_ids]
                   + rng.normal(size=(bsz, ds.base.shape[1]))
                   .astype(np.float32))
        gt_ids, _ = index.exact(queries, 10)   # metric-aware ground truth
        res = engine.search(queries, gt_ids=gt_ids)
        print(f"batch {i:02d}: B={bsz:3d} -> bucket {res.buckets} "
              f"{res.latency_ms:7.1f} ms ({res.latency_ms / bsz:6.2f} "
              f"ms/query)")

    m = engine.stats()
    print(f"\nserved {m['queries_served']:.0f} queries in "
          f"{m['requests_served']:.0f} requests | "
          f"recall@10={m['recall_at_k']:.3f} | "
          f"mean={m['latency_mean_ms']:.1f}ms "
          f"p50={m['latency_p50_ms']:.1f}ms p95={m['latency_p95_ms']:.1f}ms "
          f"p99={m['latency_p99_ms']:.1f}ms"
          f" | jit entries={m['jit_cache_size']:.0f} "
          f"(hits={m['cache_hits']:.0f} misses={m['cache_misses']:.0f}) "
          f"padded={m['padded_queries']:.0f}")
    assert m["recall_at_k"] >= args.recall_target, "recall target missed"
    if obs is not None:
        _dump_obs(obs, args.trace_out)
    print("OK")


def _dump_obs(obs, trace_out):
    obs.write_trace(trace_out)
    print(f"wrote {trace_out} ({obs.tracer.n_events} trace events) — "
          f"open in ui.perfetto.dev")
    prom = obs.registry.to_prometheus()
    if prom.strip():
        print("-- metrics registry (Prometheus text format) --")
        print(prom, end="")


def serve_async_clients(index, params, buckets, args, obs=None):
    """Single-query clients at Poisson arrivals through the coalescer,
    optionally behind the serving tier (cache / admission / replicas)."""
    from repro.serve import (AdmissionPolicy, AsyncAnnEngine, CachePolicy,
                             CoalescePolicy, ReplicaRouter, RouterPolicy)
    cache = CachePolicy(capacity=4096) if args.cache else None
    admission = None
    if args.admission:
        tw, cw = (int(x) for x in args.admission.split(","))
        admission = AdmissionPolicy(throughput_watermark=tw,
                                    critical_watermark=cw)
    router = None
    if args.replicas > 1:
        engines = [index.serve(params, bucket_sizes=buckets, obs=obs)
                   for _ in range(args.replicas)]
        router = ReplicaRouter(engines, policy=RouterPolicy(), obs=obs)
        srv = AsyncAnnEngine(
            router,
            CoalescePolicy(max_batch=max(buckets),
                           max_wait_ms=args.max_wait_ms,
                           default_deadline_ms=args.deadline_ms),
            obs=obs, cache=cache, admission=admission)
    else:
        engines = None
        srv = index.serve_async(params, max_wait_ms=args.max_wait_ms,
                                default_deadline_ms=args.deadline_ms,
                                bucket_sizes=buckets, obs=obs,
                                cache=cache, admission=admission)
    for eng in (engines if engines is not None else [srv.engine]):
        eng.warmup()
    print(f"offering ~{args.qps:g} qps "
          f"(deadline={args.deadline_ms} ms, "
          f"max_wait={args.max_wait_ms:g} ms, cache={bool(cache)}, "
          f"admission={args.admission or 'off'}, "
          f"replicas={args.replicas}, "
          f"priority_mix={args.priority_mix:g})")

    rng = np.random.RandomState(0)
    ds_dim = index.dim
    n_requests = args.batches * args.max_batch
    # a finite query pool, so --cache has repeats to replay
    pool = rng.normal(size=(32, ds_dim)).astype(np.float32)
    futs = []
    t_next = time.perf_counter()
    for i in range(n_requests):
        t_next += rng.exponential(1.0 / args.qps)
        dt = t_next - time.perf_counter()
        if dt > 0:
            time.sleep(dt)
        prio = ("critical" if rng.random_sample() < args.priority_mix
                else "throughput")
        q = pool[i % pool.shape[0]]
        futs.append((time.perf_counter(), srv.submit(q, priority=prio)))
    lats, rejected = [], 0
    for submit_t, fut in futs:
        try:
            # done_t is stamped by the dispatcher at resolution (clocking
            # here would measure this loop, not the request)
            res = fut.result(timeout=120)
            lats.append((res.done_t - submit_t) * 1e3)
        except Exception:                # noqa: BLE001 - deadline/admission
            rejected += 1
    srv.close()
    if router is not None:
        router.close()

    st = srv.stats()
    est = (engines[0] if engines is not None else srv.engine).stats()
    if lats:
        lat = np.asarray(lats)
        print(f"client-observed: p50={np.percentile(lat, 50):.1f}ms "
              f"p95={np.percentile(lat, 95):.1f}ms "
              f"p99={np.percentile(lat, 99):.1f}ms "
              f"(n={lat.size})")
    print(f"\nsubmitted {st['submitted']:.0f} requests -> "
          f"{st['batches_dispatched']:.0f} batches "
          f"(mean size {st.get('batch_size_mean', 1):.1f}) | "
          f"served={st['served']:.0f} cache={st['served_cache']:.0f} "
          f"shed={st['rejected_admission']:.0f} rejected={rejected} | "
          f"queue wait p50={st.get('queue_wait_p50_ms', 0):.2f}ms "
          f"p99={st.get('queue_wait_p99_ms', 0):.2f}ms")
    if srv.cache is not None:
        cst = srv.cache.stats()
        print(f"cache: hit_rate={cst['hit_rate']:.2f} "
              f"(hits={cst['hits']:.0f} misses={cst['misses']:.0f} "
              f"evictions={cst['evictions']:.0f})")
    if srv.admission is not None:
        ast = srv.admission.stats()
        print(f"admission: shed critical={ast['shed_critical']:.0f} "
              f"throughput={ast['shed_throughput']:.0f}")
    if router is not None:
        rst = router.stats()
        per = " ".join(f"r{i}={rst[f'replica{i}_served']:.0f}"
                       for i in range(len(router)))
        print(f"router: {per} hedges={rst['hedges']:.0f} "
              f"discarded={rst['hedge_discarded']:.0f}")
    print(f"engine: p50={est.get('latency_p50_ms', 0):.1f}ms "
          f"p95={est.get('latency_p95_ms', 0):.1f}ms "
          f"p99={est.get('latency_p99_ms', 0):.1f}ms | "
          f"jit entries={est['jit_cache_size']:.0f} "
          f"padded={est['padded_queries']:.0f}")
    bucket_engine = engines[0] if engines is not None else srv.engine
    for b in sorted(bucket_engine.bucket_sizes):
        if f"bucket{b}_chunks" in est:
            print(f"  bucket {b:3d}: {est[f'bucket{b}_chunks']:4.0f} chunks "
                  f"p50={est[f'bucket{b}_p50_ms']:.1f}ms "
                  f"p99={est[f'bucket{b}_p99_ms']:.1f}ms")
    if obs is not None:
        _dump_obs(obs, args.trace_out)
    print("OK")


if __name__ == "__main__":
    main()
