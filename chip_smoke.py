#!/usr/bin/env python3
"""Bring-up smoke test of the Speed-ANN serving path on one TPU.

    python chip_smoke.py                # one chip, the serving path
    python chip_smoke.py --chips 4      # four chips, the corpus-sharded path

One process, one pass through what a user runs:

1. device   — refuse to run unless JAX's first device is a TPU;
2. data     — a SIFT1M-shaped corpus (128-d f32, l2, k = 10; ``--n`` rows,
              cut from 1M to what the build affords) made from ``--seed``
              by ``repro.data.make_vector_dataset``;
3. build    — ``AnnIndex.build`` (NSG, degree 32), timed;
4. serve    — ``index.serve_async`` (coalescer -> bucketed engine ->
              batch-major Speed-ANN): single queries in, every future
              resolved, recall@10 against ``index.exact`` >= 0.9;
5. kernels  — one batch through ``index.search`` per Pallas f32 backend,
              ids equal to ``ref``;
6. int8     — the int8 kernels against ``ref_int8`` on codes quantized from
              the same corpus, at the corpus's real N.

With ``--chips 4`` it runs only the corpus-sharded path
(``build_partitioned_index`` -> ``AnnEngine`` mode "corpus" on a (1, 4)
mesh, one partition per chip) and what it is compared with: recall@10
against ``exact_knn`` on the full corpus, and ids against the same four
partitions searched one after another on chip 0.

Any failed phase exits non-zero.  The last line of standard output is the
JSON verdict ``{"ok": true, "device": {...}}``; nothing is printed there
when a phase fails.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

K = 10
QUERIES = 256             # single queries served; the first 64 also probe
#                           the kernels
RECALL_MIN = 0.9          # the lower of the paper's two recall targets
F32_KERNELS = ("rowgather", "dma", "dedup_gather")


def log(phase: str, **fields) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def serving_params(backend: str = "ref"):
    from repro.ann import SearchParams
    return SearchParams(k=K, queue_len=128, m_max=8, num_walkers=8,
                        local_steps=4, max_steps=128, backend=backend)


def make_data(n: int, n_queries: int, seed: int):
    from repro.data import make_vector_dataset
    t0 = time.perf_counter()
    ds = make_vector_dataset("sift", n=n, n_queries=n_queries, k=K,
                             seed=seed)
    log("data", n=n, d=ds.base.shape[1], queries=n_queries, metric="l2",
        seconds=time.perf_counter() - t0)
    return ds


def phase_build(ds):
    import numpy as np
    from repro.ann import AnnIndex, IndexSpec
    from repro.core.build import reachable
    t0 = time.perf_counter()
    index = AnnIndex.build(ds, IndexSpec(builder="nsg", metric="l2",
                                         degree=32))
    seconds = time.perf_counter() - t0
    g = index.graph
    reach = reachable(np.asarray(g.nbrs), [int(g.medoid)])
    log("build", n=index.n_nodes, degree=g.degree, seconds=seconds,
        reachable_from_medoid=int(reach.sum()))
    assert reach.all(), "graph not connected from its medoid"
    return index


def phase_serve(index, queries, device):
    import numpy as np
    from repro.core import recall_at_k

    params = serving_params()
    srv = index.serve_async(params, max_wait_ms=2.0)
    try:
        compile_s = srv.engine.warmup(index.dim)
        log("serve.compile", seconds_total=sum(compile_s.values()),
            per_bucket=json.dumps({str(b): s for b, s in compile_s.items()}))
        t0 = time.perf_counter()
        futures = [srv.submit(q) for q in queries]
        ids = np.stack([f.result(timeout=600).ids for f in futures])
        wall = time.perf_counter() - t0
    finally:
        srv.close()
    gt, _ = index.exact(queries, K)
    recall = recall_at_k(ids, gt, K)
    stats = device.memory_stats() or {}
    log("serve", algorithm=params.algorithm, backend=params.backend,
        queries=len(futures), seconds=wall, recall_at_10=recall,
        peak_bytes_in_use=stats.get("peak_bytes_in_use", "not reported"))
    assert recall >= RECALL_MIN, f"serve recall@10 {recall} < {RECALL_MIN}"


def phase_kernels(index, queries):
    import numpy as np
    import jax.numpy as jnp
    from repro.kernels import l2dist
    from repro.kernels.dedup import dedupdist
    from repro.kernels.platform import row_tiles
    from repro.kernels.ref import dist_ref

    ref = index.search(queries, serving_params())
    ref_ids = np.asarray(ref.ids)
    for backend in F32_KERNELS:
        t0 = time.perf_counter()
        got = np.asarray(index.search(queries, serving_params(backend)).ids)
        same = bool(np.array_equal(got, ref_ids))
        log("kernels.search", backend=backend, queries=len(queries),
            ids_equal_ref=same, seconds=time.perf_counter() - t0)
        assert same, f"{backend}: ids differ from ref on " \
            f"{int((got != ref_ids).any(axis=1).sum())} queries"

    # kernel level at the real N: a (B, C) candidate grid with padding ids
    g = index.graph
    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, g.n_nodes + 1, size=(len(queries), 256)),
                      jnp.int32)
    q = jnp.asarray(queries)
    tiles = row_tiles(g.vectors)
    log("kernels.tables", vectors_bytes=g.vectors.on_device_size_in_bytes(),
        vector_tiles_bytes=tiles.on_device_size_in_bytes(),
        vector_tiles_shape=tiles.shape)
    want = np.asarray(dist_ref(g.vectors, ids, q))
    got = {"rowgather": l2dist(tiles, ids, q, impl="rowgather"),
           "dma": l2dist(tiles, ids, q, impl="dma"),
           "dedup_gather": dedupdist(tiles, ids, q)}
    for name, d in got.items():
        d = np.asarray(d)
        fin = np.isfinite(want)
        err = float(np.max(np.abs(d[fin] - want[fin]) / want[fin]))
        log("kernels.dist", backend=name, n=g.n_nodes, grid=ids.shape,
            max_rel_err_vs_ref=err,
            inf_pattern_equal=bool(np.array_equal(np.isinf(d), ~fin)))
        np.testing.assert_allclose(d, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(got["dedup_gather"]),
                                  np.asarray(got["rowgather"]))


def phase_int8(index, queries):
    import numpy as np
    import jax.numpy as jnp
    from repro.ann.index import quantize_graph
    from repro.kernels.dedup import dedupdist_int8
    from repro.kernels.platform import row_tiles
    from repro.quant import QuantSpec
    from repro.quant.kernels import int8dist_rowgather, make_int8_dist_fn

    gq = quantize_graph(index.graph, QuantSpec(dtype="int8"))
    n = gq.n_nodes
    rng = np.random.RandomState(1)
    ids = jnp.asarray(rng.randint(0, n + 1, size=(len(queries), 256)),
                      jnp.int32)
    q = jnp.asarray(queries)
    want = np.asarray(make_int8_dist_fn("l2")(
        gq, None, ids[:, None, :], q))[:, 0, :]
    code_tiles = row_tiles(gq.codes)
    row = np.asarray(int8dist_rowgather(code_tiles, gq.scales, ids, q))
    dedup = np.asarray(dedupdist_int8(code_tiles, gq.scales, ids, q))
    log("int8", n=n, grid=ids.shape,
        rowgather_int8_bitwise_ref=bool(np.array_equal(row, want)),
        dedup_gather_int8_bitwise_rowgather=bool(np.array_equal(dedup, row)),
        max_abs_err=float(np.max(np.abs(np.where(np.isinf(want), 0,
                                                  row - want)))))
    np.testing.assert_allclose(row, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(dedup, row)


def run_one_chip(args, device) -> None:
    ds = make_data(args.n, QUERIES, args.seed)
    index = phase_build(ds)
    phase_serve(index, ds.queries, device)
    phase_kernels(index, ds.queries[:64])
    phase_int8(index, ds.queries[:64])


def run_corpus(args, devices) -> None:
    """The corpus-sharded deployment on a (1, S) mesh, S = len(devices)."""
    import functools

    import numpy as np
    import jax
    import jax.numpy as jnp
    from repro.ann import IndexSpec
    from repro.core import recall_at_k
    from repro.core.bfis import search_topm_batch
    from repro.core.distributed import (build_partitioned_index,
                                        make_search_mesh)
    from repro.core.graph import PaddedCSR
    from repro.serve import AnnEngine

    shards = len(devices)
    ds = make_data(args.n, QUERIES, args.seed)
    mesh = make_search_mesh((1, shards), ("data", "model"))
    spec = IndexSpec(builder="nsg", metric="l2", degree=32)
    t0 = time.perf_counter()
    sharded = build_partitioned_index(ds.base, shards, spec, mesh=mesh)
    placement = sorted({d.id for s in sharded.vectors.addressable_shards
                        for d in [s.device]})
    log("corpus.build", n=args.n, shards=shards,
        seconds=time.perf_counter() - t0, shard_devices=placement)
    assert len(placement) == shards, f"shards on devices {placement}"

    params = serving_params().with_(max_steps=512)
    engine = AnnEngine(sharded, params, mesh=mesh)
    compile_s = engine.warmup(ds.base.shape[1])
    log("corpus.compile", seconds_total=sum(compile_s.values()))
    t0 = time.perf_counter()
    res = engine.search(ds.queries)
    wall = time.perf_counter() - t0
    gt = ds.gt_ids                       # exact_knn over the full corpus
    recall = recall_at_k(res.ids, gt, K)
    log("corpus.serve", queries=len(ds.queries), seconds=wall,
        recall_at_10=recall)
    assert recall >= RECALL_MIN, f"corpus recall@10 {recall} < {RECALL_MIN}"

    # the same partitions searched one after another on chip 0
    cfg = params.to_search_config("l2").with_(m_max=1, staged=False,
                                              num_walkers=1)

    @functools.partial(jax.jit, static_argnums=(3,))
    def shard_search(nbrs, vectors, medoid, cfg, q):
        g = PaddedCSR(nbrs=nbrs, vectors=vectors, medoid=medoid, n_top=0,
                      flat=jnp.zeros((0,) + nbrs.shape[1:2]
                                     + vectors.shape[1:2], vectors.dtype))
        return search_topm_batch(g, q, cfg)

    dev0 = devices[0]
    q = jax.device_put(jnp.asarray(ds.queries), dev0)
    all_ids, all_d = [], []
    for s in range(shards):
        part = [jax.device_put(np.asarray(a[s]), dev0) for a in
                (sharded.nbrs, sharded.vectors, sharded.medoids)]
        ids, dists, _ = shard_search(*part, cfg, q)
        off = int(sharded.offsets[s])
        all_ids.append(np.where(np.asarray(ids) >= 2**31 - 1,
                                np.asarray(ids), np.asarray(ids) + off))
        all_d.append(np.asarray(dists))
    d_cat = np.concatenate(all_d, axis=1)
    i_cat = np.concatenate(all_ids, axis=1)
    order = np.lexsort((i_cat, d_cat), axis=1)[:, :K]
    ref_ids = np.take_along_axis(i_cat, order, axis=1)
    same = bool(np.array_equal(res.ids, ref_ids))
    log("corpus.compare", sequential_on=str(dev0), ids_equal=same,
        recall_sequential=recall_at_k(ref_ids, gt, K))
    assert same, "corpus-sharded ids differ from the sequential search"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    # the NSG build prunes in host numpy at ~20 ms per point on a v5e host,
    # so N is what builds inside the run's 1200 s (see PERF.md)
    ap.add_argument("--n", type=int, default=24_000,
                    help="corpus rows (default: %(default)s)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import use_compile_cache
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print("chip_smoke: no TPU found; refusing to run on "
              f"{dev.platform}", file=sys.stderr)
        return 1
    log("device", platform=dev.platform, kind=dev.device_kind,
        count=len(devices))
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 1
    log("compile_cache", dir=use_compile_cache())
    if args.chips == 4:
        run_corpus(args, devices[:4])
    else:
        run_one_chip(args, dev)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
