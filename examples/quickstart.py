"""Quickstart: the AnnIndex lifecycle — build, save/load, search, serve.

One facade covers the whole paper stack: metric-general index construction
(l2 | ip | cosine), npz persistence, every search algorithm (BFiS, top-M,
Speed-ANN, sharded walkers), every distance-kernel backend — including the
int8/bf16 quantized ones with two-stage exact re-ranking — and batched
serving.

    PYTHONPATH=src python examples/quickstart.py
"""
import os
import tempfile
import time

import numpy as np

from repro.ann import AnnIndex, IndexSpec, SearchParams
from repro.core import recall_at_k
from repro.data import make_vector_dataset
from repro.launch.compile_cache import use_compile_cache


def main():
    use_compile_cache()
    print("== Speed-ANN quickstart (AnnIndex facade) ==")
    ds = make_vector_dataset("sift", n=5000, n_queries=32, k=10, dim=32)
    print(f"dataset: {ds.base.shape[0]} points, d={ds.base.shape[1]}")

    # -- build: the metric is an index-time property ------------------------
    t0 = time.time()
    index = AnnIndex.build(ds, IndexSpec(builder="nsg", metric="l2",
                                         degree=24))
    print(f"built {index} in {time.time() - t0:.1f}s")

    # -- save / load round-trip ---------------------------------------------
    path = index.save(os.path.join(tempfile.mkdtemp(), "sift_analog.npz"))
    index = AnnIndex.load(path)
    print(f"round-tripped through {path}")

    # -- search: per-query knobs live in SearchParams -----------------------
    gt, _ = index.exact(ds.queries, 10)      # metric-aware ground truth
    for algorithm in ("bfis", "topm", "speedann"):
        params = SearchParams(k=10, queue_len=64, m_max=8, num_walkers=8,
                              max_steps=256, local_steps=8,
                              algorithm=algorithm)
        ids, _, st = index.search(ds.queries, params)
        r = recall_at_k(np.asarray(ids), gt, 10)
        s = st.summary()
        print(f"{algorithm:9s} recall@10={r:.3f} steps={s['steps']:.1f} "
              f"comps={s['dist_comps']:.0f}")

    # -- the same search through a Pallas distance kernel -------------------
    ids, _, _ = index.search(
        ds.queries, SearchParams(k=10, queue_len=64, m_max=8, num_walkers=8,
                                 max_steps=256, local_steps=8,
                                 algorithm="speedann", backend="rowgather"))
    r = recall_at_k(np.asarray(ids), gt, 10)
    print(f"speedann (Pallas rowgather kernel, interpret) recall@10={r:.3f}")

    # -- quantized storage + two-stage search -------------------------------
    # int8 codes shrink the gather-side payload 4x; the two-stage search
    # (quantized traversal, exact f32 re-rank of the top rerank_k) recovers
    # fp32 recall.  Backend + quant are pure config — no algorithm changes.
    q8 = AnnIndex.build(ds, IndexSpec(builder="nsg", metric="l2", degree=24,
                                      quant="int8"))
    q8_path = q8.save(os.path.join(tempfile.mkdtemp(), "sift_int8.npz"))
    q8 = AnnIndex.load(q8_path)          # codes + scales round-trip
    ids, _, _ = q8.search(
        ds.queries, SearchParams(k=10, queue_len=64, m_max=8, num_walkers=8,
                                 max_steps=256, local_steps=8,
                                 algorithm="speedann", backend="ref_int8",
                                 rerank_k=30))
    r = recall_at_k(np.asarray(ids), gt, 10)
    print(f"int8 two-stage (ref_int8 + rerank_k=30) recall@10={r:.3f}  "
          f"[codes table {np.asarray(q8.graph.codes).nbytes} B vs f32 "
          f"{np.asarray(q8.graph.vectors).nbytes} B]")

    # -- metric choice: cosine retrieval over the same raw vectors ----------
    cos = AnnIndex.build(ds, IndexSpec(metric="cosine", degree=24))
    cgt, _ = cos.exact(ds.queries, 10)
    ids, _, _ = cos.search(ds.queries, SearchParams(algorithm="speedann",
                                                    m_max=8, num_walkers=8,
                                                    max_steps=256))
    r = recall_at_k(np.asarray(ids), cgt, 10)
    print(f"cosine index recall@10={r:.3f} (queries normalized inside the "
          f"facade)")

    # -- serve: bucketed batched engine over the index ----------------------
    engine = index.serve(SearchParams(k=10, m_max=8, num_walkers=8,
                                      max_steps=256),
                         bucket_sizes=(1, 4, 16, 32))
    res = engine.search(ds.queries[:5], gt_ids=gt[:5])
    print(f"served B=5 -> bucket {res.buckets} in {res.latency_ms:.1f} ms, "
          f"recall@10={engine.metrics()['recall_at_k']:.3f}")


if __name__ == "__main__":
    main()
