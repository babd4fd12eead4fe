"""Distance-backend sweep: fp32 vs quantized backends in the real search loop.

``PYTHONPATH=src python -m benchmarks.run --sweep-backends``

Runs the same top-M search (and the full Speed-ANN searcher) through the
``AnnIndex`` facade with every registered distance backend — the fp32 ones
(ref | rowgather | dma) on a fp32 index and the quantized ones (ref_int8 |
rowgather_int8 | ref_bf16) on int8/bf16 indices with the two-stage exact
re-rank enabled — and records per-backend wall time, recall, and parity
against the ``ref`` backend into ``BENCH_dist_backend.json``.  Every row
carries a ``quant`` key so the trajectory tracks fp32 vs int8/bf16 on the
same host.  The file is a TRAJECTORY: each sweep APPENDS its rows, replacing
only rows with the same (searcher, backend, host, interpret) key — so this
container's interpret-mode numbers and future Mosaic/TPU numbers from other
hosts accumulate side by side instead of overwriting each other.  On this
CPU container the Pallas backends run in interpret mode, so absolute times
measure the emulation, not Mosaic.
"""
from __future__ import annotations

import platform
import time
from typing import Dict

import jax.numpy as jnp
import numpy as np

from benchmarks.common import (dataset, nsg_index, time_batched,
                               write_trajectory)
from repro.ann import SearchParams
from repro.core import recall_at_k
from repro.kernels import available_backends
from repro.kernels.platform import interprets
from repro.quant.scheme import required_quant_dtype

K = 10
BASE = SearchParams(k=K, queue_len=64, m_max=6, num_walkers=4,
                    max_steps=256, local_steps=4, sync_ratio=0.8)
# quantized rows run the full AQR-HNSW two-stage shape: quantized traversal
# over a pool widened to RERANK_K, then exact f32 re-ranking — that is the
# configuration whose recall is comparable to the fp32 rows
RERANK_K = 2 * K


def _row_key(row: Dict) -> tuple:
    """Identity of a trajectory row: same key ⇒ newer run supersedes.

    ``batch`` distinguishes the --sweep-batch rows (one per batch size B)
    from the plain backend rows (no batch key ⇒ None), so both families
    accumulate side by side in the same trajectory file."""
    return (row.get("searcher"), row.get("backend"), row.get("batch"),
            row.get("host", "<unknown>"), row.get("interpret"))


def _hostless_superseded(row: Dict, new_rows: list) -> bool:
    """Legacy rows written before the ``host`` field existed cannot name
    their machine; they are superseded by any new row with the same
    (searcher, backend, interpret) — otherwise a re-run on the very machine
    that wrote them would double-count it in the trajectory forever."""
    if "host" in row:
        return False
    return (row.get("searcher"), row.get("backend"),
            row.get("interpret")) in {
        (r.get("searcher"), r.get("backend"), r.get("interpret"))
        for r in new_rows}


def sweep(out_path: str = "BENCH_dist_backend.json", n: int = 2000,
          q: int = 16) -> Dict:
    """One row per (searcher, backend); appends to the JSON trajectory."""
    ds = dataset(n=n, q=q)
    queries = jnp.asarray(ds.queries)
    host = platform.node() or platform.machine()

    rows = []
    ref_ids: Dict[str, np.ndarray] = {}
    # ref first: it is the parity baseline for the other rows.  Each backend
    # runs on the index whose storage it reads (fp32 | int8 | bf16); the
    # graphs are built with identical parameters, only the table differs.
    backends = ("ref",) + tuple(
        b for b in available_backends() if b != "ref")
    indices = {quant: nsg_index(ds, degree=16, quant=quant)
               for quant in {required_quant_dtype(b) for b in backends}}
    for searcher in ("topm", "speedann"):
        for backend in backends:
            quant = required_quant_dtype(backend)
            rerank_k = RERANK_K if quant != "none" else 0
            fn = indices[quant].searcher(BASE.with_(
                algorithm=searcher, backend=backend, rerank_k=rerank_k))
            ids, _, stats = fn(queries)
            us = time_batched(fn, queries)
            ids = np.asarray(ids)
            if backend == "ref":
                ref_ids[searcher] = ids
            row = {
                "searcher": searcher,
                "backend": backend,
                "quant": quant,
                "rerank_k": rerank_k,
                "host": host,
                "interpret": interprets(),
                # dataset scale rides on every row: rows from sweeps with
                # different configs coexist in the trajectory, so the
                # top-level "config" (latest run) must not be trusted per row
                "n": n,
                "q": q,
                "unix_time": time.time(),
                "us_per_query": us / q,
                "recall_at_k": recall_at_k(ids, ds.gt_ids, K),
                "dist_comps": float(np.mean(np.asarray(stats.dist_comps))),
                "ids_match_ref": bool(
                    np.array_equal(ids, ref_ids[searcher])),
            }
            rows.append(row)
            print(f"bench_backend_{searcher}_{backend},"
                  f"{row['us_per_query']:.1f},"
                  f"recall={row['recall_at_k']:.3f};"
                  f"quant={quant};"
                  f"ids_match_ref={row['ids_match_ref']}")

    return write_trajectory(
        out_path, "dist_backend", rows, _row_key,
        config={"n": n, "q": q, "k": K, "m_max": BASE.m_max,
                "queue_len": BASE.queue_len, "dma_group": BASE.dma_group},
        superseded=_hostless_superseded)


if __name__ == "__main__":
    sweep()
