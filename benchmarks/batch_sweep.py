"""Batch-amortization sweep: per-query cost of the batch-major engine vs B.

``PYTHONPATH=src python -m benchmarks.run --sweep-batch``

The batch-major traversal engine advances a whole (B, d) query batch per
global step with ONE distance launch, so per-step fixed costs (kernel
launch, queue-op dispatch, interpret-mode emulation overhead) amortize over
B.  This sweep runs the same top-M searcher at B ∈ {1, 8, 64, 256} for each
requested backend and appends one row per (backend, B) to
``BENCH_dist_backend.json`` — the same trajectory file as
``--sweep-backends``, with rows keyed (searcher, backend, BATCH, host,
interpret) so batch rows and plain backend rows coexist.

Two per-query metrics per row:

* ``us_per_query``     — wall / B.  The serving-relevant number, but it
  conflates amortization with straggler cost (a batch runs until its
  SLOWEST query converges; converged lanes are masked no-ops).
* ``us_per_lane_step`` — wall / (B × executed steps), where executed steps
  = the batch's max step count.  This isolates the per-step, per-lane cost
  the batch dimension amortizes; it is the number that must DECREASE with
  B for the batch-major refactor to be paying off on a backend.

Each row also carries the cross-query frontier-overlap counters
(``SearchStats.uniq_comps`` / ``batch_dup_comps``, first-toucher
attribution): ``uniq_comps`` is how many rows a batch-deduplicating gather
actually fetches, ``dist_comps`` how many a per-lane gather fetches, and
``batch_dup_ratio`` = dup/dist the share of gathers dedup elides — the
ratio GROWS with B as frontiers overlap more, which is the dedup_gather
backend's scaling argument in one number.

On this CPU container the Pallas backends run in interpret mode, so their
absolute numbers measure the emulation; the ``ref`` backend is the
apples-to-apples amortization signal until a TPU session re-runs the sweep
compiled.
"""
from __future__ import annotations

import platform
import time
from typing import Dict, Sequence

import jax.numpy as jnp
import numpy as np

from benchmarks.common import (dataset, nsg_index, time_batched,
                               write_trajectory)
from benchmarks.dist_backend import _row_key
from repro.ann import SearchParams
from repro.core import recall_at_k
from repro.kernels.platform import interprets

K = 10
BATCHES = (1, 8, 64, 256)
BACKENDS = ("ref", "rowgather", "dedup_gather")
PARAMS = SearchParams(k=K, queue_len=32, m_max=4, max_steps=96,
                      algorithm="topm")


def sweep(out_path: str = "BENCH_dist_backend.json",
          backends: Sequence[str] = BACKENDS,
          batches: Sequence[int] = BATCHES, n: int = 2000) -> Dict:
    """One row per (backend, batch); appends to the JSON trajectory."""
    q_max = max(batches)
    ds = dataset(n=n, q=q_max)
    index = nsg_index(ds, degree=16)
    host = platform.node() or platform.machine()

    rows = []
    for backend in backends:
        fn = index.searcher(PARAMS.with_(backend=backend))
        for bsz in batches:
            queries = jnp.asarray(ds.queries[:bsz])
            ids, _, stats = fn(queries)
            us = time_batched(fn, queries)
            steps = np.asarray(stats.steps)
            # the batch executes max(steps) loop iterations; converged
            # lanes ride along masked, so B×max(steps) is the lane-step
            # count the one-launch-per-step engine actually paid for
            lane_steps = bsz * max(int(steps.max()), 1)
            dist_comps = int(np.sum(np.asarray(stats.dist_comps)))
            uniq_comps = int(np.sum(np.asarray(stats.uniq_comps)))
            dup_comps = int(np.sum(np.asarray(stats.batch_dup_comps)))
            row = {
                "searcher": "topm",
                "backend": backend,
                "batch": bsz,
                "host": host,
                "interpret": interprets(),
                "n": n,
                "q": bsz,
                "unix_time": time.time(),
                "us_per_query": us / bsz,
                "us_per_lane_step": us / lane_steps,
                "steps_mean": float(steps.mean()),
                "steps_max": int(steps.max()),
                # cross-query overlap: unique-gather count <= candidate
                # count, with the dedup ratio improving as B grows
                "dist_comps": dist_comps,
                "uniq_comps": uniq_comps,
                "batch_dup_comps": dup_comps,
                "batch_dup_ratio": (dup_comps / dist_comps
                                    if dist_comps else 0.0),
                "recall_at_k": recall_at_k(
                    np.asarray(ids), ds.gt_ids[:bsz], K),
            }
            rows.append(row)
            print(f"bench_batch_{backend}_B{bsz},"
                  f"{row['us_per_query']:.1f},"
                  f"us_per_lane_step={row['us_per_lane_step']:.2f};"
                  f"dup_ratio={row['batch_dup_ratio']:.3f};"
                  f"recall={row['recall_at_k']:.3f}")

    return write_trajectory(out_path, "dist_backend", rows, _row_key)


if __name__ == "__main__":
    sweep()
