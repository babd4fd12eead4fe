"""Build-throughput sweep: batched graph construction vs ``build_batch``.

``PYTHONPATH=src python -m benchmarks.run --sweep-build``

Construction routes every candidate search through the jit-compiled
batch-major engine (``search_topm_batch``), ``build_batch`` lanes per device
call.  Like serving, per-call fixed costs (dispatch, queue ops, interpret
emulation) amortize over the batch — so build wall-clock should DROP as
``build_batch`` grows while the output graph stays bit-identical.  This
sweep measures exactly that claim:

* one ``mode="serial"`` baseline row — the scalar per-point reference
  builder (``build_nsg_serial``: host prune loops, one search lane per
  device call), the seed builder's cost shape;
* one ``mode="batched"`` row per (build_batch, backend) over the SAME data
  and seed, reporting ``points_per_s`` (insertion throughput) and
  ``build_s`` (wall clock);
* ``build_s`` is **steady-state**: every configuration builds once untimed
  to compile its batch shape, and the clock runs on the second build — the
  same convention as the serving trajectories (us_per_query is steady-state
  jitted).  A cold first build would charge each batch size its one-off
  jit compile and bury the amortization signal under it;
* every batched row is checked for **bit-parity** against the
  ``build_batch=1`` graph (identical nbrs/medoid bytes) and the row records
  ``deterministic`` = a second run + a batch-order-permuted run reproduced
  the same bytes — the acceptance gate of the batched-construction change,
  recomputed at bench time on bench-scale data;
* ``recall_at_k`` of a fixed beam search over each built graph vs exact
  ground truth, so a throughput win can never silently trade recall away.

Rows append to ``BENCH_build.json`` keyed (n, batch, backend, mode, host,
interpret) — re-runs on the same host replace their own rows, other hosts'
trajectories persist (docs/benchmarks.md).

On this CPU container the Pallas backends run in interpret mode; ``ref`` is
the apples-to-apples amortization signal until a TPU session re-runs the
sweep compiled.
"""
from __future__ import annotations

import platform
import time
from typing import Dict, Optional, Sequence

import jax.numpy as jnp
import numpy as np

from benchmarks.common import K, dataset, write_trajectory
from repro.core import build_nsg, build_nsg_serial, recall_at_k
from repro.core.bfis import search_topm_batch
from repro.core.config import SearchConfig
from repro.kernels.platform import interprets

BATCHES = (1, 8, 32, 128)
BACKENDS = ("ref",)
DEGREE = 16
EF = 32
PASSES = 2
SEED = 0


def _row_key(row: Dict) -> tuple:
    return (row["n"], row["batch"], row["backend"], row["mode"],
            row["host"], row["interpret"])


def _graph_bytes(g) -> bytes:
    return (np.asarray(g.nbrs).tobytes()
            + np.asarray(g.medoid).tobytes())


def _build(data, *, batch: int, backend: str,
           batch_perm: Optional[int] = None):
    return build_nsg(data, degree=DEGREE, alpha=1.2, ef_construction=EF,
                     seed=SEED, passes=PASSES, metric="l2",
                     build_batch=batch, build_backend=backend,
                     batch_perm=batch_perm)


def _graph_recall(g, ds) -> float:
    cfg = SearchConfig(k=K, queue_len=64, m_max=4, max_steps=128)
    ids, _, _ = search_topm_batch(g, jnp.asarray(ds.queries), cfg)
    return recall_at_k(np.asarray(ids), ds.gt_ids, K)


def sweep(out_path: str = "BENCH_build.json",
          backends: Sequence[str] = BACKENDS,
          batches: Sequence[int] = BATCHES, n: int = 2000) -> Dict:
    """One serial baseline + one row per (build_batch, backend)."""
    ds = dataset(n=n, q=64)
    data = np.asarray(ds.base, np.float32)
    host = platform.node() or platform.machine()
    base = {"n": n, "host": host, "interpret": interprets(),
            "degree": DEGREE, "ef": EF, "passes": PASSES}

    def _serial():
        return build_nsg_serial(data, degree=DEGREE, alpha=1.2,
                                ef_construction=EF, seed=SEED,
                                passes=PASSES)

    rows = []
    g_serial = _serial()                     # warm-up: compiles the 1-lane shape
    t0 = time.perf_counter()
    g_serial = _serial()
    serial_s = time.perf_counter() - t0
    ref_bytes = _graph_bytes(g_serial)
    rows.append(dict(base, mode="serial", batch=1, backend="ref",
                     unix_time=time.time(), build_s=serial_s,
                     points_per_s=n / serial_s, deterministic=True,
                     parity_vs_serial=True,
                     recall_at_k=_graph_recall(g_serial, ds)))
    print(f"bench_build_serial,{serial_s:.2f}s,"
          f"{rows[-1]['points_per_s']:.0f}pts/s,"
          f"recall={rows[-1]['recall_at_k']:.3f}")

    for backend in backends:
        for batch in batches:
            g = _build(data, batch=batch, backend=backend)   # compile pass
            gb = _graph_bytes(g)
            t0 = time.perf_counter()
            g2 = _build(data, batch=batch, backend=backend)  # timed, warm
            build_s = time.perf_counter() - t0
            # two-run + permuted-chunk reproducibility, recomputed here
            # (the timed warm run doubles as the second-run witness)
            deterministic = (
                gb == _graph_bytes(g2)
                and gb == _graph_bytes(_build(data, batch=batch,
                                              backend=backend,
                                              batch_perm=7)))
            row = dict(base, mode="batched", batch=batch, backend=backend,
                       unix_time=time.time(), build_s=build_s,
                       points_per_s=n / build_s,
                       deterministic=deterministic,
                       parity_vs_serial=gb == ref_bytes,
                       recall_at_k=_graph_recall(g, ds))
            rows.append(row)
            print(f"bench_build_{backend}_bb{batch},{build_s:.2f}s,"
                  f"{row['points_per_s']:.0f}pts/s,"
                  f"parity={row['parity_vs_serial']};"
                  f"det={row['deterministic']};"
                  f"recall={row['recall_at_k']:.3f}")
            assert row["parity_vs_serial"], (
                f"build_batch={batch} diverged from the serial reference")
            assert deterministic, (
                f"build_batch={batch} is not run/permutation deterministic")

    return write_trajectory(out_path, "build", rows, _row_key,
                            config=dict(base, batches=list(batches),
                                        backends=list(backends)))


if __name__ == "__main__":
    sweep()
