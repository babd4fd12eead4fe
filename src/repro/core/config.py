"""Speed-ANN search configuration — the traversal layer's plumbing type.

:class:`SearchConfig` lives HERE, next to the algorithms it parameterizes
(``core.bfis`` / ``core.speedann`` / ``core.distributed``), not in the
model-config grab-bag ``repro.config`` (which re-exports it for backward
compatibility).  Public callers should prefer the :mod:`repro.ann` facade
(``IndexSpec`` + ``SearchParams``); ``SearchParams.to_search_config`` lowers
onto this type.

A frozen dataclass: hashable (usable as a jit static argument and as a
searcher-cache key), serializable, and diffable.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass


@dataclass(frozen=True)
class SearchConfig:
    """Speed-ANN search hyperparameters (Algorithm 3 + §4)."""
    k: int = 10                  # neighbors to return
    # distance metric of the index: "l2" (squared L2, minimized), "ip"
    # (negative inner product, minimized — MIPS), "cosine" (ip on unit-norm
    # vectors; the AnnIndex facade pre-normalizes base vectors and queries).
    metric: str = "l2"
    queue_len: int = 64          # L, bounded frontier capacity
    m_max: int = 8               # max expansion width M (paper: up to #threads)
    stage_every: int = 1         # t: double M every t global steps (paper: t=1)
    staged: bool = True          # staged search (§4.2); False = fixed M=m_max
    max_steps: int = 64          # step budget (safety bound; BFiS may need more)
    sync_ratio: float = 0.8      # R in Algorithm 2 (paper: 0.8/0.9 per dataset)
    local_steps: int = 4         # max local steps between sync checks
    num_walkers: int = 1         # W: private-queue workers (vmapped or devices)
    visited_mode: str = "bitmap"  # "bitmap" | "loose" | "hash"
    hash_bits: int = 14          # hash-set capacity = 2**hash_bits
    # distance backend for the neighbor-expansion hot path; resolved through
    # repro.kernels.registry:  "ref" (pure-jnp gather), "rowgather"
    # (scalar-prefetch Pallas row gather), "dma" (explicit-DMA tile
    # gather).  Backends are BATCH-MAJOR: one kernel launch covers the
    # whole (B, M, R) expansion of a query batch per global step.  Pallas
    # backends run in interpret mode on CPU and lower through Mosaic on TPU
    # (see kernels/platform.pallas_call).
    dist_backend: str = "ref"
    dma_group: int = 8           # G: rows per DMA tile ("dma" backend only)
    # distributed search: static outer (scatter/merge) round budget — bounded
    # rounds give deterministic worst-case latency (straggler mitigation)
    global_rounds: int = 12

    def with_(self, **kw) -> "SearchConfig":
        return dataclasses.replace(self, **kw)
