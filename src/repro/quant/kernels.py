"""Quantized distance backends for the neighbor-expansion hot path.

These are drop-in BATCH-MAJOR ``DistFn`` implementations (see
``core.bfis.DistFn``: (B, M, R) ids in, (B, M, R) f32 distances out, one
launch per global step for the whole query batch)
that read the index's QUANTIZED table (``PaddedCSR.codes`` + ``.scales``)
instead of the float32 ``vectors`` — the gather-side payload shrinks 4x
(int8) or 2x (bf16), which is exactly what the paper's memory-hierarchy
analysis says bounds expansion throughput.  They register themselves with
``repro.kernels.registry`` so search algorithms never change:

* ``ref_int8``       — pure-jnp int8 gather; per-vector scales take the
  integer fast path (int32-accumulated dot against an integer-quantized
  query on the widest non-overflowing grid, ONE f32 rescale per candidate);
  per-dimension scales dequantize the gathered rows and reduce in f32
  (memory win only).
* ``rowgather_int8`` — scalar-prefetch Pallas kernel: candidate ids drive
  the BlockSpec index_map of the row-tiled int8 codes
  (``PaddedCSR.code_tiles``, see ``registry.with_kernel_tables``), the VPU accumulates the code dot in int32
  and rescales once by the candidate's per-vector scale.  Per-vector
  scales only (the integer path is the point of the kernel).
* ``ref_bf16``       — pure-jnp bf16 gather, f32 reduction (scale-free).

Every backend serves every metric: "l2" uses
``s²·‖cx‖² − 2·s·s_q·(cx·c_q) + ‖q‖²`` with the EXACT f32 query norm (the
only exact term available without touching the f32 table), "ip"/"cosine"
use ``−s·s_q·(cx·c_q)``.  Distances are float32, padded ids (≥ N) map to
+inf — identical contracts to the f32 backends, so the two-stage re-ranked
search composes with any of them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import pallas_call, row_tiles, split_batch
from repro.kernels.registry import kernel_table, register_backend
from repro.quant.codec import quantize_query


def require_codes(graph, dtype: str, tiled: bool = False):
    """Trace-time check that the graph carries a ``dtype`` quantized table.

    Raises with build guidance instead of a shape error deep inside jit.
    Returns (codes, scales); with ``tiled`` (the int8 Pallas kernels) the
    row-tiled ``code_tiles`` in place of the codes, and per-vector scales
    are required."""
    codes, scales = getattr(graph, "codes", None), getattr(graph, "scales",
                                                           None)
    if codes is None or codes.size == 0:
        raise ValueError(
            f"the '{dtype}' distance backends need a quantized table; "
            f"build the index with IndexSpec(quant=\"{dtype}\")")
    want = jnp.int8 if dtype == "int8" else jnp.bfloat16
    if codes.dtype != want:
        raise ValueError(
            f"index is quantized as {codes.dtype}, not {dtype}; pick the "
            f"matching backend or rebuild with IndexSpec(quant=\"{dtype}\")")
    if not tiled:
        return codes, scales
    if scales.shape[0] == 1:
        raise NotImplementedError(
            "the int8 Pallas kernels implement the per-vector-scale integer "
            "path; per-dimension scales are served by 'ref_int8'")
    return kernel_table(graph, "code_tiles"), scales


def _kmetric(metric: str) -> str:
    if metric in ("ip", "cosine"):
        return "ip"
    if metric == "l2":
        return "l2"
    raise ValueError(f"unknown metric {metric!r}")


# ---------------------------------------------------------------------------
# ref_int8 / ref_bf16: pure-jnp quantized gathers
# ---------------------------------------------------------------------------

def make_int8_dist_fn(metric: str = "l2"):
    """Batch-major int8 DistFn: int32-accumulated integer dot (per-vector
    scales) or dequantize-and-reduce (per-dimension scales).  One call
    gathers every query's (B, M·R) code rows at once."""
    kmetric = _kmetric(metric)

    def dist_fn(graph, active_ids, nbr_ids, queries):
        codes, scales = require_codes(graph, "int8")
        b, m, r = nbr_ids.shape
        flat = nbr_ids.reshape(b, m * r)
        n = graph.n_nodes
        safe = jnp.minimum(flat, n - 1)
        rows = codes[safe]                                 # (B, C, d) int8
        qf = queries.astype(jnp.float32)                   # (B, d)
        per_dim = scales.shape[0] == 1                     # static at trace
        if per_dim:
            x = rows.astype(jnp.float32) * scales          # (B, C, d) f32
            if kmetric == "ip":
                d = -jnp.sum(x * qf[:, None, :], axis=-1)
            else:
                d = jnp.sum((x - qf[:, None, :]) ** 2, axis=-1)
        else:
            # query codes live on a wider grid (codec.query_levels) sized so
            # the int8 x query dot cannot overflow the int32 accumulator;
            # the asymmetric error stays dominated by the stored codes.
            # Integer arithmetic is exact, so the batched einsum is
            # bit-identical to the per-query matvec it replaces.
            qc, qs = quantize_query(qf)                    # (B,d) i32, (B,1)
            acc = jnp.einsum("bcd,bd->bc", rows.astype(jnp.int32), qc)
            s = scales[safe, 0]                            # (B, C) f32
            xq = s * qs * acc.astype(jnp.float32)
            if kmetric == "ip":
                d = -xq
            else:
                rn2 = jnp.sum(rows.astype(jnp.int32) ** 2, axis=-1)
                q2 = jnp.sum(qf * qf, axis=-1, keepdims=True)
                d = jnp.maximum(
                    s * s * rn2.astype(jnp.float32) - 2.0 * xq + q2, 0.0)
        d = jnp.where(flat < n, d, jnp.inf)
        return d.reshape(b, m, r)
    return dist_fn


def make_bf16_dist_fn(metric: str = "l2"):
    """Batch-major bf16 DistFn: half-width gather, f32 reduction, no
    scales."""
    kmetric = _kmetric(metric)

    def dist_fn(graph, active_ids, nbr_ids, queries):
        codes, _ = require_codes(graph, "bf16")
        b, m, r = nbr_ids.shape
        flat = nbr_ids.reshape(b, m * r)
        n = graph.n_nodes
        rows = codes[jnp.minimum(flat, n - 1)].astype(jnp.float32)
        qf = queries.astype(jnp.float32)                   # (B, d)
        if kmetric == "ip":
            d = -jnp.sum(rows * qf[:, None, :], axis=-1)
        else:
            d = jnp.sum((rows - qf[:, None, :]) ** 2, axis=-1)
        d = jnp.where(flat < n, d, jnp.inf)
        return d.reshape(b, m, r)
    return dist_fn


# ---------------------------------------------------------------------------
# rowgather_int8: scalar-prefetch Pallas kernel (int32 accumulate + rescale)
# ---------------------------------------------------------------------------

def int8_query_operands(queries: jax.Array, k: int):
    """Quantize a (B, d) f32 query batch once per call: (B, k, 128) int32
    query codes (zero-padded like the code tiles) and (B, 2) f32
    ``[query scale, ‖q‖²]``."""
    qc, qs = quantize_query(queries)                       # (B,d) i32, (B,1)
    q2 = jnp.sum(queries.astype(jnp.float32) ** 2, axis=-1, keepdims=True)
    qc = row_tiles(qc)
    assert qc.shape[1] == k, (qc.shape, k)
    return qc, jnp.concatenate([qs, q2], axis=1)


def int8_pair_dist(row: jax.Array, qc: jax.Array, s, qs, q2,
                   metric: str) -> jax.Array:
    """One (code row, query) distance: int32-accumulated integer dot, ONE
    f32 rescale — the op order of ``ref_int8``'s per-vector path, so every
    int8 kernel is bit-identical to it."""
    row = row.astype(jnp.int32)                            # int8 -> i32
    acc = jnp.sum(row * qc)                                # i32 accumulation
    xq = s * qs * acc.astype(jnp.float32)                  # one f32 rescale
    if metric == "ip":
        return -xq
    rn2 = jnp.sum(row * row)                               # i32 accumulation
    return jnp.maximum(s * s * rn2.astype(jnp.float32) - 2.0 * xq + q2, 0.0)


def _rowgather_int8_kernel(ids_ref, row_ref, scale_ref, qc_ref, qmeta_ref,
                           out_ref, *, n_nodes: int, metric: str):
    b = pl.program_id(0)
    c = pl.program_id(1)
    dist = int8_pair_dist(row_ref[...], qc_ref[b], scale_ref[b, c],
                          qmeta_ref[b, 0], qmeta_ref[b, 1], metric)
    out_ref[b, c] = jnp.where(ids_ref[b, c] < n_nodes, dist,
                              jnp.float32(jnp.inf))


@split_batch
def int8dist_rowgather(
    code_tiles: jax.Array, scales: jax.Array, ids: jax.Array,
    queries: jax.Array, *, metric: str = "l2",
) -> jax.Array:
    """(N, k, 128) row-tiled int8 codes + (N, 1) scales, (B, C) ids, (B, d)
    f32 queries -> (B, C) f32 distances.

    The prefetched candidate ids drive the code table's index_map, so the
    pipeline's gather-side traffic is ~d bytes per candidate instead of
    4d.  Each candidate's scale (4 bytes) is gathered outside the kernel
    and read from SMEM; query quantization (codes + [scale, ‖q‖²] meta)
    happens once per call outside the grid.
    """
    n, k, lanes = code_tiles.shape
    bsz, c = ids.shape
    if scales.shape != (n, 1):
        raise ValueError(
            f"int8dist_rowgather needs per-vector scales of shape "
            f"({n}, 1), got {scales.shape}; per-dimension scales are "
            f"served by the 'ref_int8' backend")
    qc, qmeta = int8_query_operands(queries, k)
    s = scales[jnp.minimum(ids, n - 1), 0]                 # (B, C) f32

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bsz, c),
        in_specs=[
            pl.BlockSpec(
                (None, k, lanes), lambda b, cc, ids_ref: (jnp.minimum(
                    ids_ref[b, cc], n - 1), 0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((bsz, k, lanes), lambda b, cc, ids_ref: (0, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
    )
    kernel = functools.partial(_rowgather_int8_kernel, n_nodes=n,
                               metric=_kmetric(metric))
    return pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bsz, c), jnp.float32),
    )(ids, code_tiles, s, qc, qmeta)


def make_rowgather_int8_dist_fn(metric: str = "l2"):
    """Batch-major Pallas int8 DistFn (mirroring ``registry.make_dist_fn``):
    the whole (B, M·R) candidate grid is ONE scalar-prefetch launch."""
    def dist_fn(graph, active_ids, nbr_ids, queries):
        code_tiles, scales = require_codes(graph, "int8", tiled=True)
        b, m, r = nbr_ids.shape
        d = int8dist_rowgather(code_tiles, scales,
                               nbr_ids.reshape(b, m * r), queries,
                               metric=metric)
        return d.reshape(b, m, r)
    return dist_fn


# ---------------------------------------------------------------------------
# registry entries — selectable purely via SearchParams.backend
# ---------------------------------------------------------------------------

def _cfg_metric(cfg) -> str:
    return getattr(cfg, "metric", "l2") or "l2"


@register_backend("ref_int8")
def _ref_int8_backend(cfg):
    return make_int8_dist_fn(_cfg_metric(cfg))


@register_backend("rowgather_int8", tiles="code_tiles")
def _rowgather_int8_backend(cfg):
    return make_rowgather_int8_dist_fn(_cfg_metric(cfg))


@register_backend("ref_bf16")
def _ref_bf16_backend(cfg):
    return make_bf16_dist_fn(_cfg_metric(cfg))
