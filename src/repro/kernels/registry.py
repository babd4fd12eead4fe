"""Pluggable distance-backend registry for the search hot path.

The neighbor expansion (Challenges II & IV) is the paper's compute hot spot;
this module is the seam between the search algorithms (``core.bfis``,
``core.speedann``, ``core.distributed``) and the distance implementations
(``kernels.l2dist``).  Search code never names a kernel: it carries a
``SearchConfig.dist_backend`` string that is resolved here to a BATCH-MAJOR
``DistFn(graph, active_ids (B,M), nbr_ids (B,M,R), queries (B,d)) ->
(B,M,R)`` — one launch covers the whole query batch's expansion for a
global step, so the kernels see the full (B·M·R, d) × (B, d) workload they
can amortize instead of B per-lane gathers.

Built-in backends:

* ``ref``       — pure-jnp two-level gather (``core.bfis.dist_l2``); exploits
  the flattened neighbor layout for hot vertices.
* ``rowgather`` — scalar-prefetch Pallas kernel: candidate ids drive the
  BlockSpec index_map so the pipeline streams exactly the needed rows; the
  batch rides in the kernel grid's leading dimension.
* ``dma``       — explicit-DMA tile gather (G rows per grid step); candidate counts
  are padded to the ``cfg.dma_group`` tile (padding ids map to +inf and are
  sliced off, so ragged M·R shapes are transparent to callers).
* ``dedup_gather`` — batch-deduplicating gather (``kernels.dedup``): the
  step's flattened (B·C,) candidate ids are sorted first and each DISTINCT
  row is gathered ONCE for the whole batch while every lane's pair is
  reduced.  Bit-identical to ``rowgather``; the saved gathers are exactly
  ``SearchStats.batch_dup_comps`` when the step fits one launch
  (``kernels.platform.MAX_PAIRS``).

Quantized backends (``ref_int8`` | ``rowgather_int8`` | ``dedup_gather_int8``
| ``ref_bf16``, from ``repro.quant.kernels`` and ``kernels.dedup``) gather
from the index's int8/bf16 codes table
instead of the f32 vectors; they require an index built with
``IndexSpec(quant=...)`` and compose with the two-stage re-ranked search
(``SearchParams.rerank_k``).

New kernels register with :func:`register_backend` and become selectable via
``SearchConfig(dist_backend=...)`` without touching any search algorithm.
"""
from __future__ import annotations

from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.kernels.platform import row_tiles

# factory(cfg: SearchConfig) -> DistFn (see core.bfis.DistFn)
DistFactory = Callable[..., Callable]

_REGISTRY: Dict[str, DistFactory] = {}
# backend name -> the row-tiled table its Pallas kernel reads
_TILED: Dict[str, str] = {}


def register_backend(name: str, tiles: Optional[str] = None):
    """Decorator: register ``factory(cfg) -> DistFn`` under ``name``.
    ``tiles`` names the row-tiled table the backend's kernel reads
    (``"vector_tiles"`` | ``"code_tiles"``, see :func:`with_kernel_tables`).
    """
    def deco(factory: DistFactory) -> DistFactory:
        _REGISTRY[name] = factory
        if tiles is not None:
            _TILED[name] = tiles
        return factory
    return deco


def with_kernel_tables(graph, backend: str):
    """``graph`` (a ``PaddedCSR`` or ``ShardedIndex``) plus the row-tiled
    table ``backend``'s Pallas kernel gathers from: ``vector_tiles`` from
    the f32 vectors, ``code_tiles`` from int8 codes
    (:func:`repro.kernels.platform.row_tiles`).  Laid out here, once,
    outside any jitted search — for d > 128 the layout is a copy of the
    whole table — and only for the backends that read it.  Unchanged for
    the ``ref`` backends and when the table is already there."""
    field = _TILED.get(backend)
    if field is None or getattr(graph, field, None) is not None:
        return graph
    src = getattr(graph, "vectors" if field == "vector_tiles" else "codes",
                  None)
    if src is None or (field == "code_tiles" and src.dtype != jnp.int8):
        return graph      # the backend's own check says what is missing
    return graph._replace(**{field: row_tiles(src)})


def kernel_table(graph, field: str) -> jax.Array:
    """``graph``'s row-tiled ``field``; raises when the graph was not
    given it by :func:`with_kernel_tables`."""
    table = getattr(graph, field, None)
    if table is None:
        raise ValueError(
            f"the graph carries no {field}: the Pallas backends read a "
            f"row-tiled table that kernels.registry.with_kernel_tables("
            f"graph, backend) lays out once, outside the jitted search "
            f"(AnnIndex searchers and AnnEngine do this themselves)")
    return table


def available_backends() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def resolve_backend(cfg) -> Callable:
    """``SearchConfig.dist_backend`` -> DistFn (raises on unknown names)."""
    name = getattr(cfg, "dist_backend", "ref") or "ref"
    try:
        factory = _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown dist_backend {name!r}; available: "
            f"{available_backends()}") from None
    return factory(cfg)


def pad_ids_to_tile(ids: jax.Array, tile: int, n_nodes: int) -> jax.Array:
    """Pad a (..., C) id array along its LAST axis to a multiple of ``tile``
    with the sentinel ``n_nodes`` (>= N ids produce +inf distances in every
    kernel)."""
    c = ids.shape[-1]
    pad = (-c) % tile
    if pad == 0:
        return ids
    return jnp.concatenate(
        [ids, jnp.full(ids.shape[:-1] + (pad,), n_nodes, ids.dtype)],
        axis=-1)


def make_dist_fn(impl: str = "rowgather", *, metric: str = "l2",
                 dma_group: int = 8) -> Callable:
    """Adapter producing a batch-major ``core.bfis.DistFn`` that routes the
    whole batch's (B, M, R) expansion through ONE (B, C) kernel launch
    (C = M·R, padded to the DMA tile for ``impl="dma"``).

    ``metric`` is the index metric tag ("l2" | "ip" | "cosine"); every
    backend serves every metric (cosine = ip on pre-normalized vectors).

    The kernels read ``graph.vector_tiles``, the row-tiled copy of the
    table laid out by :func:`with_kernel_tables`.
    """
    if impl == "ref":
        from repro.core.bfis import make_ref_dist_fn
        return make_ref_dist_fn(metric)

    def dist_fn(graph, active_ids, nbr_ids, queries):
        b, m, r = nbr_ids.shape
        flat = nbr_ids.reshape(b, m * r)
        if impl == "dma":
            flat = pad_ids_to_tile(flat, dma_group, graph.n_nodes)
        d = ops.l2dist(kernel_table(graph, "vector_tiles"), flat, queries,
                       impl=impl, g=dma_group, metric=metric)
        return d[:, :m * r].reshape(b, m, r)
    return dist_fn


def _cfg_metric(cfg) -> str:
    return getattr(cfg, "metric", "l2") or "l2"


@register_backend("ref")
def _ref_backend(cfg):
    # lazy import: core.bfis imports this module for resolution
    from repro.core.bfis import make_ref_dist_fn
    return make_ref_dist_fn(_cfg_metric(cfg))


@register_backend("rowgather", tiles="vector_tiles")
def _rowgather_backend(cfg):
    return make_dist_fn("rowgather", metric=_cfg_metric(cfg))


@register_backend("dma", tiles="vector_tiles")
def _dma_backend(cfg):
    return make_dist_fn("dma", metric=_cfg_metric(cfg),
                        dma_group=int(getattr(cfg, "dma_group", 8)))


# the quantized backends live next to their codec in repro.quant.kernels and
# self-register on import; importing them HERE (not from repro.quant's
# __init__) keeps the quant package importable without this module and this
# module the single place the backend catalogue is assembled
import repro.quant.kernels as _quant_kernels  # noqa: E402,F401
# the batch-dedup backends (dedup_gather / dedup_gather_int8) self-register
# the same way
import repro.kernels.dedup as _dedup_kernels  # noqa: E402,F401
