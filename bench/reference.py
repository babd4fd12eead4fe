"""The plain reference: brute-force k nearest neighbours under the
configuration's metric.

Independent of the program (it imports nothing of ``repro``).  The search runs
on the device in blocks of queries with float32 matmuls at
``Precision.HIGHEST``; the distances that answers are judged by are worked
out again on the host in float64 from the definition:

* ``l2``: squared L2, sum((q - x) ** 2);
* ``ip``: the negative inner product, -q . x (smaller is nearer);
* ``cosine``: the negative inner product of the unit-normalized q and x.

Any other metric is refused (``check_metric``).

``precision`` also gives the controls: ``"high"`` is ``Precision.HIGH``, the
TPU's three-pass bfloat16 product (on a CPU it is float32, so it controls
nothing there), and ``"bf16"`` a single pass on bfloat16 inputs.  Both
accumulate in float32.
"""
from __future__ import annotations

import functools

import numpy as np

QUERY_BLOCK = 2048
HOST_BLOCK = 4096
METRICS = ("l2", "ip", "cosine")


def check_metric(metric: str) -> None:
    if metric not in METRICS:
        raise ValueError(f"the reference has no metric {metric!r}; "
                         f"it knows {METRICS}")


def _unit(x: np.ndarray) -> np.ndarray:
    x = np.asarray(x, np.float64)
    return x / np.maximum(np.linalg.norm(x, axis=-1, keepdims=True), 1e-30)


def _dot(a, b, precision: str):
    """a (Q, d) @ b (N, d).T in float32 at the given precision."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    if precision in ("highest", "high"):
        p = {"highest": jax.lax.Precision.HIGHEST,
             "high": jax.lax.Precision.HIGH}[precision]
        return jnp.matmul(a, b.T, precision=p)
    if precision == "bf16":
        return jnp.matmul(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16).T,
                          preferred_element_type=f32)
    raise ValueError(f"unknown precision {precision!r}")


@functools.lru_cache(maxsize=None)
def _knn_fn(k: int, precision: str, metric: str):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def knn_block(base, base_sq, q):
        dots = _dot(q, base, precision)
        if metric == "l2":
            q_sq = jnp.sum(q * q, axis=1, keepdims=True)
            d = q_sq - 2.0 * dots + base_sq[None, :]
        else:
            d = -dots
        neg, ids = jax.lax.top_k(-d, k)
        return ids.astype(jnp.int32), -neg

    return knn_block


def knn(base: np.ndarray, queries: np.ndarray, k: int,
        precision: str = "highest",
        metric: str = "l2") -> tuple[np.ndarray, np.ndarray]:
    """Exact top-k: (ids (Q, k) int32, dists (Q, k) float32), nearest first.
    The distances are worked out at ``precision`` (for l2 in the expanded
    form)."""
    import jax.numpy as jnp
    check_metric(metric)
    if metric == "cosine":
        base = _unit(base).astype(np.float32)
        queries = _unit(queries).astype(np.float32)
    fn = _knn_fn(k, precision, metric)
    b = jnp.asarray(base, jnp.float32)
    b_sq = jnp.sum(b * b, axis=1)
    ids, dists = [], []
    for s in range(0, queries.shape[0], QUERY_BLOCK):
        q = queries[s:s + QUERY_BLOCK]
        pad = QUERY_BLOCK - q.shape[0]
        qp = np.concatenate([q, np.zeros((pad, q.shape[1]), q.dtype)]) \
            if pad else q
        i, d = fn(b, b_sq, jnp.asarray(qp, jnp.float32))
        ids.append(np.asarray(i)[:q.shape[0]])
        dists.append(np.asarray(d)[:q.shape[0]])
    del b, b_sq
    return np.concatenate(ids), np.concatenate(dists)


def exact_dists(base: np.ndarray, queries: np.ndarray, ids: np.ndarray,
                metric: str = "l2") -> np.ndarray:
    """float64 distance from each query to each of its ids, (Q, k); NaN
    where an id is not a row of ``base``."""
    check_metric(metric)
    n = base.shape[0]
    out = np.full(ids.shape, np.nan)
    for s in range(0, ids.shape[0], HOST_BLOCK):
        i = ids[s:s + HOST_BLOCK]
        ok = (i >= 0) & (i < n)
        rows = base[np.where(ok, i, 0)].astype(np.float64)
        q = queries[s:s + HOST_BLOCK].astype(np.float64)[:, None, :]
        if metric == "l2":
            d = np.sum((rows - q) ** 2, axis=-1)
        else:
            if metric == "cosine":
                rows, q = _unit(rows), _unit(q)
            d = -np.sum(rows * q, axis=-1)
        out[s:s + HOST_BLOCK] = np.where(ok, d, np.nan)
    return out
