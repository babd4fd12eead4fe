"""How ``correct`` is decided: what the timed path answered, against the plain
reference (``bench.reference``) on the same corpus.

The numbers compared, each against its own limit:

* ``unanswered`` — requests that raised or never came back; limit 0.
* ``bad_rows`` — answers with an id outside the corpus, an id twice, a
  non-finite distance or distances out of ascending order; limit 0.
* ``dist_gap`` — the widest gap between a distance the program reported and
  the float64 distance, under the configuration's metric, from the query to
  the id it reported, relative to the size of that distance or, where it is
  smaller, to the median size of the reference's k-th neighbour distance.
  This is what a lower-precision distance path fails.
* ``recall_at_10`` — mean recall@k of every answer against the reference's
  exact top k; limit: the configuration's ``recall_target``.  This is what a
  traversal that does not search fails.

A traffic kind may add numbers of its own, each with its limit
(``verdict``'s ``extra``).

``DIST_GAP_LIMIT`` is set from chip readings of the program and of the
control (see PERF.md, "How correct is decided").
"""
from __future__ import annotations

import numpy as np

from bench import reference

DIST_GAP_LIMIT = 2e-5


def bad_rows(ids: np.ndarray, dists: np.ndarray, n: int) -> np.ndarray:
    """(Q,) bool: the answer row is malformed."""
    out_of_range = ((ids < 0) | (ids >= n)).any(axis=1)
    s = np.sort(ids, axis=1)
    twice = (s[:, 1:] == s[:, :-1]).any(axis=1)
    nonfinite = ~np.isfinite(dists).all(axis=1)
    unordered = (np.diff(dists, axis=1) < 0).any(axis=1)
    return out_of_range | twice | nonfinite | unordered


def readings(base: np.ndarray, queries: np.ndarray, ids: np.ndarray,
             dists: np.ndarray, k: int, metric: str) -> dict:
    """The compared numbers for answers (ids, dists) to ``queries`` over
    ``base`` under ``metric``, plus per-query recall for the end-to-end
    metric."""
    ref_ids, _ = reference.knn(base, queries, k, metric=metric)
    hit = (ids[:, :, None] == ref_ids[:, None, :]).any(axis=2)
    recall = hit.sum(axis=1) / k
    true = reference.exact_dists(base, queries, ids, metric)
    kth = reference.exact_dists(base, queries, ref_ids[:, -1:], metric)[:, 0]
    scale = float(np.median(np.abs(kth)))
    rel = np.abs(dists.astype(np.float64) - true) \
        / np.maximum(np.abs(true), scale)
    rel = np.where(np.isnan(true), np.inf, rel)
    return {
        "bad_rows": int(bad_rows(ids, dists, base.shape[0]).sum()),
        "dist_gap": float(np.max(rel)) if rel.size else 0.0,
        "recall_per_query": recall,
    }


def verdict(values: dict, recall_target: float,
            extra: dict | None = None) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit", "op"}}) for the numbers present;
    ``extra`` maps a further number's name to its (op, limit)."""
    limits = {
        "unanswered": ("<=", 0),
        "bad_rows": ("<=", 0),
        "dist_gap": ("<=", DIST_GAP_LIMIT),
        "recall_at_10": (">=", recall_target),
        **(extra or {}),
    }
    out, ok = {}, True
    for name, (op, limit) in limits.items():
        if name not in values:
            continue
        v = values[name]
        passed = v <= limit if op == "<=" else v >= limit
        ok = ok and bool(passed)
        out[name] = {"value": v, "op": op, "limit": limit}
    return ok, out
