"""Clustered Gaussians: ``n_clusters`` centres drawn N(0, center_scale^2) per
coordinate, each point a random centre plus unit noise.

A copy of the program's generator (``repro.data.vectors.make_vector_dataset``),
kept here so that the yardstick does not move with the program: for one data
seed both give the same base vectors, bit for bit.  Fresh points (queries)
are drawn the same way around the same centres.
"""
from __future__ import annotations

import numpy as np


def corpus(n: int, dim: int, *, n_clusters: int, center_scale: float,
           seed: int) -> tuple[np.ndarray, np.ndarray]:
    """(base (n, dim) float32, centres (n_clusters, dim) float32)."""
    rng = np.random.RandomState(seed)
    centers = rng.normal(size=(n_clusters, dim)).astype(np.float32)
    centers = centers * np.float32(center_scale)
    assign = rng.randint(0, n_clusters, size=n)
    base = centers[assign] + rng.normal(size=(n, dim)).astype(np.float32)
    return base.astype(np.float32), centers


def fresh(centers: np.ndarray, rng: np.random.Generator,
          count: int) -> np.ndarray:
    """``count`` new points near the centres, (count, dim) float32."""
    assign = rng.integers(0, centers.shape[0], size=count)
    noise = rng.standard_normal((count, centers.shape[1]), np.float32)
    return (centers[assign] + noise).astype(np.float32)
