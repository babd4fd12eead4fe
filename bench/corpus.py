"""The benchmark's data: the corpus of a configuration, fresh points drawn
from ``--seed``, and which of them each request carries.

A configuration's ``data`` names its generator, ``bench/generators/<name>.py``,
and gives its parameters, among them the data seed: the corpus is fixed in
the configuration, as a deployment's dataset is fixed.  ``--seed`` only draws
what varies between runs: the queries and the warm-up points.
"""
from __future__ import annotations

from typing import Callable, NamedTuple

import numpy as np

from bench import byname

BLOCK = 4096        # points (or requests) per generated block

# stream tags: each use of --seed draws from its own independent stream
QUERIES, WARMUP, PLAN = 1, 3, 5


class Corpus(NamedTuple):
    base: np.ndarray     # (n, dim) float32, what the index is built from
    state: object        # what the generator needs to draw fresh points
    fresh: Callable      # fresh(state, rng, count) -> (count, dim) float32


def config_corpus(cfg: dict) -> Corpus:
    params = dict(cfg["data"])
    gen = byname.load("generators", params.pop("generator"))
    base, state = gen.corpus(cfg["n"], cfg["dim"], **params)
    return Corpus(base, state, gen.fresh)


class Stream:
    """Fresh points from the corpus's generator, never repeating.

    Point ``j`` of a stream depends only on (seed, tag, j): block ``j // BLOCK``
    is drawn from its own generator, so how the points are taken (one by one
    or in batches) does not change them.
    """

    def __init__(self, data: Corpus, seed: int, tag: int):
        if seed < 0:
            raise ValueError(f"seed must be >= 0, got {seed}")
        self.data = data
        self.seed = int(seed)
        self.tag = tag
        self._blocks: dict[int, np.ndarray] = {}
        self.pos = 0

    def _block(self, i: int) -> np.ndarray:
        blk = self._blocks.get(i)
        if blk is None:
            rng = np.random.default_rng([self.tag, self.seed, i])
            blk = self.data.fresh(self.data.state, rng, BLOCK)
            self._blocks[i] = blk
        return blk

    def prefetch(self, count: int) -> None:
        """Generate the blocks that hold points ``pos .. pos + count - 1``
        now, so that taking them later costs no generation."""
        for i in range(self.pos // BLOCK, (self.pos + count - 1) // BLOCK + 1):
            self._block(i)

    def point(self, j: int) -> np.ndarray:
        return self._block(j // BLOCK)[j % BLOCK]

    def take(self, count: int) -> np.ndarray:
        """The next ``count`` points, (count, dim) float32."""
        rows = [self.point(j) for j in range(self.pos, self.pos + count)]
        self.pos += count
        return np.stack(rows) if rows else np.zeros(
            (0, self.data.base.shape[1]), np.float32)

    def rows(self, idx) -> np.ndarray:
        """Points at the given stream positions (already taken or not)."""
        return np.stack([self.point(int(j)) for j in idx])


class QueryPlan:
    """Which stream point request ``i`` carries, from a traffic mix's
    ``"queries"`` entry:

    * absent, or ``{"repeat": "none"}``: point ``i``, so no query is ever
      sent twice;
    * ``{"repeat": "zipf", "distinct": P, "s": s}``: one of the stream's
      first ``P`` points, drawn from the seed with probability proportional
      to ``rank ** -s``, the ranks shuffled over the points by the seed.

    Request ``i``'s point depends only on (seed, i).
    """

    def __init__(self, spec: dict | None, seed: int):
        spec = dict(spec or {"repeat": "none"})
        self.kind = spec.pop("repeat")
        self.seed = int(seed)
        self._blocks: dict[int, np.ndarray] = {}
        if self.kind == "none":
            if spec:
                raise ValueError(f"unknown query plan keys {sorted(spec)}")
            return
        if self.kind != "zipf":
            raise ValueError(f"unknown query repeat {self.kind!r}")
        self.distinct = int(spec.pop("distinct"))
        s = float(spec.pop("s"))
        if spec:
            raise ValueError(f"unknown query plan keys {sorted(spec)}")
        weights = np.arange(1, self.distinct + 1, dtype=np.float64) ** -s
        self._cdf = np.cumsum(weights) / weights.sum()
        self._points = np.random.default_rng(
            [PLAN, self.seed, 0]).permutation(self.distinct)

    def _block(self, b: int) -> np.ndarray:
        blk = self._blocks.get(b)
        if blk is None:
            rng = np.random.default_rng([PLAN, self.seed, 1, b])
            ranks = np.searchsorted(self._cdf, rng.random(BLOCK), side="right")
            blk = self._points[np.minimum(ranks, self.distinct - 1)]
            self._blocks[b] = blk
        return blk

    def points_needed(self, requests: int) -> int:
        """How many stream points the first ``requests`` requests use."""
        return requests if self.kind == "none" else self.distinct

    def prefetch(self, requests: int) -> None:
        if self.kind != "none":
            for b in range((requests - 1) // BLOCK + 1):
                self._block(b)

    def index(self, i: int) -> int:
        if self.kind == "none":
            return i
        return int(self._block(i // BLOCK)[i % BLOCK])

    def indices(self, idx) -> np.ndarray:
        return np.asarray([self.index(int(i)) for i in idx], np.int64)
