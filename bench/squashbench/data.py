"""Dataset, request stream and arrivals, all made from the run's seed.

The dataset is a clustered stand-in for a Table 2 corpus (arXiv:2502.01528
§5.1): ``clusters`` Gaussian clusters, each on a ``lid``-dimensional affine
manifold with geometrically decaying energy plus small ambient noise, and A
uniform integer attributes. It draws from the same distribution as the
program's ``data/synthetic.make_vector_dataset`` but is vectorised by
cluster (one matmul per cluster, float32 throughout), so it is not bitwise
equal to it and takes seconds, not tens of seconds, at N = 1M.

Predicates follow §5.1: one range predicate on each attribute, all of one
width, ``round(s^(1/A) · cardinality)`` values, with the lower ends drawn
per request. Nothing here imports the program.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Tuple

import numpy as np

# A predicate set is a tuple of (attribute, lo, hi) ranges, inclusive.
Ranges = Tuple[Tuple[int, int, int], ...]


@dataclasses.dataclass
class Dataset:
    vectors: np.ndarray     # (N, d) float32
    attributes: np.ndarray  # (N, A) int32, uniform over [0, cardinality)
    queries: np.ndarray     # (pool, d) float32, held out from the same mixture
    cardinality: int


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Independent generator for one use of the run's seed."""
    return np.random.default_rng([int(seed) % (1 << 63), stream])


def make_dataset(*, n: int, d: int, clusters: int, lid: int,
                 num_attributes: int, cardinality: int, query_pool: int,
                 seed: int) -> Dataset:
    rng = rng_for(seed, 0)
    total = n + query_pool
    centers = rng.normal(0.0, 10.0, size=(clusters, d)).astype(np.float32)
    bases = (rng.normal(size=(clusters, lid, d)) / np.sqrt(d)).astype(
        np.float32)
    energies = np.geomspace(4.0, 0.5, lid).astype(np.float32)
    which = rng.integers(0, clusters, size=total)
    pts = rng.standard_normal((total, d), dtype=np.float32)
    pts *= np.float32(0.05)                                 # ambient noise
    order = np.argsort(which, kind="stable")
    bounds = np.searchsorted(which[order], np.arange(clusters + 1))
    for c in range(clusters):
        rows = order[bounds[c]:bounds[c + 1]]
        latent = rng.standard_normal((rows.size, lid), dtype=np.float32)
        pts[rows] += centers[c] + (latent * energies) @ bases[c]
    attrs = rng.integers(0, cardinality, size=(n, num_attributes),
                         dtype=np.int32)
    return Dataset(vectors=pts[:n], attributes=attrs, queries=pts[n:],
                   cardinality=cardinality)


def predicate_width(cardinality: int, num_attributes: int,
                    target_selectivity: float) -> int:
    """Values each range covers, as the §5.1 predicates size them."""
    s = target_selectivity ** (1.0 / num_attributes)
    return max(1, int(round(s * cardinality)))


def joint_selectivity(cardinality: int, num_attributes: int,
                      width: int) -> float:
    """Expected share of rows passing every range under uniform attributes."""
    return (width / cardinality) ** num_attributes


@dataclasses.dataclass
class Request:
    index: int
    query_rows: np.ndarray   # rows of the query pool
    ranges: Ranges


class RequestStream:
    """The requests of a traffic mix, in order, from the seed.

    Queries cycle through a seed-permuted pool; each request draws its own
    predicate set. ``stream[i]`` is the same for the same seed, however far
    a run reads.
    """

    BLOCK = 4096

    def __init__(self, traffic: dict, ds: Dataset, seed: int,
                 stream: int = 1):
        self._rng = rng_for(seed, stream)
        self.q = int(traffic["queries_per_request"])
        self.a = ds.attributes.shape[1]
        self.width = predicate_width(ds.cardinality, self.a,
                                     float(traffic["target_selectivity"]))
        self._high = ds.cardinality - self.width + 1
        self._pool = ds.queries.shape[0]
        self._perm = self._rng.permutation(self._pool)
        self._lows = np.empty((0, self.a), np.int64)

    def __getitem__(self, i: int) -> Request:
        while i >= self._lows.shape[0]:
            more = self._rng.integers(0, self._high, size=(self.BLOCK, self.a))
            self._lows = np.concatenate([self._lows, more])
        rows = self._perm[(i * self.q + np.arange(self.q)) % self._pool]
        ranges = tuple((j, int(lo), int(lo) + self.width - 1)
                       for j, lo in enumerate(self._lows[i]))
        return Request(index=i, query_rows=rows, ranges=ranges)


def arrival_offsets(rate_per_s: float, seconds: float, seed: int
                    ) -> np.ndarray:
    """Due times (s from the window's start) of a Poisson open loop.

    Every seed gets the same set of inter-arrival gaps, the exponential
    quantiles at (i + 1/2)/m, in its own order: the load is fixed and only
    its order varies, so runs of different seeds differ by no more work.
    """
    m = max(1, math.ceil(rate_per_s * seconds))
    u = (np.arange(m) + 0.5) / m
    gaps = -np.log1p(-u) / rate_per_s
    gaps = gaps[rng_for(seed, 2).permutation(m)]
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return due[due < seconds]
