"""Coarse partitioning + filtered partition ranking/selection (paper §2.4.1–2.4.2).

Balanced (capacity-constrained) k-means yields computationally balanced
partitions for the resource-constrained workers; Eq. 1 derives the centroid
distance-ratio threshold T; Algorithm 1 selects, per query, the minimal
partition set that (a) covers every centroid within factor T of the nearest
and (b) contains ≥ k predicate-passing vectors — guaranteeing a single
distributed pass.
"""

from __future__ import annotations

import concurrent.futures
import dataclasses
import os
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.obs.metrics import REGISTRY as _METRICS

__all__ = [
    "balanced_kmeans",
    "compute_threshold",
    "select_partitions",
    "Partitioning",
]


@dataclasses.dataclass
class Partitioning:
    centroids: np.ndarray   # (P, d)
    assign: np.ndarray      # (N,) partition id per vector
    threshold: float        # T from Eq. 1

    @property
    def num_partitions(self) -> int:
        return int(self.centroids.shape[0])

    def residency_bitmap(self) -> np.ndarray:
        """Compact P_V map: (P, N) bool — vector residency per partition.

        Rows with out-of-range assignment (the ``assign == P`` sentinel a
        live-index compaction leaves on physically removed vectors) reside
        nowhere.
        """
        p = self.num_partitions
        n = self.assign.shape[0]
        pv = np.zeros((p, n), dtype=bool)
        resident = self.assign < p
        pv[self.assign[resident], np.arange(n)[resident]] = True
        return pv


def balanced_kmeans(
    x: np.ndarray,
    num_partitions: int,
    iters: int = 15,
    seed: int = 0,
    slack: float = 1.05,
) -> Tuple[np.ndarray, np.ndarray]:
    """Capacity-constrained Lloyd iterations (paper's 'constrained clustering').

    Each iteration assigns vectors greedily in order of *assignment margin*
    (gap between best and second-best centroid), respecting a per-partition
    capacity of ``slack * ceil(N/P)``. Returns (centroids, assign).
    """
    x = np.asarray(x, dtype=np.float64)
    n, d = x.shape
    p = num_partitions
    rng = np.random.default_rng(seed)
    cent = x[rng.choice(n, size=p, replace=False)].copy()
    cap = int(np.ceil(slack * n / p))
    assign = np.zeros(n, dtype=np.int64)
    for _ in range(iters):
        d2 = ((x[:, None, :] - cent[None, :, :]) ** 2).sum(-1) if n * p * d < 5e7 \
            else _chunked_sqdist(x, cent)
        order = np.argsort(np.partition(d2, 1, axis=1)[:, 0] - np.partition(d2, 1, axis=1)[:, 1])
        pref = np.argsort(d2, axis=1)
        _greedy_assign(pref[order], order, cap, assign)

        def centre(c: int) -> np.ndarray:
            members = x[assign == c]
            return members.mean(axis=0) if members.shape[0] else cent[c]

        # Each mean reads only its own rows: they run side by side.
        with concurrent.futures.ThreadPoolExecutor(
                max(1, min(p, len(os.sched_getaffinity(0))))) as pool:
            cent = np.stack(list(pool.map(centre, range(p))))
    return cent, assign


def _greedy_assign(pref: np.ndarray, rows: np.ndarray, cap: int,
                   assign: np.ndarray) -> None:
    """Give each row, in turn, its most preferred centroid with room left.

    ``pref`` (m, p) lists each row's centroids best first, rows in the
    order they choose; ``assign`` is written in place at ``rows``. Between
    two moments a centroid fills, every row's choice is its first centroid
    not yet full, so the turns run in at most p + 1 vectorised passes, one
    per fill, instead of one Python step per row. A row that finds every
    centroid full keeps its old assignment.
    """
    m, p = pref.shape
    counts = np.zeros(p, dtype=np.int64)
    full = np.zeros(p, dtype=bool)
    pos = 0
    while pos < m and not full.all():
        sub = pref[pos:]
        choice = sub[np.arange(sub.shape[0]), np.argmax(~full[sub], axis=1)]
        # Rank of each row among the rows before it that chose the same.
        onehot = choice[:, None] == np.arange(p)[None, :]
        taken = counts[choice] + np.cumsum(onehot, axis=0)[
            np.arange(sub.shape[0]), choice]
        fills = np.flatnonzero(taken == cap)
        stop = sub.shape[0] if fills.size == 0 else int(fills[0]) + 1
        assign[rows[pos:pos + stop]] = choice[:stop]
        counts += np.bincount(choice[:stop], minlength=p)
        if fills.size:
            full[choice[fills[0]]] = True
        pos += stop


def _chunked_sqdist(x: np.ndarray, cent: np.ndarray, chunk: int = 8192) -> np.ndarray:
    n = x.shape[0]
    out = np.empty((n, cent.shape[0]), dtype=np.float64)
    c2 = (cent ** 2).sum(-1)
    ct = np.ascontiguousarray(cent.T)    # a transposed view runs off BLAS
    for lo in range(0, n, chunk):
        hi = min(lo + chunk, n)
        xx = x[lo:hi]
        out[lo:hi] = (np.einsum("nd,nd->n", xx, xx)[:, None]
                      - 2 * (xx @ ct) + c2[None, :])
    return np.maximum(out, 0.0)


def compute_threshold(
    x: np.ndarray,
    centroids: np.ndarray,
    assign: np.ndarray,
    beta: float = 0.001,
    sample: Optional[int] = 20000,
    seed: int = 0,
) -> float:
    """Centroid distance-ratio threshold T (Eq. 1).

    Builds the vector↔centroid distance-ratio matrix R (each row divided by
    the home-centroid distance), takes row-wise means/stds, then
    T = 1 + σ_µ/µ_µ + β·√d over *their* means.
    """
    x = np.asarray(x, dtype=np.float64)
    n, d = x.shape
    if sample is not None and n > sample:
        idx = np.random.default_rng(seed).choice(n, size=sample, replace=False)
        x, assign = x[idx], assign[idx]
        n = sample
    dist = np.sqrt(_chunked_sqdist(x, centroids))
    home = dist[np.arange(n), assign]
    ratio = dist / np.maximum(home[:, None], 1e-12)
    mu_r = ratio.mean(axis=1)
    sigma_r = ratio.std(axis=1)
    mu_mu = float(mu_r.mean())
    sigma_mu = float(sigma_r.mean())
    return 1.0 + sigma_mu / max(mu_mu, 1e-12) + beta * np.sqrt(d)


def select_partitions(
    queries: np.ndarray,
    centroids: np.ndarray,
    filter_masks: np.ndarray,
    members: Sequence[np.ndarray],
    threshold: float,
    k: int,
    balance: bool = False,
    escalations: Optional[list] = None,
) -> Tuple[np.ndarray, List[Dict[int, np.ndarray]]]:
    """Algorithm 1 — Filtered Partition Ranking and Selection.

    Args:
      queries: (Q, d).
      centroids: (P, d).
      filter_masks: attribute satisfaction mask F over the N global ids:
        (N,) bool, one filter the whole batch shares, or (Q, N) bool, one
        per query.
      members: P ascending int arrays of global ids, each partition's rows
        in its local order (``PartitionIndex.vector_ids``, the P_V map): a
        row's local position is its index in ``members[pid]``.
      threshold: T (multiplicative factor over the nearest centroid distance).
      k: top-k target.
      balance: optional batch load-balancing step (assign extra queries to
        under-visited partitions, narrowest-miss first).
      escalations: optional one-element list; incremented by the number of
        (query, partition) visits *past* the Eq. 1 threshold cut — the §2.5
        filter-count guarantee at work (counted here, where the cut decision
        is made, so callers can't drift from it).

    Returns:
      visit: (Q, P) bool — partitions each query must be issued to.
      cands: per-query dict partition → local candidate row indices (into the
        partition's local vector order), ascending. Every visited partition
        carries a non-empty candidate bitmap, so per-partition processors
        prune all non-passing vectors (single-pass guarantee).

    A visit reads only its partition's member rows, and each (filter,
    partition) pair is scanned at most once a call: with a shared (N,)
    filter every query reuses the rows of a partition already scanned.
    Counters ``planner.alg1.visits`` and ``planner.alg1.row_scans`` record
    the visits taken and the scans made.
    """
    queries = np.asarray(queries, dtype=np.float64)
    qn, d = queries.shape
    p = centroids.shape[0]
    shared = filter_masks.ndim == 1
    scanned: Dict[Tuple[int, int], np.ndarray] = {}
    row_scans = _METRICS.counter("planner.alg1.row_scans")

    def passing(qi: int, pid: int) -> np.ndarray:
        """Local positions of ``pid``'s rows that pass query ``qi``'s filter."""
        key = (0 if shared else qi, pid)
        rows = scanned.get(key)
        if rows is None:
            mask = filter_masks if shared else filter_masks[qi]
            rows = scanned[key] = np.flatnonzero(mask[members[pid]])
            row_scans.inc()
        return rows

    dists = np.sqrt(_chunked_sqdist(queries, centroids))
    visit = np.zeros((qn, p), dtype=bool)
    cands: List[Dict[int, np.ndarray]] = []
    near_miss: List[Tuple[float, int, int]] = []  # (margin, q, partition)
    escalated = 0
    for qi in range(qn):
        cand_total = 0
        per_part: Dict[int, np.ndarray] = {}
        ranked = np.argsort(dists[qi])
        dmin = dists[qi, ranked[0]]
        for rank, pid in enumerate(ranked):
            past_cut = dists[qi, pid] > threshold * max(dmin, 1e-12)
            if past_cut and cand_total >= k:
                near_miss.append((dists[qi, pid] / max(dmin, 1e-12), qi, pid))
                break
            rows = passing(qi, pid)
            if rows.size:
                visit[qi, pid] = True
                per_part[pid] = rows
                cand_total += rows.size
                if past_cut:
                    escalated += 1
        cands.append(per_part)
    if escalations is not None:
        escalations[0] += escalated
    if balance:
        visits_per_part = visit.sum(axis=0)
        target = max(1, int(np.ceil(visit.sum() / p)))
        near_miss.sort()
        for margin, qi, pid in near_miss:
            if visits_per_part[pid] < target and not visit[qi, pid]:
                rows = passing(qi, pid)
                if rows.size:
                    visit[qi, pid] = True
                    cands[qi][pid] = rows
                    visits_per_part[pid] += 1
    _METRICS.counter("planner.alg1.visits").inc(int(visit.sum()))
    return visit, cands
