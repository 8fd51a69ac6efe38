"""Plain reference for filtered top-k search, and the comparison that
decides ``correct``.

The reference is exact brute force under the filter, in float64 NumPy on
the host: evaluate every range on the raw attributes, take the squared L2
distance of every passing row, keep the k nearest. It imports nothing of
the program and takes nothing the program made: it sees only the dataset,
the requests and the answers.

Numbers compared, each against its limit (readings in PERF.md):

* ``filter_violations``: answered ids whose row fails the request's
  ranges, or that repeat within one answer, or lie outside [0, N). Exact: 0.
* ``unanswered``: answer slots left empty (id -1) although at least that
  many rows pass the filter. SQUASH's single-pass guarantee (§2.5) returns k
  whenever k rows pass. Exact: 0.
* ``dist_rel_err``: the widest relative gap between a returned distance
  and the float64 distance of the returned id. Stage 5 refines on the
  full-precision rows, so the gap is float32 rounding on a sound run.
* ``recall``: mean recall@k, |answer ∩ exact top-k| / k. Its floor is the
  configuration's stated guarantee, not a reading.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Sequence

import numpy as np

# The widest relative distance gap a sound float32 run may show. Readings
# and the reason for this value are in PERF.md ("How correct is decided").
DIST_REL_ERR_LIMIT = 1e-4


def range_mask(attributes: np.ndarray, ranges) -> np.ndarray:
    """(N,) bool: rows whose every attribute lies in its inclusive range."""
    mask = np.ones(attributes.shape[0], dtype=bool)
    for attr, lo, hi in ranges:
        col = attributes[:, attr]
        mask &= (col >= lo) & (col <= hi)
    return mask


def exact_topk(vectors: np.ndarray, queries: np.ndarray, mask: np.ndarray,
               k: int):
    """Exact filtered top-k: (ids (Q, k) int64 with -1 padding, dists)."""
    idx = np.flatnonzero(mask)
    qn = queries.shape[0]
    ids = np.full((qn, k), -1, dtype=np.int64)
    dists = np.full((qn, k), np.inf)
    if idx.size == 0:
        return ids, dists
    sub = vectors[idx]
    kk = min(k, idx.size)
    # A float32 pass over ||x||² − 2x·q + ||q||² picks a shortlist of 4k
    # rows, which float64 re-measures directly. The shortlist is exact when
    # its k-th float64 distance² lies below the cut by more than the float32
    # pass can err; otherwise every passing row is measured in float64.
    norms = np.einsum("nd,nd->n", sub, sub)
    d2 = norms[None, :] - 2.0 * (queries @ sub.T) + np.einsum(
        "qd,qd->q", queries, queries)[:, None]
    short = min(4 * k, idx.size)
    cand = np.argpartition(d2, short - 1, axis=1)[:, :short]
    for qi in range(qn):
        q = queries[qi]
        rows = cand[qi]
        dd = exact_dist(sub, rows, q)
        if short < idx.size:
            cut = float(np.partition(d2[qi], short)[short])
            err = 1e-5 * (float(norms.max()) + float(q @ q))
            if float(np.sort(dd)[kk - 1]) ** 2 > cut - err:
                rows = np.arange(idx.size)
                dd = exact_dist(sub, rows, q)
        order = np.argsort(dd, kind="stable")[:kk]
        ids[qi, :kk] = idx[rows[order]]
        dists[qi, :kk] = dd[order]
    return ids, dists


def exact_dist(vectors: np.ndarray, rows: np.ndarray, query: np.ndarray
               ) -> np.ndarray:
    """float64 L2 distances of ``rows`` to one query."""
    diff = vectors[rows].astype(np.float64) - query.astype(np.float64)
    return np.sqrt(np.einsum("nd,nd->n", diff, diff))


@dataclasses.dataclass
class Answer:
    """One served request: its queries, ranges and what came back."""

    queries: np.ndarray   # (Q, d)
    ranges: tuple
    ids: np.ndarray       # (Q, k)
    dists: np.ndarray     # (Q, k)


def compare(vectors: np.ndarray, attributes: np.ndarray,
            answers: Sequence[Answer], k: int, recall_floor: float
            ) -> Dict[str, Dict[str, float]]:
    """Hold answers to the reference; returns {name: {value, limit}}."""
    n = vectors.shape[0]
    violations = unanswered = 0
    worst_rel = 0.0
    hits = slots = 0
    for ans in answers:
        mask = range_mask(attributes, ans.ranges)
        passing = int(mask.sum())
        ref_ids, _ = exact_topk(vectors, ans.queries, mask, k)
        for qi in range(ans.ids.shape[0]):
            got = np.asarray(ans.ids[qi], dtype=np.int64)
            dist = np.asarray(ans.dists[qi], dtype=np.float64)
            present = got >= 0
            ok_range = present & (got < n)
            violations += int((present & ~ok_range).sum())
            rows = got[ok_range]
            violations += int((~mask[rows]).sum())
            violations += rows.size - np.unique(rows).size
            unanswered += max(0, min(k, passing) - int(present.sum()))
            if rows.size:
                exact = exact_dist(vectors, rows, ans.queries[qi])
                gap = np.abs(dist[ok_range] - exact) / np.maximum(exact,
                                                                  1e-30)
                gap = np.where(np.isfinite(gap), gap, np.inf)
                worst_rel = max(worst_rel, float(gap.max()))
            ref = set(ref_ids[qi][ref_ids[qi] >= 0].tolist())
            if ref:
                hits += len(ref & set(rows.tolist()))
                slots += len(ref)
    recall = hits / slots if slots else 0.0
    return {
        "filter_violations": {"value": violations, "limit": 0},
        "unanswered": {"value": unanswered, "limit": 0},
        "dist_rel_err": {"value": worst_rel, "limit": DIST_REL_ERR_LIMIT},
        "recall": {"value": recall, "limit": recall_floor},
    }


def passes(checks: Dict[str, Dict[str, float]]) -> bool:
    """Every number within its limit (recall is a floor, the rest ceilings)."""
    ok = True
    for name, c in checks.items():
        if name.startswith("recall"):
            ok &= c["value"] >= c["limit"]
        else:
            ok &= c["value"] <= c["limit"]
    return bool(ok)


def sample_answers(answers: List[Answer], cap: int, seed: int
                   ) -> List[Answer]:
    """All answers, or ``cap`` of them drawn from the seed."""
    if len(answers) <= cap:
        return list(answers)
    rng = np.random.default_rng([int(seed) % (1 << 63), 3])
    pick = np.sort(rng.choice(len(answers), size=cap, replace=False))
    return [answers[i] for i in pick]
