"""Algorithm 1 over each partition's member ids.

``select_partitions`` reads a visit's rows from ``members[pid]`` (the
partition's ascending global ids) and scans each (filter, partition) pair
at most once a call. These tests pin it bit for bit against the per-visit
full-scan formulation it replaced (kept here as ``_full_scan``), pin the
member-order invariant it reads on a ``LiveIndex``, and pin the
``planner.alg1.*`` counters that show the reuse.
"""

from typing import Dict, List, Set, Tuple

import numpy as np
import pytest

from repro.core import partitions
from repro.core.live import LiveIndex
from repro.core.pipeline import SquashConfig, SquashIndex
from repro.data import synthetic
from repro.obs.metrics import REGISTRY

K = 10


def _full_scan(queries, centroids, filter_masks, assign, threshold, k,
               balance=False, escalations=None, scanned=None):
    """Algorithm 1 as it was: a stable sort of ``assign`` for local
    positions, then a scan of all N rows on every visit. ``scanned``
    collects the (query, partition) pairs it scanned."""
    queries = np.asarray(queries, dtype=np.float64)
    qn = queries.shape[0]
    p = centroids.shape[0]
    n = assign.shape[0]
    order = np.argsort(assign, kind="stable")
    local_pos = np.empty(n, dtype=np.int64)
    counts = np.bincount(assign, minlength=p)
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    local_pos[order] = np.arange(n) - np.repeat(starts, counts)
    scanned = set() if scanned is None else scanned

    def rows_of(qi, pid):
        scanned.add((qi, int(pid)))
        return np.where(filter_masks[qi] & (assign == pid))[0]

    dists = np.sqrt(((queries[:, None, :] - centroids[None]) ** 2).sum(-1))
    visit = np.zeros((qn, p), dtype=bool)
    cands: List[Dict[int, np.ndarray]] = []
    near_miss: List[Tuple[float, int, int]] = []
    escalated = 0
    for qi in range(qn):
        cand_total = 0
        per_part: Dict[int, np.ndarray] = {}
        ranked = np.argsort(dists[qi])
        dmin = dists[qi, ranked[0]]
        for pid in ranked:
            past_cut = dists[qi, pid] > threshold * max(dmin, 1e-12)
            if past_cut and cand_total >= k:
                near_miss.append((dists[qi, pid] / max(dmin, 1e-12), qi, pid))
                break
            rows = rows_of(qi, pid)
            if rows.size:
                visit[qi, pid] = True
                per_part[pid] = local_pos[rows]
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
        for _, qi, pid in near_miss:
            if visits_per_part[pid] < target and not visit[qi, pid]:
                rows = rows_of(qi, pid)
                if rows.size:
                    visit[qi, pid] = True
                    cands[qi][pid] = local_pos[rows]
                    visits_per_part[pid] += 1
    return visit, cands


def _members(assign: np.ndarray, p: int) -> List[np.ndarray]:
    return [np.flatnonzero(assign == pid) for pid in range(p)]


def _clusters(seed: int, n: int = 3000, p: int = 6, qn: int = 12):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, 8))
    cent, assign = partitions.balanced_kmeans(x, p, iters=4, seed=seed)
    queries = rng.normal(size=(qn, 8))
    return rng, cent, assign, queries


def _masks(rng, kind: str, qn: int, n: int) -> np.ndarray:
    """A filter of ``kind``: one the batch shares ((N,)), one per query
    ((Q, N)), one nothing passes, or one so selective that reaching k
    candidates takes visits past the Eq. 1 cut."""
    if kind == "shared":
        return rng.random(n) < 0.3
    if kind == "per_query":
        return rng.random((qn, n)) < 0.3
    if kind == "empty":
        return np.zeros(n, dtype=bool)
    assert kind == "escalating"
    return rng.random(n) < 0.004


def _as_rows(mask: np.ndarray, qn: int) -> np.ndarray:
    return mask if mask.ndim == 2 else np.broadcast_to(mask, (qn, mask.size))


def _assert_same(got, want) -> None:
    visit, cands = got
    visit_ref, cands_ref = want
    np.testing.assert_array_equal(visit, visit_ref)
    assert len(cands) == len(cands_ref)
    for per, per_ref in zip(cands, cands_ref):
        assert sorted(per) == sorted(per_ref)
        for pid, rows in per.items():
            assert rows.dtype == np.int64 == per_ref[pid].dtype
            np.testing.assert_array_equal(rows, per_ref[pid])
            assert np.all(np.diff(rows) > 0)


@pytest.mark.parametrize("balance", [False, True], ids=["plain", "balance"])
@pytest.mark.parametrize("kind", ["shared", "per_query", "empty",
                                  "escalating"])
def test_matches_the_full_scan(kind, balance):
    rng, cent, assign, queries = _clusters(seed=21)
    qn, n, p = queries.shape[0], assign.size, cent.shape[0]
    f = _masks(rng, kind, qn, n)
    esc, esc_ref = [0], [0]
    got = partitions.select_partitions(queries, cent, f, _members(assign, p),
                                       1.05, K, balance=balance,
                                       escalations=esc)
    want = _full_scan(queries, cent, _as_rows(f, qn), assign, 1.05, K,
                      balance=balance, escalations=esc_ref)
    _assert_same(got, want)
    assert esc == esc_ref
    if kind == "empty":
        assert not got[0].any()
    if kind == "escalating":
        assert esc[0] > 0


def test_balance_adds_visits_in_these_cases():
    """The balance cases above do exercise the near-miss scans."""
    rng, cent, assign, queries = _clusters(seed=21)
    f = _masks(rng, "shared", queries.shape[0], assign.size)
    members = _members(assign, cent.shape[0])
    plain, _ = partitions.select_partitions(queries, cent, f, members, 1.05,
                                            K)
    balanced, _ = partitions.select_partitions(queries, cent, f, members,
                                               1.05, K, balance=True)
    assert balanced.sum() > plain.sum()


# ------------------------------------------------------------ live index

STAGES = ("build", "insert", "delete", "compact")


def _live_at(stage: str):
    """A ``LiveIndex`` taken through ``STAGES`` up to ``stage``, and the
    queries of its dataset."""
    ds = synthetic.make_vector_dataset("sift1m", scale=0.002, num_queries=8,
                                       seed=5)
    cfg = SquashConfig(num_partitions=5, kmeans_iters=4, lloyd_iters=6)
    index = SquashIndex.build(ds.vectors, ds.attributes, cfg, seed=5)
    live = LiveIndex(index)
    rng = np.random.default_rng(5)
    upto = STAGES.index(stage)
    if upto >= 1:
        x = ds.vectors[:40]
        live.insert(x + rng.normal(0, 0.5, x.shape), ds.attributes[:40])
    if upto >= 2:
        n = index.partitioning.assign.size
        live.delete(rng.choice(n, size=n // 5, replace=False))
    if upto >= 3:
        for pid in range(len(index.parts)):
            live.compact(pid, requantize=pid % 2 == 0)
        assert (index.partitioning.assign == live.sentinel).any()
    return ds, index


@pytest.mark.parametrize("stage", STAGES)
def test_members_ascend_and_match_assign(stage):
    _, index = _live_at(stage)
    assign = index.partitioning.assign
    for pid, part in enumerate(index.parts):
        assert np.all(np.diff(part.vector_ids) > 0)
        np.testing.assert_array_equal(part.vector_ids,
                                      np.flatnonzero(assign == pid))


@pytest.mark.parametrize("stage", STAGES[1:])
def test_live_index_matches_the_full_scan(stage):
    ds, index = _live_at(stage)
    pg = index.partitioning
    rng = np.random.default_rng(6)
    f = (rng.random(pg.assign.size) < 0.3) & index.live_mask
    qn = ds.queries.shape[0]
    esc, esc_ref = [0], [0]
    got = partitions.select_partitions(
        ds.queries, pg.centroids, f, [pt.vector_ids for pt in index.parts],
        pg.threshold, K, balance=True, escalations=esc)
    want = _full_scan(ds.queries, pg.centroids, _as_rows(f, qn), pg.assign,
                      pg.threshold, K, balance=True, escalations=esc_ref)
    _assert_same(got, want)
    assert esc == esc_ref


# -------------------------------------------------------------- counters

def _counted(f, members, cent, queries, balance):
    REGISTRY.reset()
    REGISTRY.enable()
    try:
        visit, _ = partitions.select_partitions(queries, cent, f, members,
                                                1.05, K, balance=balance)
        counters = REGISTRY.snapshot()["counters"]
    finally:
        REGISTRY.disable()
        REGISTRY.reset()
    return visit, counters


@pytest.mark.parametrize("balance", [False, True], ids=["plain", "balance"])
def test_shared_filter_scans_each_partition_once(balance):
    rng, cent, assign, queries = _clusters(seed=21)
    f = _masks(rng, "shared", queries.shape[0], assign.size)
    visit, counters = _counted(f, _members(assign, cent.shape[0]), cent,
                               queries, balance)
    scanned: Set[Tuple[int, int]] = set()
    _full_scan(queries, cent, _as_rows(f, queries.shape[0]), assign, 1.05, K,
               balance=balance, scanned=scanned)
    assert counters["planner.alg1.visits"] == visit.sum()
    assert counters["planner.alg1.row_scans"] == len({p for _, p in scanned})
    assert counters["planner.alg1.row_scans"] < visit.sum()


def test_per_query_filters_scan_every_visit():
    rng, cent, assign, queries = _clusters(seed=21)
    f = _masks(rng, "per_query", queries.shape[0], assign.size)
    visit, counters = _counted(f, _members(assign, cent.shape[0]), cent,
                               queries, balance=True)
    assert counters["planner.alg1.row_scans"] == visit.sum()
    assert counters["planner.alg1.visits"] == visit.sum()


def test_counters_record_nothing_while_off():
    rng, cent, assign, queries = _clusters(seed=21)
    f = _masks(rng, "shared", queries.shape[0], assign.size)
    REGISTRY.reset()
    assert not REGISTRY.enabled
    partitions.select_partitions(queries, cent, f,
                                 _members(assign, cent.shape[0]), 1.05, K)
    assert REGISTRY.snapshot()["counters"] == {}
