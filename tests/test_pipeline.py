"""End-to-end SQUASH pipeline tests — the paper's recall claims (§5)."""

import numpy as np
import pytest

from repro.core.attributes import Predicate
from repro.core.pipeline import SquashConfig, SquashIndex
from repro.data import synthetic


@pytest.fixture(scope="module")
def sift_small():
    ds = synthetic.make_vector_dataset("sift1m", scale=0.01, num_queries=40, seed=0)
    preds = synthetic.default_predicates()
    cfg = SquashConfig(num_partitions=8, kmeans_iters=6, lloyd_iters=10)
    index = SquashIndex.build(ds.vectors, ds.attributes, cfg, seed=0)
    return ds, preds, index


def test_recall_at_10_meets_paper_target(sift_small):
    """Paper §5.3: SQUASH calibrated to 97 % recall@k (and can exceed 99 %).
    With H_perc=10, R=2 defaults we require ≥0.95 on the synthetic stand-in."""
    ds, preds, index = sift_small
    gt_ids, _ = synthetic.ground_truth(ds, preds, k=10)
    ids, dists, stats = index.search(ds.queries, preds, k=10)
    recalls = []
    for qi in range(ds.queries.shape[0]):
        g = set(gt_ids[qi][gt_ids[qi] >= 0].tolist())
        r = set(ids[qi][ids[qi] >= 0].tolist())
        if g:
            recalls.append(len(g & r) / len(g))
    recall = float(np.mean(recalls))
    assert recall >= 0.95, f"recall@10 = {recall}"


def test_all_results_satisfy_predicate(sift_small):
    """Hybrid guarantee: every returned vector passes the filter."""
    ds, preds, index = sift_small
    ids, _, _ = index.search(ds.queries[:10], preds, k=10)
    for row in ids:
        for vid in row[row >= 0]:
            for p in preds:
                assert p.eval(np.array([ds.attributes[vid, p.attr]]))[0]


def test_results_sorted_and_unique(sift_small):
    ds, preds, index = sift_small
    ids, dists, _ = index.search(ds.queries[:10], preds, k=10)
    for qi in range(10):
        valid = ids[qi] >= 0
        d = dists[qi][valid]
        assert np.all(np.diff(d) >= -1e-9)
        assert np.unique(ids[qi][valid]).size == valid.sum()


def test_pruning_pipeline_reduces_work(sift_small):
    """Multi-stage pruning: ADC evaluations ≪ N, refinement ≈ R·k."""
    ds, preds, index = sift_small
    qn = 10
    _, _, stats = index.search(ds.queries[:qn], preds, k=10, collect_stats=True)
    # Attribute filter alone prunes to ~8 %.
    assert stats.filter_pass < 0.16 * ds.n * qn
    # Hamming keeps H_perc (plus floor).
    assert stats.hamming_kept <= max(
        0.2 * stats.hamming_in, index.config.min_hamming_keep * stats.partitions_visited
    )
    # Refinement is tiny: ≤ R·k per (query, partition).
    assert stats.refined <= stats.partitions_visited * 2 * 10


def test_exact_match_query(sift_small):
    """A query equal to a database vector passing the filter returns it."""
    ds, preds, index = sift_small
    mask = np.ones(ds.n, dtype=bool)
    for p in preds:
        mask &= p.eval(ds.attributes[:, p.attr])
    target = int(np.where(mask)[0][0])
    ids, dists, _ = index.search(ds.vectors[target][None, :], preds, k=5)
    assert target in ids[0].tolist()
    assert dists[0][ids[0].tolist().index(target)] < 1e-5


def test_unfiltered_search():
    ds = synthetic.make_vector_dataset("deep10m", scale=0.001, num_queries=10, seed=1)
    cfg = SquashConfig(num_partitions=4, kmeans_iters=4, lloyd_iters=8)
    index = SquashIndex.build(ds.vectors, ds.attributes, cfg, seed=1)
    gt_ids, _ = synthetic.ground_truth(ds, [], k=10)
    ids, _, _ = index.search(ds.queries, [], k=10)
    recalls = [
        len(set(gt_ids[q].tolist()) & set(ids[q].tolist())) / 10
        for q in range(10)
    ]
    assert np.mean(recalls) >= 0.9


def test_index_compression(sift_small):
    """OSQ primary index ≈ b/32 of full precision (b = 4·d vs 32-bit floats)."""
    ds, _, index = sift_small
    sizes = index.index_bytes()
    full = sizes["full_precision"]
    # float64 in-memory copy: compare against float32 (the paper's baseline).
    full32 = full // 2
    assert sizes["primary_osq"] <= full32 / 7.0
    assert sizes["lowbit_osq"] <= full32 / 30.0


def test_no_refine_mode():
    ds = synthetic.make_vector_dataset("sift1m", scale=0.005, num_queries=10, seed=2)
    cfg = SquashConfig(num_partitions=4, enable_refine=False, kmeans_iters=4,
                       lloyd_iters=8)
    index = SquashIndex.build(ds.vectors, ds.attributes, cfg, seed=2)
    ids, dists, _ = index.search(ds.queries, [], k=10)
    assert (ids >= 0).all()


def test_dataset_generation_in_row_chunks_keeps_values():
    """Points are assembled in row chunks; the values are those of the
    one-shot (N+Q, lid, d) basis gather, bit for bit, across chunk edges."""
    preset, scale, nq, seed = "sift1m", 0.02, 9, 5
    ds = synthetic.make_vector_dataset(preset, scale=scale, num_queries=nq,
                                       seed=seed)
    spec = synthetic.DATASET_PRESETS[preset]
    n, d, lid = ds.n, spec["d"], spec["lid"]
    assert n + nq > synthetic._CHUNK_ROWS
    c = min(spec["clusters"], max(4, n // 256))
    rng = np.random.default_rng(seed)
    centers = rng.normal(0.0, 10.0, size=(c, d))
    bases = rng.normal(size=(c, lid, d)) / np.sqrt(d)
    energies = np.geomspace(4.0, 0.5, lid)
    which = rng.integers(0, c, size=n + nq)
    latent = rng.normal(size=(n + nq, lid)) * energies[None, :]
    ambient = rng.normal(size=(n + nq, d)) * 0.05
    pts = (centers[which] + np.einsum("nl,nld->nd", latent, bases[which])
           + ambient)
    np.testing.assert_array_equal(ds.vectors, pts[:n].astype(np.float32))
    np.testing.assert_array_equal(ds.queries, pts[n:].astype(np.float32))
