"""End-to-end system tests: the full pipeline against brute-force ground
truth, and the distributed path against the one-host pipeline."""

import numpy as np

from repro.core.pipeline import SquashConfig, SquashIndex
from repro.data.synthetic import (default_predicates, ground_truth,
                                  make_vector_dataset)


# ----------------------------------------------------- end-to-end vs oracle

def test_full_system_recall_and_stage_accounting():
    ds = make_vector_dataset("sift1m", scale=0.005, num_queries=16, seed=3)
    preds = default_predicates(ds.attr_cardinality)
    idx = SquashIndex.build(ds.vectors, ds.attributes,
                            SquashConfig(num_partitions=6))
    ids, dists, stats = idx.search(ds.queries, preds, k=10,
                                   collect_stats=True)
    gt_ids, gt_d = ground_truth(ds, preds, k=10)
    hits = sum(len(set(ids[i]) & set(gt_ids[i])) for i in range(len(ids)))
    assert hits / gt_ids.size >= 0.9
    # every returned id satisfies the predicate (paper's hard guarantee)
    for row in ids:
        for vid in row:
            if vid >= 0:
                assert all(p.eval(np.asarray([ds.attributes[vid, p.attr]]))[0]
                           for p in preds)
    # stage monotonicity: filter ∩ → hamming prune → adc → refine
    assert stats.hamming_kept <= stats.hamming_in
    assert stats.refined <= stats.adc_evals


def test_distributed_matches_single_host_pipeline():
    from repro.core.distributed import distributed_search
    ds = make_vector_dataset("sift1m", scale=0.004, num_queries=8, seed=5)
    preds = default_predicates(ds.attr_cardinality)
    idx = SquashIndex.build(ds.vectors, ds.attributes,
                            SquashConfig(num_partitions=4))
    ids_ref, d_ref, _ = idx.search(ds.queries, preds, k=5)
    ids_dist, d_dist = distributed_search(idx, ds.queries, preds, k=5)
    # same neighbor sets (order ties can differ at equal distance)
    for a, b in zip(ids_ref, ids_dist):
        assert set(a.tolist()) == set(b.tolist())
    np.testing.assert_allclose(d_ref, d_dist, rtol=1e-4, atol=1e-4)
