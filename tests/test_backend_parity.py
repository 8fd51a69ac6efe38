"""NumPy vs JAX query data-plane parity (pipeline backend switch).

The batched jitted plane (core/dataplane.py) must return **bitwise-identical
ids** to the per-query NumPy reference for every supported configuration:
selective predicates, empty-result predicates, unfiltered search, no-refine
mode, Stage 4 with and without chunk lanes (hot dims past 128 cells, and an
index capped at 128 cells a dim), and k larger than some partitions'
candidate sets. SearchStats counters must agree exactly, and the plane must
trace exactly once per (Q, k, index shape).
"""

import numpy as np
import pytest

from repro.core import dataplane
from repro.core.attributes import Predicate
from repro.core.pipeline import SquashConfig, SquashIndex
from repro.data import synthetic
from repro.serve.vector_service import ServiceConfig, VectorSearchService

from _traffic import assert_guarantees, cell_predicates


@pytest.fixture(scope="module")
def built():
    ds = synthetic.make_vector_dataset("sift1m", scale=0.008, num_queries=24,
                                       seed=5)
    preds = synthetic.default_predicates()
    cfg = SquashConfig(num_partitions=6, kmeans_iters=5, lloyd_iters=8)
    index = SquashIndex.build(ds.vectors, ds.attributes, cfg, seed=5)
    return ds, preds, index


def _both(index, queries, preds, k):
    ids_n, d_n, s_n = index.search(queries, preds, k=k, backend="numpy")
    ids_j, d_j, s_j = index.search(queries, preds, k=k, backend="jax")
    return (ids_n, d_n, s_n), (ids_j, d_j, s_j)


@pytest.fixture(scope="module")
def built_7bit(built):
    """The same data at ``max_bits_per_dim = 7`` (as in ``sift1m-7bit``):
    every dim fits one 128-cell lane, the identity lane layout."""
    ds, preds, _ = built
    cfg = SquashConfig(num_partitions=6, kmeans_iters=5, lloyd_iters=8,
                       max_bits_per_dim=7)
    index = SquashIndex.build(ds.vectors, ds.attributes, cfg, seed=5)
    assert index.device_stack().lane_dim.shape[-1] == index.dim
    return ds, preds, index


@pytest.mark.parametrize("k", [1, 10, 100])
@pytest.mark.parametrize("width", [2, 3, 5, 9, 12, 16])
@pytest.mark.parametrize("lanes", ["chunk", "identity"])
def test_selective_predicates_identical(request, seed, lanes, width, k):
    """The cells' predicates at every width from past the Eq. 1 cut (2 of
    16: ~0.02% of rows pass) to unfiltered, with k up to past the passing
    rows: the planes return the same ids, distances and stats, and both
    keep the filter and the k guarantee."""
    ds, _, index = request.getfixturevalue(
        "built" if lanes == "chunk" else "built_7bit")
    preds = cell_predicates(width, seed)
    (ids_n, d_n, s_n), (ids_j, d_j, s_j) = _both(index, ds.queries, preds, k)
    np.testing.assert_array_equal(ids_n, ids_j)
    finite = np.isfinite(d_n)
    np.testing.assert_array_equal(finite, np.isfinite(d_j))
    np.testing.assert_allclose(d_j[finite], d_n[finite], rtol=1e-9, atol=1e-9)
    assert s_n == s_j
    assert_guarantees(ids_j, ds.attributes, preds, k)


def test_unfiltered_identical(built):
    ds, _, index = built
    (ids_n, _, s_n), (ids_j, _, s_j) = _both(index, ds.queries, [], 10)
    np.testing.assert_array_equal(ids_n, ids_j)
    assert s_n == s_j


def test_empty_result_predicate(built):
    ds, _, index = built
    impossible = [Predicate(attr=0, op="=", lo=1e9)]
    (ids_n, d_n, s_n), (ids_j, d_j, s_j) = _both(
        index, ds.queries[:5], impossible, 10)
    assert (ids_n == -1).all() and (ids_j == -1).all()
    assert np.isinf(d_n).all() and np.isinf(d_j).all()
    assert s_n == s_j
    assert s_j.hamming_in == 0 and s_j.refined == 0


def test_k_exceeds_candidates(built):
    """k larger than some partitions' filtered candidate sets: -1 padding in
    both planes, identical placement."""
    ds, _, index = built
    narrow = [Predicate(attr=0, op="=", lo=float(ds.attributes[0, 0]))]
    (ids_n, d_n, _), (ids_j, d_j, _) = _both(index, ds.queries[:6], narrow, 50)
    np.testing.assert_array_equal(ids_n, ids_j)
    np.testing.assert_array_equal(np.isfinite(d_n), np.isfinite(d_j))


def test_single_query_and_odd_batches(built):
    ds, preds, index = built
    for qn in (1, 3):
        (ids_n, _, _), (ids_j, _, _) = _both(index, ds.queries[:qn], preds, 7)
        np.testing.assert_array_equal(ids_n, ids_j)


def test_no_refine_backend_parity(built):
    ds, preds, _ = built
    cfg = SquashConfig(num_partitions=4, enable_refine=False, kmeans_iters=4,
                       lloyd_iters=6)
    index = SquashIndex.build(ds.vectors, ds.attributes, cfg, seed=6)
    (ids_n, d_n, s_n), (ids_j, d_j, s_j) = _both(index, ds.queries[:8],
                                                 preds, 10)
    np.testing.assert_array_equal(ids_n, ids_j)
    assert s_n == s_j and s_n.refined == 0


def test_table_kernel_path_parity(built):
    """max_bits_per_dim small → every dim fits one 128-cell lane → D' = d,
    the identity lane layout, must match too."""
    ds, preds, _ = built
    cfg = SquashConfig(num_partitions=4, kmeans_iters=4, lloyd_iters=6,
                       max_bits_per_dim=5)
    index = SquashIndex.build(ds.vectors, ds.attributes, cfg, seed=7)
    stacked = index.device_stack()
    assert stacked.lane_dim.shape[-1] == index.dim, "config adds chunk lanes"
    (ids_n, _, s_n), (ids_j, _, s_j) = _both(index, ds.queries[:10], preds, 10)
    np.testing.assert_array_equal(ids_n, ids_j)
    assert s_n == s_j


@pytest.mark.parametrize("use_pallas", [True, False],
                         ids=["pallas-interpret", "jnp-twin"])
def test_chunk_lane_parity_at_12_bits(built, monkeypatch, use_pallas):
    """The default index (up to 12 bits a dim, M+1 = 4097) spreads its hot
    dims over chunk lanes; both kernel back ends give the NumPy plane's ids."""
    from repro.kernels import ops

    ds, preds, index = built
    assert index.config.max_bits_per_dim == 12
    assert max(int(p.quant.cells.max()) for p in index.parts) == 4096
    stacked = index.device_stack()
    assert stacked.lane_dim.shape[-1] > index.dim     # chunk lanes engaged
    monkeypatch.setattr(ops, "_use_pallas", lambda o: use_pallas)
    monkeypatch.setattr(ops, "_interpret", lambda o: True)
    index._plane_cache.clear()
    try:
        (ids_n, d_n, s_n), (ids_j, d_j, s_j) = _both(index, ds.queries[:4],
                                                     preds, 10)
    finally:
        index._plane_cache.clear()
    np.testing.assert_array_equal(ids_n, ids_j)
    finite = np.isfinite(d_n)
    np.testing.assert_allclose(d_j[finite], d_n[finite], rtol=1e-9, atol=1e-9)
    assert s_n == s_j


def test_config_backend_field_and_validation(built):
    ds, preds, index = built
    index.config.backend = "jax"
    try:
        ids_cfg, _, _ = index.search(ds.queries[:4], preds, k=5)
    finally:
        index.config.backend = "numpy"
    ids_j, _, _ = index.search(ds.queries[:4], preds, k=5, backend="jax")
    np.testing.assert_array_equal(ids_cfg, ids_j)
    with pytest.raises(ValueError, match="unknown backend"):
        index.search(ds.queries[:2], preds, k=5, backend="torch")


def test_jax_plane_traces_once_per_shape(built):
    """One trace per (Q, k, index shape): repeated same-shape calls reuse the
    compiled plane; a new Q adds exactly one trace."""
    ds, preds, index = built
    base = index._trace_counter[0]
    index.search(ds.queries[:8], preds, k=10, backend="jax")
    after_first = index._trace_counter[0]
    index.search(ds.queries[:8], preds, k=10, backend="jax")
    index.search(ds.queries[8:16], preds, k=10, backend="jax")
    assert index._trace_counter[0] == after_first  # same (Q, k): no retrace
    index.search(ds.queries[:3], preds, k=10, backend="jax")
    assert index._trace_counter[0] == after_first + 1  # new Q: one trace


def test_stage_counts_match_reference_formulas():
    cfg = SquashConfig(min_hamming_keep=8, hamming_perc=10.0, refine_ratio=2.0)
    n_cand = np.array([[0, 1, 7, 8, 50, 500, 3000]], dtype=np.int32)
    keep, take = dataplane.stage_counts(n_cand, cfg, k=10)
    for i, n in enumerate(n_cand[0]):
        n = int(n)
        if n == 0:
            ref_keep = 0
        else:
            # The floor is raised to k (8 → 10 here): a partition that can
            # fill the answer keeps k of its candidates.
            ref_keep = max(min(max(cfg.min_hamming_keep, 10), n),
                           int(np.ceil(n * cfg.hamming_perc / 100.0)))
            ref_keep = min(ref_keep, n)
        assert keep[0, i] == ref_keep
        assert take[0, i] == min(int(np.ceil(cfg.refine_ratio * 10)), ref_keep)
    keep_s, take_s = dataplane.static_counts(3000, cfg, k=10)
    assert keep_s == max(8, 300) and take_s == 20
    assert (keep <= keep_s).all() and (take <= take_s).all()


def test_service_routes_and_accounts(built):
    ds, preds, index = built
    svc = VectorSearchService(index, ServiceConfig(backend="auto"))
    assert svc.resolve_backend(1) == "numpy"
    assert svc.resolve_backend(64) == "jax"
    ids_b, _, _ = svc.query(ds.queries[:8], preds)          # auto → jax
    ids_1, _, _ = svc.query(ds.queries[:1], preds)          # auto → numpy
    assert svc.queries_served["jax"] == 8
    assert svc.queries_served["numpy"] == 1
    ids_ref, _, _ = index.search(ds.queries[:8], preds, k=10, backend="numpy")
    np.testing.assert_array_equal(ids_b, ids_ref)
    assert svc.stats.queries == 9
    # explicit "auto" must route, not leak into SquashIndex.search
    ids_a, _, _ = svc.query(ds.queries[:8], preds, backend="auto")
    np.testing.assert_array_equal(ids_a, ids_ref)
    with pytest.raises(ValueError):
        VectorSearchService(index, ServiceConfig(backend="torch"))


def test_service_validates_per_call_backend(built):
    """Regression: a bad per-call backend string must fail at the service
    boundary — before touching the index — and leave accounting unchanged."""
    ds, preds, index = built
    svc = VectorSearchService(index, ServiceConfig(backend="auto"))
    before_requests = svc.requests
    before_stats = svc.stats.queries
    with pytest.raises(ValueError, match="unknown backend 'torch'"):
        svc.query(ds.queries[:2], preds, backend="torch")
    assert svc.requests == before_requests
    assert svc.stats.queries == before_stats
    assert all(v == 0 for v in svc.queries_served.values())
