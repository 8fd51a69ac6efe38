"""Partition selection (Alg. 1, Eq. 1), ADC tables, low-bit Hamming tests."""

import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core import adc, lowbit, osq, partitions


def _members(assign, p):
    return [np.flatnonzero(assign == pid) for pid in range(p)]


# ----------------------------------------------------------------- partitions

def test_balanced_kmeans_balance():
    rng = np.random.default_rng(0)
    x = rng.normal(size=(4000, 16))
    cent, assign = partitions.balanced_kmeans(x, 8, iters=5)
    counts = np.bincount(assign, minlength=8)
    assert counts.max() <= int(np.ceil(1.05 * 4000 / 8))
    assert counts.min() > 0
    assert cent.shape == (8, 16)


def test_threshold_formula():
    rng = np.random.default_rng(1)
    x = rng.normal(size=(2000, 32))
    cent, assign = partitions.balanced_kmeans(x, 4, iters=4)
    t = partitions.compute_threshold(x, cent, assign, beta=0.001)
    # T = 1 + σ_µ/µ_µ + β√d  — must exceed 1 and stay sane.
    assert 1.0 < t < 3.0


def test_select_partitions_guarantee():
    """Alg. 1 guarantee: if ≥k filtered vectors exist globally, the visited
    partitions cover ≥k of them; every centroid within T·d_min is visited."""
    rng = np.random.default_rng(2)
    x = rng.normal(size=(3000, 8))
    cent, assign = partitions.balanced_kmeans(x, 6, iters=4)
    q = rng.normal(size=(5, 8))
    f = rng.random((5, 3000)) < 0.05
    k = 10
    visit, cands = partitions.select_partitions(
        q, cent, f, _members(assign, 6), 1.2, k)
    for qi in range(5):
        total = sum(v.size for v in cands[qi].values())
        assert total >= min(k, int(f[qi].sum()))
        # Threshold condition: all partitions within T·dmin visited
        # (unless they hold no candidates).
        d = np.sqrt(((q[qi][None, :] - cent) ** 2).sum(-1))
        dmin = d.min()
        for pid in range(6):
            if d[pid] <= 1.2 * dmin:
                has_cand = (f[qi] & (assign == pid)).any()
                assert visit[qi, pid] == bool(has_cand)


def test_select_partitions_empty_filter():
    rng = np.random.default_rng(3)
    x = rng.normal(size=(500, 4))
    cent, assign = partitions.balanced_kmeans(x, 3, iters=3)
    q = rng.normal(size=(2, 4))
    f = np.zeros((2, 500), dtype=bool)
    visit, cands = partitions.select_partitions(
        q, cent, f, _members(assign, 3), 1.2, 5)
    assert not visit.any()
    assert all(not c for c in cands)


def test_local_candidate_indices_are_local():
    rng = np.random.default_rng(4)
    x = rng.normal(size=(1000, 4))
    cent, assign = partitions.balanced_kmeans(x, 4, iters=3)
    q = rng.normal(size=(1, 4))
    f = np.ones((1, 1000), dtype=bool)
    visit, cands = partitions.select_partitions(
        q, cent, f, _members(assign, 4), 10.0, 5)
    for pid, rows in cands[0].items():
        n_local = int((assign == pid).sum())
        assert rows.max() < n_local
        assert rows.min() >= 0
        assert np.unique(rows).size == rows.size


# ------------------------------------------------------------------------ ADC

def _quantize(x, per_dim=4):
    bits = np.full(x.shape[1], per_dim, dtype=np.int32)
    q = osq.design_quantizers(x, bits)
    return q, osq.encode(q, x)


def test_adc_is_lower_bound():
    """LB(q, v) ≤ ||q − v|| for every vector — the VA-file invariant."""
    rng = np.random.default_rng(5)
    x = rng.normal(size=(2000, 12))
    q_obj, codes = _quantize(x)
    for qi in range(5):
        qv = rng.normal(size=12)
        table = adc.build_adc_table(qv, q_obj.boundaries, q_obj.cells)
        lb = np.asarray(adc.lb_distances(table, codes))
        exact = np.sqrt(((x - qv[None, :]) ** 2).sum(axis=1))
        assert np.all(lb <= exact + 1e-4), (lb - exact).max()


def test_adc_zero_for_own_cell():
    rng = np.random.default_rng(6)
    x = rng.normal(size=(1000, 6))
    q_obj, codes = _quantize(x)
    # Query = an existing data point ⇒ LB to itself must be 0.
    table = adc.build_adc_table(x[42], q_obj.boundaries, q_obj.cells)
    lb = np.asarray(adc.lb_distances(table, codes[42:43]))
    assert lb[0] == 0.0


def test_adc_onehot_matches_gather():
    rng = np.random.default_rng(7)
    x = rng.normal(size=(512, 9))
    q_obj, codes = _quantize(x, per_dim=3)
    qv = rng.normal(size=9)
    table = adc.build_adc_table(qv, q_obj.boundaries, q_obj.cells)
    a = np.asarray(adc.lb_distances(table, codes))
    b = np.asarray(adc.lb_distances_onehot(table, codes))
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


def test_adc_table_cost():
    """Paper: building L needs only (Σ C[j]) − 1 distance computations — i.e.
    the table has Σ C[j] meaningful entries; padding must be inf."""
    rng = np.random.default_rng(8)
    x = rng.normal(size=(500, 4))
    bits = np.array([2, 3, 1, 4], dtype=np.int32)
    q_obj = osq.design_quantizers(x, bits)
    table = adc.build_adc_table(rng.normal(size=4), q_obj.boundaries, q_obj.cells)
    finite = np.isfinite(table).sum()
    assert finite == q_obj.cells.sum()


# --------------------------------------------------------------------- lowbit

def test_hamming_matches_bit_count():
    rng = np.random.default_rng(9)
    bits_a = rng.integers(0, 2, size=(64,))
    bits_b = rng.integers(0, 2, size=(20, 64))
    pa = lowbit.pack_bits_u32(bits_a[None, :])[0]
    pb = lowbit.pack_bits_u32(bits_b)
    d = np.asarray(lowbit.hamming_distances(pa, pb))
    expect = (bits_a[None, :] != bits_b).sum(axis=1)
    np.testing.assert_array_equal(d, expect)


@given(seed=st.integers(0, 2**31 - 1), d=st.integers(1, 200))
@settings(max_examples=25, deadline=None)
def test_hamming_property(seed, d):
    rng = np.random.default_rng(seed)
    a = rng.integers(0, 2, size=(d,))
    b = rng.integers(0, 2, size=(7, d))
    pa = lowbit.pack_bits_u32(a[None, :])[0]
    pb = lowbit.pack_bits_u32(b)
    got = np.asarray(lowbit.hamming_distances(pa, pb))
    np.testing.assert_array_equal(got, (a[None, :] != b).sum(axis=1))


def test_hamming_prune_retains_true_neighbors():
    """§2.4.3's enabling observation, tested as the pipeline uses it: on
    clustered data, the true Euclidean top-k survives a 10 % Hamming cut."""
    rng = np.random.default_rng(10)
    centers = rng.normal(0, 10, size=(16, 128))
    which = rng.integers(0, 16, size=2000)
    x = centers[which] + rng.normal(size=(2000, 128))
    idx = lowbit.build_lowbit_index(x)
    survived = []
    for qi in range(10):
        q = centers[rng.integers(0, 16)] + rng.normal(size=128)
        qp = idx.encode_queries(q[None, :])[0]
        ham = np.asarray(lowbit.hamming_distances(qp, idx.packed)).astype(float)
        eu = np.sqrt(((x - q[None, :]) ** 2).sum(axis=1))
        top10 = np.argsort(eu)[:10]
        cut = np.percentile(ham, 10.0)
        survived.append((ham[top10] <= cut).mean())
    assert np.mean(survived) > 0.8, f"Hamming cut loses neighbors: {survived}"


def test_hamming_prune_keeps_best():
    rng = np.random.default_rng(11)
    x = rng.normal(size=(500, 64))
    idx = lowbit.build_lowbit_index(x)
    q = rng.normal(size=64)
    qp = idx.encode_queries(q[None, :])[0]
    mask = np.ones(500, dtype=np.int32)
    kept_idx, kept_d = lowbit.hamming_prune(qp, idx.packed, mask, keep=50)
    all_d = np.asarray(lowbit.hamming_distances(qp, idx.packed))
    assert np.asarray(kept_d).max() <= np.partition(all_d, 50)[50:].min() + 1
