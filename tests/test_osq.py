"""Unit + property tests for OSQ quantization (paper §2.2)."""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import osq


def test_allocate_bits_sums_to_budget():
    var = np.array([10.0, 1.0, 0.1, 0.01])
    bits = osq.allocate_bits(var, budget=16)
    assert bits.sum() == 16
    # Highest-variance dimension gets the most bits.
    assert bits[0] == bits.max()
    assert np.all(bits >= 0)


def test_allocate_bits_nonuniform():
    var = np.geomspace(100.0, 0.001, 16)
    bits = osq.allocate_bits(var, budget=64)
    assert bits.sum() == 64
    assert bits[0] > bits[-1], "variance-greedy must be non-uniform"


@given(
    d=st.integers(2, 24),
    per_dim=st.integers(1, 6),
    seed=st.integers(0, 2**31 - 1),
)
@settings(max_examples=20, deadline=None)
def test_allocate_bits_property(d, per_dim, seed):
    rng = np.random.default_rng(seed)
    var = np.abs(rng.normal(size=d)) + 1e-9
    budget = d * per_dim
    bits = osq.allocate_bits(var, budget)
    assert bits.sum() == budget
    assert bits.min() >= 0
    assert bits.max() <= 12


def test_lloyd_max_boundaries_sorted():
    rng = np.random.default_rng(0)
    x = rng.normal(size=(4096, 3)) * np.array([1.0, 5.0, 0.2])
    b = osq.lloyd_max_1d(x, k=8)
    assert b.shape == (9, 3)
    assert np.all(np.diff(b[1:-1], axis=0) >= 0)
    assert np.isneginf(b[0]).all() and np.isposinf(b[-1]).all()


def _lloyd_max_masked(x, k, iters=25):
    """The per-cell mask-loop Lloyd-Max that lloyd_max_1d's update replaced."""
    n, dd = x.shape
    cent = np.quantile(x, (np.arange(k) + 0.5) / k, axis=0)
    for _ in range(iters):
        bounds = (cent[:-1] + cent[1:]) / 2.0
        codes = np.stack([np.searchsorted(bounds[:, j], x[:, j], side="right")
                          for j in range(dd)], axis=1)
        new_cent = cent.copy()
        for c in range(k):
            mask = codes == c
            cnt = mask.sum(axis=0)
            sums = np.where(mask, x, 0.0).sum(axis=0)
            nz = cnt > 0
            new_cent[c, nz] = sums[nz] / cnt[nz]
        new_cent = np.sort(new_cent, axis=0)
        done = np.allclose(new_cent, cent, rtol=0, atol=1e-12)
        cent = new_cent
        if done:
            break
    return (cent[:-1] + cent[1:]) / 2.0


@pytest.mark.parametrize("n,dd,k", [(3000, 4, 8), (500, 3, 64), (257, 2, 2)])
def test_lloyd_max_bincount_update_matches_mask_loop(n, dd, k):
    """The O(n) bincount update gives the old per-cell loop's boundaries,
    empty cells (k=64 over 500 samples) included."""
    rng = np.random.default_rng(n + dd + k)
    x = rng.normal(size=(n, dd)) * rng.uniform(0.1, 5.0, size=dd)
    got = osq.lloyd_max_1d(x, k=k)[1:-1]
    np.testing.assert_allclose(got, _lloyd_max_masked(x, k), rtol=0,
                               atol=1e-12)


def test_encode_decode_roundtrip_error_shrinks_with_bits():
    rng = np.random.default_rng(1)
    x = rng.normal(size=(8192, 8))
    errs = []
    for per_dim in (2, 4, 6):
        bits = np.full(8, per_dim, dtype=np.int32)
        q = osq.design_quantizers(x, bits)
        codes = osq.encode(q, x)
        assert codes.min() >= 0
        assert np.all(codes.max(axis=0) < q.cells)
        rec = osq.decode_cell_centers(q, codes)
        errs.append(np.mean((rec - x) ** 2))
    assert errs[0] > errs[1] > errs[2], f"MSE must shrink with bits: {errs}"


def test_encode_out_of_range_values_clamp_to_edge_cells():
    rng = np.random.default_rng(2)
    x = rng.normal(size=(2048, 4))
    q = osq.design_quantizers(x, np.full(4, 3, dtype=np.int32))
    extreme = np.array([[1e9, -1e9, 0.0, 0.0]])
    codes = osq.encode(q, extreme)
    assert codes[0, 0] == q.cells[0] - 1
    assert codes[0, 1] == 0


def test_zero_bit_dimension():
    rng = np.random.default_rng(3)
    x = rng.normal(size=(1024, 3))
    bits = np.array([4, 0, 2], dtype=np.int32)
    q = osq.design_quantizers(x, bits)
    codes = osq.encode(q, x)
    assert np.all(codes[:, 1] == 0)
    assert q.cells.tolist() == [16, 1, 4]


def test_nonuniform_beats_uniform_on_skewed_data():
    """The point of VA+-style allocation: skewed variance ⇒ lower MSE."""
    rng = np.random.default_rng(4)
    scales = np.geomspace(10.0, 0.01, 12)
    x = rng.normal(size=(8192, 12)) * scales
    budget = 12 * 4
    uni = osq.design_quantizers(x, np.full(12, 4, dtype=np.int32))
    non = osq.design_quantizers(x, osq.allocate_bits(x.var(axis=0), budget))
    mse_u = np.mean((osq.decode_cell_centers(uni, osq.encode(uni, x)) - x) ** 2)
    mse_n = np.mean((osq.decode_cell_centers(non, osq.encode(non, x)) - x) ** 2)
    assert mse_n < mse_u
