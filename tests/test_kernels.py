"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (interpret=True)."""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import jax.numpy as jnp

from repro.core import lowbit, osq, segments
from repro.core.adc import build_adc_table
from repro.kernels import adc_lookup, hamming, ops, ref


# ------------------------------------------------------------------- hamming

@pytest.mark.parametrize("n", [1, 7, 512, 513, 2048])
@pytest.mark.parametrize("g", [1, 4, 30])
def test_hamming_kernel_sweep(n, g):
    rng = np.random.default_rng(n * 31 + g)
    q = rng.integers(0, 2**32, size=(g,), dtype=np.uint32)
    db = rng.integers(0, 2**32, size=(n, g), dtype=np.uint32)
    got = np.asarray(ops.hamming_distances(jnp.asarray(q), jnp.asarray(db),
                                           interpret=True))
    want = np.asarray(ref.hamming_ref(jnp.asarray(q), jnp.asarray(db)))
    np.testing.assert_array_equal(got, want)


@given(seed=st.integers(0, 2**31 - 1), n=st.integers(1, 300),
       g=st.integers(1, 12))
@settings(max_examples=15, deadline=None)
def test_hamming_kernel_property(seed, n, g):
    rng = np.random.default_rng(seed)
    q = rng.integers(0, 2**32, size=(g,), dtype=np.uint32)
    db = rng.integers(0, 2**32, size=(n, g), dtype=np.uint32)
    got = np.asarray(ops.hamming_distances(jnp.asarray(q), jnp.asarray(db),
                                           interpret=True))
    np.testing.assert_array_equal(got, np.asarray(ref.hamming_ref(q, db)))


def test_hamming_kernel_on_real_lowbit_index():
    rng = np.random.default_rng(0)
    x = rng.normal(size=(1000, 96))
    idx = lowbit.build_lowbit_index(x)
    q = idx.encode_queries(rng.normal(size=(1, 96)))[0]
    got = np.asarray(ops.hamming_distances(jnp.asarray(q),
                                           jnp.asarray(idx.packed),
                                           interpret=True))
    want = np.asarray(lowbit.hamming_distances(q, idx.packed))
    np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------- adc_lookup

@pytest.mark.parametrize("n,d,m1", [(1, 4, 5), (100, 16, 17), (300, 33, 9),
                                    (257, 128, 32)])
def test_adc_kernel_sweep(n, d, m1):
    rng = np.random.default_rng(n + d + m1)
    table = rng.exponential(size=(m1, d)).astype(np.float32)
    codes = rng.integers(0, m1, size=(n, d)).astype(np.int32)
    got = np.asarray(ops.adc_distances(jnp.asarray(table), jnp.asarray(codes),
                                       interpret=True))
    want = np.asarray(ref.adc_lb_ref(table, codes))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_adc_kernel_dtypes(dtype):
    rng = np.random.default_rng(5)
    table = rng.exponential(size=(9, 24)).astype(dtype)
    codes = rng.integers(0, 9, size=(64, 24)).astype(np.int32)
    got = np.asarray(ops.adc_distances(jnp.asarray(table), jnp.asarray(codes),
                                       interpret=True))
    want = np.asarray(ref.adc_lb_ref(table.astype(np.float32), codes))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_adc_kernel_matches_real_quantizer():
    """End-to-end: kernel LB == reference LB on a real OSQ index + query."""
    rng = np.random.default_rng(6)
    x = rng.normal(size=(800, 32)) * np.geomspace(3, 0.2, 32)
    bits = osq.allocate_bits(x.var(axis=0), 4 * 32)
    q_obj = osq.design_quantizers(x, bits)
    codes = osq.encode(q_obj, x).astype(np.int32)
    table = build_adc_table(rng.normal(size=32), q_obj.boundaries, q_obj.cells)
    safe = np.where(np.isfinite(table), table, 0.0).astype(np.float32)
    got = np.asarray(ops.adc_distances(jnp.asarray(safe), jnp.asarray(codes),
                                       interpret=True))
    want = np.asarray(ref.adc_lb_ref(safe, codes))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


@given(seed=st.integers(0, 2**31 - 1))
@settings(max_examples=10, deadline=None)
def test_adc_kernel_property(seed):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(1, 200))
    d = int(rng.integers(1, 40))
    m1 = int(rng.integers(2, 40))
    table = rng.exponential(size=(m1, d)).astype(np.float32)
    codes = rng.integers(0, m1, size=(n, d)).astype(np.int32)
    got = np.asarray(ops.adc_distances(jnp.asarray(table), jnp.asarray(codes),
                                       interpret=True, sqrt=False))
    want = np.asarray(ref.adc_lb_ref(table, codes, sqrt=False))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


# ------------------------------------------- batched (multi-query) variants

@pytest.mark.parametrize("q,p,n", [(1, 1, 1), (3, 2, 7), (9, 4, 513),
                                   (2, 3, 515)])
@pytest.mark.parametrize("g", [1, 5])
def test_hamming_stacked_sweep(q, p, n, g):
    """Padding edges: N not a multiple of block_n, Q not of block_q,
    single-row/single-query inputs."""
    rng = np.random.default_rng(q * 131 + p * 17 + n + g)
    qs = rng.integers(0, 2**32, size=(q, p, g), dtype=np.uint32)
    db = rng.integers(0, 2**32, size=(p, n, g), dtype=np.uint32)
    got = np.asarray(hamming.packed_hamming_stacked(
        jnp.asarray(qs), jnp.asarray(db), interpret=True, block_n=256,
        block_q=8))
    want = np.asarray(ref.hamming_stacked_ref(jnp.asarray(qs),
                                              jnp.asarray(db)))
    np.testing.assert_array_equal(got, want)


def test_hamming_multi_matches_per_query_kernel():
    rng = np.random.default_rng(3)
    qs = rng.integers(0, 2**32, size=(6, 3), dtype=np.uint32)
    db = rng.integers(0, 2**32, size=(300, 3), dtype=np.uint32)
    got = np.asarray(hamming.packed_hamming_multi(
        jnp.asarray(qs), jnp.asarray(db), interpret=True, block_n=128))
    for qi in range(6):
        row = np.asarray(ops.hamming_distances(
            jnp.asarray(qs[qi]), jnp.asarray(db), interpret=True))
        np.testing.assert_array_equal(got[qi], row)


@given(seed=st.integers(0, 2**31 - 1), q=st.integers(1, 12),
       p=st.integers(1, 5), n=st.integers(1, 300))
@settings(max_examples=10, deadline=None)
def test_hamming_stacked_property(seed, q, p, n):
    rng = np.random.default_rng(seed)
    g = int(rng.integers(1, 8))
    qs = rng.integers(0, 2**32, size=(q, p, g), dtype=np.uint32)
    db = rng.integers(0, 2**32, size=(p, n, g), dtype=np.uint32)
    got = np.asarray(hamming.packed_hamming_stacked(
        jnp.asarray(qs), jnp.asarray(db), interpret=True))
    np.testing.assert_array_equal(got, np.asarray(
        ref.hamming_stacked_ref(jnp.asarray(qs), jnp.asarray(db))))


@pytest.mark.parametrize("b,n,d,m1", [(1, 1, 1, 2), (3, 33, 17, 9),
                                      (5, 257, 24, 12), (2, 70, 130, 5)])
def test_adc_batch_sweep(b, n, d, m1):
    """Padding edges: N not a multiple of block_n, d not of block_d,
    single-row inputs."""
    rng = np.random.default_rng(b + n + d + m1)
    tables = rng.exponential(size=(b, m1, d)).astype(np.float32)
    codes = rng.integers(0, m1, size=(b, n, d)).astype(np.int32)
    got = np.asarray(adc_lookup.adc_lb_distances_batch(
        jnp.asarray(tables), jnp.asarray(codes), interpret=True, block_n=64,
        block_d=128))
    want = np.asarray(ref.adc_lb_batch_ref(tables, codes))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_adc_batch_matches_per_pair_kernel():
    rng = np.random.default_rng(11)
    tables = rng.exponential(size=(4, 9, 24)).astype(np.float32)
    codes = rng.integers(0, 9, size=(4, 64, 24)).astype(np.int32)
    got = np.asarray(adc_lookup.adc_lb_distances_batch(
        jnp.asarray(tables), jnp.asarray(codes), interpret=True))
    for bi in range(4):
        row = np.asarray(ops.adc_distances(
            jnp.asarray(tables[bi]), jnp.asarray(codes[bi]), interpret=True))
        np.testing.assert_allclose(got[bi], row, rtol=1e-5, atol=1e-5)


@given(seed=st.integers(0, 2**31 - 1))
@settings(max_examples=8, deadline=None)
def test_adc_batch_property(seed):
    rng = np.random.default_rng(seed)
    b = int(rng.integers(1, 6))
    n = int(rng.integers(1, 120))
    d = int(rng.integers(1, 40))
    m1 = int(rng.integers(2, 24))
    tables = rng.exponential(size=(b, m1, d)).astype(np.float32)
    codes = rng.integers(0, m1, size=(b, n, d)).astype(np.int32)
    got = np.asarray(adc_lookup.adc_lb_distances_batch(
        jnp.asarray(tables), jnp.asarray(codes), interpret=True, sqrt=False))
    want = np.asarray(ref.adc_lb_batch_ref(tables, codes, sqrt=False))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


# ------------------------------------------------------------------- bitpack

@pytest.mark.parametrize("seg_bits", [8, 16, 32])
def test_extract_kernel_roundtrip(seg_bits):
    rng = np.random.default_rng(seg_bits)
    bits = rng.integers(0, 10, size=24).tolist()
    bits[0] = max(bits[0], 1)
    layout = segments.build_layout(bits, seg_bits=seg_bits)
    codes = np.stack(
        [rng.integers(0, 1 << b, size=700) if b else np.zeros(700, np.int64)
         for b in bits], axis=1)
    packed = segments.pack_codes(layout, codes)
    got = np.asarray(ops.extract_codes(jnp.asarray(packed), layout,
                                       interpret=True))
    np.testing.assert_array_equal(got, codes)
    want = np.asarray(ref.extract_ref(packed, layout))
    np.testing.assert_array_equal(got, want)


def test_extract_kernel_odd_sizes():
    layout = segments.build_layout([3, 9, 1, 7, 12], seg_bits=8)
    rng = np.random.default_rng(1)
    codes = np.stack(
        [rng.integers(0, 1 << b, size=13) for b in [3, 9, 1, 7, 12]], axis=1)
    packed = segments.pack_codes(layout, codes)
    got = np.asarray(ops.extract_codes(jnp.asarray(packed), layout,
                                       interpret=True))
    np.testing.assert_array_equal(got, codes)


@given(seed=st.integers(0, 2**31 - 1))
@settings(max_examples=10, deadline=None)
def test_extract_kernel_property(seed):
    rng = np.random.default_rng(seed)
    d = int(rng.integers(1, 16))
    bits = rng.integers(0, 11, size=d).tolist()
    if sum(bits) == 0:
        bits[0] = 1
    layout = segments.build_layout(bits, seg_bits=int(rng.choice([8, 16, 32])))
    n = int(rng.integers(1, 150))
    codes = np.stack(
        [rng.integers(0, 1 << b, size=n) if b else np.zeros(n, np.int64)
         for b in bits], axis=1)
    packed = segments.pack_codes(layout, codes)
    got = np.asarray(ops.extract_codes(jnp.asarray(packed), layout,
                                       interpret=True))
    np.testing.assert_array_equal(got, codes)
