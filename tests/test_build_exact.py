"""The host build's vectorised steps against plain loops of the same rules.

Each reference below is the straightforward one-row- or one-dim-at-a-time
form of a build step; the program's vectorised form must give bit for bit
the same arrays, so a faster build never moves an index.
"""

import numpy as np
import pytest

from repro.core import lowbit, osq, partitions, pipeline, segments
from repro.core.pipeline import SquashConfig, SquashIndex


def _greedy_reference(pref, rows, cap, assign):
    counts = np.zeros(pref.shape[1], dtype=np.int64)
    for r, choices in zip(rows, pref):
        for c in choices:
            if counts[c] < cap:
                assign[r] = c
                counts[c] += 1
                break


@pytest.mark.parametrize("n,p,slack", [(500, 3, 1.05), (3000, 12, 1.0),
                                       (2000, 7, 0.6)])
def test_greedy_assign_matches_one_row_at_a_time(n, p, slack):
    rng = np.random.default_rng(n + p)
    pref = np.argsort(rng.normal(size=(n, p)), axis=1)
    rows = rng.permutation(n)
    cap = int(np.ceil(slack * n / p))
    want = rng.integers(0, p, n)                      # old assignment
    got = want.copy()
    _greedy_reference(pref, rows, cap, want)
    partitions._greedy_assign(pref, rows, cap, got)
    assert np.array_equal(got, want)


def _lloyd_reference(x, k, iters):
    """One dimension at a time: searchsorted and a segment sum per cell."""
    n, dd = x.shape
    srt = np.sort(x.T, axis=1)
    cent = np.quantile(srt, (np.arange(k) + 0.5) / k, axis=1)
    for _ in range(iters):
        bounds = (cent[:-1] + cent[1:]) / 2.0
        cnt = np.empty((k, dd), dtype=np.int64)
        sums = np.zeros((k, dd))
        for j in range(dd):
            start = np.concatenate(
                ([0], np.searchsorted(srt[j], bounds[:, j], side="left")))
            cnt[:, j] = np.diff(start, append=n)
            full = cnt[:, j] > 0
            sums[full, j] = np.add.reduceat(srt[j], start[full])
        new = np.sort(np.where(cnt > 0, sums / np.maximum(cnt, 1), cent),
                      axis=0)
        done = np.allclose(new, cent, rtol=0, atol=1e-12)
        cent = new
        if done:
            break
    out = np.full((k + 1, dd), np.inf)
    out[0] = -np.inf
    out[1:-1] = (cent[:-1] + cent[1:]) / 2.0
    return out


@pytest.mark.parametrize("k,iters,ties", [(2, 3, False), (16, 15, False),
                                          (256, 8, True), (4096, 4, False)])
def test_lloyd_max_matches_one_dim_at_a_time(k, iters, ties):
    rng = np.random.default_rng(k)
    x = rng.normal(size=(3000, 5)) * np.array([1e-2, 1.0, 3.0, 50.0, 7.0])
    if ties:
        x = np.round(x, 1)
        x[:, 0] = 2.5                                 # one constant dim
    assert np.array_equal(osq.lloyd_max_1d(x, k, iters=iters),
                          _lloyd_reference(x, k, iters))


def test_sorted_quantiles_match_numpy():
    rng = np.random.default_rng(3)
    for n in (1, 2, 7, 1000):
        srt = np.sort(np.round(rng.normal(size=(4, n)), 2), axis=1)
        for k in (1, 2, 64):
            qs = (np.arange(k) + 0.5) / k
            assert np.array_equal(osq._sorted_quantiles(srt, qs),
                                  np.quantile(srt, qs, axis=1))


def test_encode_matches_column_search():
    rng = np.random.default_rng(4)
    x = rng.normal(size=(777, 24)) * 3
    bits = osq.allocate_bits(x.var(axis=0), 96, max_bits=12)
    q = osq.design_quantizers(x, bits, iters=5)
    want = np.zeros(x.shape, np.int32)
    for j, k in enumerate(q.cells):
        if k > 1:
            want[:, j] = np.searchsorted(q.boundaries[1:k, j], x[:, j],
                                         side="right")
    got = osq.encode(q, x)
    assert got.flags.c_contiguous and np.array_equal(got, want)


@pytest.mark.parametrize("seg_bits", [8, 16, 32])
def test_pack_codes_matches_the_bit_stream(seg_bits):
    rng = np.random.default_rng(seg_bits)
    bits = rng.integers(0, 13, size=61)
    layout = segments.build_layout(bits, seg_bits=seg_bits)
    codes = rng.integers(0, 1 << 12, size=(301, 61))
    stream = np.concatenate(
        [(codes[:, [j]] >> np.arange(b - 1, -1, -1)) & 1
         for j, b in enumerate(bits) if b] + [np.zeros((301, 0), int)], axis=1)
    stream = np.pad(stream, ((0, 0), (0, -stream.shape[1] % seg_bits)))
    weights = 1 << np.arange(seg_bits - 1, -1, -1, dtype=np.uint64)
    want = (stream.reshape(301, -1, seg_bits).astype(np.uint64)
            @ weights).astype(layout.dtype)
    got = segments.pack_codes(layout, codes)
    assert got.dtype == layout.dtype and np.array_equal(got, want)
    assert np.array_equal(np.asarray(segments.extract_all(got, layout)),
                          codes & ((1 << bits) - 1))


@pytest.mark.parametrize("d", [1, 31, 32, 33, 960])
def test_pack_bits_matches_weighted_sum(d):
    b = np.random.default_rng(d).integers(0, 2, size=(19, d)).astype(np.int8)
    g = -(-d // 32)
    padded = np.zeros((19, g * 32), np.uint64)
    padded[:, :d] = b
    want = (padded.reshape(19, g, 32)
            @ (1 << np.arange(31, -1, -1, dtype=np.uint64))).astype(np.uint32)
    assert np.array_equal(lowbit.pack_bits_u32(b), want)


def test_threaded_build_equals_one_worker(monkeypatch):
    rng = np.random.default_rng(9)
    x = rng.normal(size=(3000, 40)) + rng.integers(0, 6, (3000, 1)) * 3.0
    attrs = rng.integers(0, 16, (3000, 4)).astype(np.float64)
    cfg = SquashConfig(num_partitions=6, kmeans_iters=3, lloyd_iters=5)
    many = SquashIndex.build(x, attrs, cfg, seed=2)
    monkeypatch.setattr(pipeline.os, "sched_getaffinity", lambda pid: {0})
    monkeypatch.setattr(partitions.os, "sched_getaffinity", lambda pid: {0})
    one = SquashIndex.build(x, attrs, cfg, seed=2)
    assert np.array_equal(many.partitioning.assign, one.partitioning.assign)
    for a, b in zip(many.parts, one.parts):
        for f in ("vector_ids", "klt", "codes", "packed", "vectors"):
            assert np.array_equal(getattr(a, f), getattr(b, f)), f
        assert np.array_equal(a.quant.boundaries, b.quant.boundaries)
        assert np.array_equal(a.low.packed, b.low.packed)
