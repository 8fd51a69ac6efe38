"""The benchmark's generator, request stream and arrivals (CPU, small N)."""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from squashbench import data as bdata  # noqa: E402
from squashbench import reference  # noqa: E402

SIFT = dict(d=128, lid=13, num_attributes=4, cardinality=16)


@pytest.fixture(scope="module")
def ds():
    return bdata.make_dataset(n=20000, clusters=64, query_pool=512, seed=11,
                              **SIFT)


def test_shapes_and_types_follow_the_preset(ds):
    assert ds.vectors.shape == (20000, 128)
    assert ds.vectors.dtype == np.float32
    assert ds.queries.shape == (512, 128)
    assert ds.attributes.shape == (20000, 4)
    assert ds.attributes.min() == 0 and ds.attributes.max() == 15
    # The clustered stand-in: 64 centres at scale 10, each cluster on a
    # 13-dim manifold, so a point's nearest neighbour is far closer than a
    # random pair.
    x = ds.vectors[:2000].astype(np.float64)
    d2 = ((x[:, None, :] - x[None, :1000, :]) ** 2).sum(-1)
    np.fill_diagonal(d2[:1000], np.inf)
    assert np.median(d2.min(axis=1)) < 0.1 * np.median(d2)


def test_same_seed_same_data_other_seed_other_data():
    a = bdata.make_dataset(n=3000, clusters=8, query_pool=16, seed=2**31 + 5,
                           **SIFT)
    b = bdata.make_dataset(n=3000, clusters=8, query_pool=16, seed=2**31 + 5,
                           **SIFT)
    c = bdata.make_dataset(n=3000, clusters=8, query_pool=16, seed=6, **SIFT)
    assert np.array_equal(a.vectors, b.vectors)
    assert np.array_equal(a.attributes, b.attributes)
    assert not np.array_equal(a.vectors, c.vectors)


def test_predicates_have_the_intended_joint_selectivity(ds):
    traffic = {"queries_per_request": 16, "target_selectivity": 0.08}
    stream = bdata.RequestStream(traffic, ds, seed=3)
    width = bdata.predicate_width(16, 4, 0.08)
    assert width == 9
    want = bdata.joint_selectivity(16, 4, width)
    assert want == pytest.approx((9 / 16) ** 4)
    shares = []
    for i in range(200):
        req = stream[i]
        assert len(req.ranges) == 4
        assert all(hi - lo + 1 == width and 0 <= lo and hi <= 15
                   for _, lo, hi in req.ranges)
        assert req.query_rows.shape == (16,)
        shares.append(reference.range_mask(ds.attributes, req.ranges).mean())
    assert np.mean(shares) == pytest.approx(want, rel=0.03)


def test_stream_is_fixed_by_the_seed(ds):
    traffic = {"queries_per_request": 1, "target_selectivity": 0.08}
    a = bdata.RequestStream(traffic, ds, seed=9)
    b = bdata.RequestStream(traffic, ds, seed=9)
    late = a[5000]
    assert b[5000].ranges == late.ranges
    assert np.array_equal(b[5000].query_rows, late.query_rows)
    assert a[0].ranges == b[0].ranges


def test_arrivals_share_one_set_of_gaps_across_seeds():
    m = 300
    full = -np.log1p(-(np.arange(m) + 0.5) / m) / 10.0
    offsets = [bdata.arrival_offsets(10.0, 30.0, seed=s) for s in (1, 2)]
    assert not np.array_equal(*offsets)
    for a in offsets:
        assert a[0] == 0.0 and a[-1] < 30.0
        assert a.size == pytest.approx(m, abs=4)
        gaps = np.diff(a)
        # Every gap is one of the fixed set, none twice.
        pos = np.searchsorted(full, gaps - 1e-12)
        assert np.allclose(full[pos], gaps)
        assert np.unique(pos).size == pos.size
