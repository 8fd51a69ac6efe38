"""Stage 4 lane layout: hot dims cut into 128-cell chunk lanes.

The layout (``dataplane.lane_width`` / ``lane_layout`` / ``lane_bounds``) is
built on the host from each partition's cell counts; the plane turns it into
(Q, P, 129, D') tables (``lane_tables``) and (Q, P, S, D') codes
(``lane_codes``) for the ADC kernel. These tests pin the layout from
hand-made cell counts, the table entries against the NumPy reference table
cell by cell at M+1 = 4097, and where each code lands.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import adc, dataplane, osq

L = dataplane.LANE_CELLS


@pytest.mark.parametrize("cells,d_lanes,extra", [
    ([128] * 16, 16, 0),
    ([1, 2, 64, 128, 16, 8, 32, 4], 8, 0),
    ([4096] + [16] * 127, 256, 31),
    ([256, 512] + [8] * 14, 128, 1 + 3),
    ([4096] * 4 + [16] * 124, 256, 4 * 31),
    ([4096] * 4 + [2048] + [16] * 123, 384, 4 * 31 + 15),
])
def test_lane_width_and_chunk_count(cells, d_lanes, extra):
    cells = np.asarray(cells)
    assert dataplane.chunk_lanes(cells) == extra
    assert dataplane.lane_width([cells], cells.size) == d_lanes
    # The widest partition sets D' for all.
    assert dataplane.lane_width([np.ones_like(cells), cells],
                                cells.size) == d_lanes


def test_layout_is_identity_when_every_dim_fits_a_lane():
    cells = np.array([128, 1, 64, 2, 128, 32])
    lanes = dataplane.lane_width([cells], cells.size)
    lane_dim, lane_base = dataplane.lane_layout(cells, lanes)
    assert lanes == cells.size
    np.testing.assert_array_equal(lane_dim, np.arange(cells.size))
    np.testing.assert_array_equal(lane_base, 0)


def test_one_4096_cell_dim_gives_31_extra_lanes():
    d = 128
    cells = np.full(d, 16)
    cells[5] = 4096
    lanes = dataplane.lane_width([cells], d)
    lane_dim, lane_base = dataplane.lane_layout(cells, lanes)
    assert lanes == 256
    np.testing.assert_array_equal(lane_dim[:d], np.arange(d))
    np.testing.assert_array_equal(lane_base[:d], 0)
    np.testing.assert_array_equal(lane_dim[d:d + 31], 5)
    np.testing.assert_array_equal(lane_base[d:d + 31], L * np.arange(1, 32))
    # Pad lanes: empty chunks of dim 0, starting at its cell count.
    np.testing.assert_array_equal(lane_dim[d + 31:], 0)
    np.testing.assert_array_equal(lane_base[d + 31:], cells[0])


def test_layout_refuses_too_few_lanes():
    with pytest.raises(ValueError, match="lanes"):
        dataplane.lane_layout(np.array([4096, 16]), 2)


@pytest.fixture(scope="module")
def tall_quantizer():
    """A partition's quantizer at M+1 = 4097: 12, 9, 7 and 3-bit dims."""
    rng = np.random.default_rng(3)
    bits = np.array([12, 9, 7, 3, 12, 0])
    x = rng.normal(size=(6000, bits.size)) * np.array([4, 2, 1, 1, 3, 1])
    return osq.design_quantizers(x, bits, iters=4), x


def _lanes_of(q):
    lanes = dataplane.lane_width([q.cells], q.d)
    lane_dim, lane_base = dataplane.lane_layout(q.cells, lanes)
    bounds = dataplane.lane_bounds(q.boundaries, lane_dim, lane_base,
                                   np.float64)
    return lane_dim, lane_base, bounds


def _tables(q, queries):
    """(Q, 129, D') lane tables, computed in float64 as the x64 plane does."""
    lane_dim, lane_base, bounds = _lanes_of(q)
    cells = jnp.asarray(q.cells.astype(np.int32))[None]
    lane_cells = np.clip(
        np.asarray(dataplane.lane_select(cells[None], jnp.asarray(
            lane_dim[None])))[0] - lane_base, 0, L)
    qt_lane = dataplane.lane_select(jnp.asarray(queries)[:, None, :],
                                    jnp.asarray(lane_dim[None]))
    t = dataplane.lane_tables(qt_lane, jnp.asarray(bounds[None]),
                              jnp.asarray(lane_cells))
    return np.asarray(t)[:, 0], lane_dim, lane_base


def test_lane_bounds_rows(tall_quantizer):
    q, _ = tall_quantizer
    lane_dim, lane_base, bounds = _lanes_of(q)
    assert bounds.shape == (L + 1, lane_dim.size)
    m1 = q.boundaries.shape[0]
    for v in range(lane_dim.size):
        for r in range(L + 1):
            row = lane_base[v] + r
            want = q.boundaries[row, lane_dim[v]] if row < m1 else np.inf
            assert bounds[r, v] == want


def test_lane_tables_bitwise_equal_reference_cell_by_cell(tall_quantizer):
    q, x = tall_quantizer
    assert q.boundaries.shape[0] == 4097
    rng = np.random.default_rng(4)
    # Queries inside the data, far outside it, and on a boundary.
    queries = np.concatenate([x[rng.integers(0, len(x), 5)],
                              rng.normal(size=(2, q.d)) * 50,
                              q.boundaries[100:101].clip(-1e3, 1e3)])
    tables, lane_dim, lane_base = _tables(q, queries)
    assert tables.dtype == np.float32
    assert (tables[:, L] == 0).all()                  # row 128: the zero row
    for qi, qv in enumerate(queries):
        ref = adc.build_adc_table(qv, q.boundaries, q.cells)
        seen = np.zeros((q.boundaries.shape[0], q.d), bool)
        for v in range(lane_dim.size):
            j, base = int(lane_dim[v]), int(lane_base[v])
            for r in range(L):
                c = base + r
                if c < q.cells[j]:
                    assert tables[qi, r, v].tobytes() == ref[c, j].tobytes()
                    seen[c, j] = True
                else:
                    assert tables[qi, r, v] == 0.0
        # Every real cell of every dim sits on exactly one lane row.
        for j in range(q.d):
            assert seen[:q.cells[j], j].all() and not seen[q.cells[j]:, j].any()


def test_codes_outside_a_chunk_land_on_row_128(tall_quantizer):
    q, x = tall_quantizer
    lane_dim, lane_base, _ = _lanes_of(q)
    codes = osq.encode(q, x[:300]).astype(np.int32)
    codes[0] = q.cells - 1                            # each dim's last cell
    codes[1] = 0
    got = np.asarray(dataplane.lane_codes(
        jnp.asarray(codes)[None, None], jnp.asarray(lane_dim[None]),
        jnp.asarray(lane_base[None])))[0, 0]
    assert got.shape == (codes.shape[0], lane_dim.size)
    for v in range(lane_dim.size):
        rel = codes[:, lane_dim[v]] - lane_base[v]
        inside = (rel >= 0) & (rel < L)
        np.testing.assert_array_equal(got[inside, v], rel[inside])
        np.testing.assert_array_equal(got[~inside, v], L)
    # Each code sits on exactly one lane of its dim.
    for j in range(q.d):
        on = (got[:, lane_dim == j] < L).sum(axis=1)
        np.testing.assert_array_equal(on, 1)


def test_lane_sums_equal_reference_lb(tall_quantizer):
    """Looked up over lanes, each survivor's LB equals the NumPy reference's
    sum over dims: the extra lanes add exact zeros."""
    q, x = tall_quantizer
    lane_dim, lane_base, _ = _lanes_of(q)
    qv = x[7] + 0.25
    tables, _, _ = _tables(q, qv[None])
    codes = osq.encode(q, x[:500]).astype(np.int32)
    lc = np.asarray(dataplane.lane_codes(
        jnp.asarray(codes)[None, None], jnp.asarray(lane_dim[None]),
        jnp.asarray(lane_base[None])))[0, 0]
    got = tables[0][lc, np.arange(lane_dim.size)[None, :]].astype(np.float64)
    ref = adc.build_adc_table(qv, q.boundaries, q.cells)
    want = ref[codes, np.arange(q.d)[None, :]].astype(np.float64)
    np.testing.assert_array_equal(np.sort(got, axis=1)[:, -q.d:],
                                  np.sort(want, axis=1))
    np.testing.assert_allclose(got.sum(axis=1), want.sum(axis=1), rtol=1e-12)


@pytest.mark.parametrize("max_bits", [12, 5])
def test_gauges_report_lanes(max_bits):
    """``dataplane.adc.lanes`` (set when the plane traces) reads D';
    ``dataplane.adc.chunk_lanes`` (set when the index is stacked) reads the
    most chunk lanes a partition uses, 0 when every dim fits one lane."""
    from repro.core.pipeline import SquashConfig, SquashIndex
    from repro.data import synthetic
    from repro.obs.metrics import REGISTRY

    ds = synthetic.make_vector_dataset("sift1m", scale=0.003, num_queries=4,
                                       seed=2)
    cfg = SquashConfig(num_partitions=3, kmeans_iters=3, lloyd_iters=4,
                       max_bits_per_dim=max_bits)
    index = SquashIndex.build(ds.vectors, ds.attributes, cfg, seed=2)
    want_chunks = max(dataplane.chunk_lanes(p.quant.cells)
                      for p in index.parts)
    assert (want_chunks > 0) == (max_bits > 7)
    REGISTRY.reset()
    REGISTRY.enable()
    try:
        index.search(ds.queries, [], k=5, backend="jax")
        gauges = REGISTRY.snapshot()["gauges"]
    finally:
        REGISTRY.disable()
        REGISTRY.reset()
    lanes = index.device_stack().lane_dim.shape[-1]
    assert lanes == (256 if max_bits > 7 else index.dim)
    assert gauges["dataplane.adc.lanes"] == lanes
    assert gauges["dataplane.adc.chunk_lanes"] == want_chunks
