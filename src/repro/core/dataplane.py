"""Batched JAX query data plane — Stages 3–5 of §2.4 with fixed shapes.

This module is the single implementation of the paper's per-partition hot
path (low-bit Hamming prune → ADC lookup-table lower bounds → full-precision
refinement → single-pass top-k merge), batched over queries *and* partitions
and jit-compiled end to end. Two consumers share it:

* ``SquashIndex.search(backend="jax")`` (``repro.core.pipeline``) — single
  host, the whole :class:`StackedIndex` resident.
* ``repro.core.distributed`` — the same stages inside a ``shard_map`` body,
  partitions sharded over the ``model`` mesh axis (the QP plane).

Layout: all partitions are stacked to a fixed row budget ``n_max`` with
validity masks (:func:`stack_index`), so every stage is a dense fixed-shape
tensor op — ``(Q, P, G)`` packed query words × ``(P, n_max, G)`` stacked
codes for the Hamming kernel, ``(Q·P, 129, D')`` tables × ``(Q·P, keep, D')``
survivor lane codes for the ADC kernel. The kernels dispatch through
``repro.kernels.ops``: Pallas on TPU, pure-jnp XLA twins on CPU.

Stage 4 lanes: a dimension's cells are cut into chunks of 128
(:data:`LANE_CELLS`). Lane j < d is chunk 0 of dim j; each further chunk of a
hot dim (OSQ gives up to 2^12 cells) gets a lane of its own after lane d−1,
and ``D'`` rounds the count up to a multiple of 128 with empty pad lanes
(:func:`lane_width`, :func:`lane_layout`). Each pair's table is then 129 rows
tall whatever the index's M+1: row r of lane v holds the entry of cell
``lane_base[v] + r`` of dim ``lane_dim[v]``, and row 128 is 0. A survivor's
code on a lane is its cell less the lane's base when that falls in the
chunk, else 128, so every lane but the one holding the cell adds an exact
0.0. With every dim at 128 cells or fewer ``D'`` = d and the layout is the
identity. The queries' and the survivors' lane copies are exact one-hot
selections over the d axis (a sum, and a matmul over the survivors), never
per-survivor gathers.

Parity contract: the returned ids are **bitwise identical** to the NumPy
reference path in ``pipeline.py``. Data-dependent per-(query, partition)
candidate/keep/refine counts (byproducts of Algorithm 1 on the host) enter
as dense integer arrays and are applied as masks over statically-shaped
``top_k`` results, so shapes never depend on data — one trace per
(Q, k, index-shape). Ties are broken identically on both sides: ascending
(score, row) within a stage, ascending (distance, partition, rank) at the
merge — ``lax.top_k`` prefers lower indices, the NumPy path uses stable
sorts over partition-ascending candidate streams.

Known residual: both sides compute identical float32 ADC table *entries*,
but row sums reduce in backend-specific order (NumPy pairwise vs XLA, over
d dims vs over D' lanes of which the extra ones add 0.0), so
two survivors whose LB sums differ only at f32-ULP scale could straddle the
refine-take cut differently. Final ids then still agree unless the excluded
row belonged to the true top-k — a measure-zero event the R·k refinement
buffer absorbs; the parity suite and smoke gate run seed-deterministic data
where this holds exactly.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops
from repro.obs.metrics import REGISTRY as _METRICS

__all__ = [
    "StackedIndex", "stack_index", "part_stack_arrays", "stack_single_part",
    "pack_query_bits", "LANE_CELLS", "chunk_lanes", "lane_width", "lane_layout",
    "lane_bounds", "lane_select", "lane_tables", "lane_codes", "plane_dtype",
    "build_cand_arrays", "stage_counts", "static_counts", "batched_stage345",
    "make_plane",
]

# A Python int, not a jnp scalar: a device constant made at import would
# start a jax backend in every process that imports this module.
_BIG_HAMMING = 1 << 30

# Cells per Stage 4 lane; table row LANE_CELLS is the all-zero row that
# codes outside a lane's chunk select.
LANE_CELLS = 128


@dataclasses.dataclass
class StackedIndex:
    """All partitions stacked to a fixed row budget (leading axis = partition).

    Padding rows have ``valid=False`` and never reach the results. This is the
    payload a QP shard holds resident (the DRE singleton, in HBM terms).
    """

    low_packed: jnp.ndarray   # (P, n_max, G32) uint32
    codes: jnp.ndarray        # (P, n_max, d) int32
    vectors: jnp.ndarray      # (P, n_max, d) float
    valid: jnp.ndarray        # (P, n_max) bool
    vector_ids: jnp.ndarray   # (P, n_max) int32
    part_mean: jnp.ndarray    # (P, d)
    klt: jnp.ndarray          # (P, d, d)
    low_mean: jnp.ndarray     # (P, d)
    low_std: jnp.ndarray      # (P, d)
    cells: jnp.ndarray        # (P, d) int32
    lane_dim: jnp.ndarray     # (P, D') int32 — the dim each Stage 4 lane reads
    lane_base: jnp.ndarray    # (P, D') int32 — its first cell
    lane_bounds: jnp.ndarray  # (P, 129, D') float — boundaries base..base+128

    @property
    def num_partitions(self) -> int:
        return int(self.low_packed.shape[0])

    @property
    def boundaries(self) -> jnp.ndarray:
        """The resident boundary rows, in lane layout (``lane_bounds``).

        The benchmark harness reads the stack's table height from here.
        """
        return self.lane_bounds

    @property
    def n_max(self) -> int:
        return int(self.low_packed.shape[1])


jax.tree_util.register_dataclass(
    StackedIndex,
    data_fields=[f.name for f in dataclasses.fields(StackedIndex)],
    meta_fields=[],
)


def chunk_lanes(cells: np.ndarray) -> int:
    """Lanes one partition needs past d: its dims' chunks after the first."""
    return int(np.sum(-(-np.asarray(cells, np.int64) // LANE_CELLS) - 1))


def lane_width(cells_per_part, d: int) -> int:
    """``D'``: d lanes plus the most chunk lanes any partition needs.

    ``cells_per_part`` holds each partition's (d,) cell counts. With every
    dim at :data:`LANE_CELLS` cells or fewer this is d; otherwise d plus
    the extra chunks, rounded up to a multiple of 128 (the TPU's lane tile).
    """
    extra = max(chunk_lanes(c) for c in cells_per_part)
    return d if extra == 0 else -(-(d + extra) // 128) * 128


def lane_layout(cells: np.ndarray, lanes: int
                ) -> Tuple[np.ndarray, np.ndarray]:
    """One partition's ``(lane_dim, lane_base)``, each (lanes,) int32.

    Lane j < d is chunk 0 of dim j; the further chunks of each dim follow in
    dim order. Pad lanes are empty chunks of dim 0 starting at its cell
    count, so their table columns and their codes' rows are the zero row.
    """
    cells = np.asarray(cells, np.int64)
    d = cells.shape[0]
    dims, bases = list(range(d)), [0] * d
    for j in range(d):
        for base in range(LANE_CELLS, int(cells[j]), LANE_CELLS):
            dims.append(j)
            bases.append(base)
    if len(dims) > lanes:
        raise ValueError(f"{len(dims)} lanes needed, {lanes} available")
    pad = lanes - len(dims)
    dims += [0] * pad
    bases += [int(cells[0])] * pad
    return np.asarray(dims, np.int32), np.asarray(bases, np.int32)


def lane_bounds(boundaries: np.ndarray, lane_dim: np.ndarray,
                lane_base: np.ndarray, dtype=np.float32) -> np.ndarray:
    """(129, lanes): ``boundaries[base_v + r, dim_v]``, +inf past M."""
    m1, d = boundaries.shape
    padded = np.full((m1 + LANE_CELLS + 1, d), np.inf, dtype)
    padded[:m1] = boundaries
    rows = lane_base[None, :] + np.arange(LANE_CELLS + 1)[:, None]
    return padded[rows, lane_dim[None, :]]


def part_stack_arrays(pt, *, n_max: int, lanes: int, d: int,
                      dtype=np.float32,
                      live_rows: Optional[np.ndarray] = None
                      ) -> Dict[str, np.ndarray]:
    """One partition's numpy slab of the stacked payload (no leading P axis).

    The field values are exactly what :func:`stack_index` writes at that
    partition's row, so a QueryProcessor worker holding only its own
    partition can rebuild ``stack_index(index)[pid:pid+1]`` bit-for-bit from
    (this dict, the global ``n_max`` and ``lanes`` = :func:`lane_width`)
    without the rest of the index —
    the contract the ProcessTransport parity tests pin.

    ``live_rows`` (optional, (n,) bool) folds a live-index tombstone bitmap
    into ``valid`` so Stage 3 (``batched_stage345``'s ``alive0`` mask) drops
    dead rows even when a request names them as candidates.
    """
    n = pt.size
    g32 = pt.low.packed.shape[1]
    out = {
        "low_packed": np.zeros((n_max, g32), np.uint32),
        "codes": np.zeros((n_max, d), np.int32),
        "vectors": np.zeros((n_max, d), dtype),
        "valid": np.zeros((n_max,), bool),
        "vector_ids": np.full((n_max,), -1, np.int32),
        "part_mean": np.asarray(pt.mean, dtype),
        "klt": (pt.klt.astype(dtype) if pt.klt is not None
                else np.eye(d, dtype=dtype)),
        "low_mean": np.asarray(pt.low.mean, dtype),
        "low_std": np.maximum(pt.low.std, 1e-12).astype(dtype),
        "cells": np.asarray(pt.quant.cells, np.int32),
    }
    out["lane_dim"], out["lane_base"] = lane_layout(pt.quant.cells, lanes)
    out["lane_bounds"] = lane_bounds(pt.quant.boundaries.astype(dtype),
                                     out["lane_dim"], out["lane_base"], dtype)
    out["low_packed"][:n] = pt.low.packed
    out["codes"][:n] = pt.codes
    out["vectors"][:n] = pt.vectors
    out["valid"][:n] = True if live_rows is None else np.asarray(
        live_rows, dtype=bool)
    out["vector_ids"][:n] = pt.vector_ids
    return out


def stack_single_part(arrays: Dict[str, np.ndarray]) -> StackedIndex:
    """Build a 1-partition :class:`StackedIndex` from a part's slab arrays."""
    return StackedIndex(**{k: jnp.asarray(v[None]) for k, v in arrays.items()})


def stack_index(index, pad_to_multiple: int = 1, dtype=np.float32,
                sharding=None) -> StackedIndex:
    """Stack a built ``SquashIndex`` into fixed-shape device arrays.

    ``dtype`` sets the float width of the stacked payload: the jax backend
    uses float64 when x64 is enabled so it matches the NumPy reference
    bit-for-bit, float32 otherwise (the deployment configuration). With a
    ``sharding`` (partition axis over a mesh) the stack is assembled on the
    host and each device receives only its slice of every leaf.
    """
    parts = index.parts
    p = len(parts)
    pad_p = -(-p // pad_to_multiple) * pad_to_multiple
    n_max = max(pt.size for pt in parts)
    d = index.dim
    g32 = parts[0].low.packed.shape[1]
    lanes = lane_width([pt.quant.cells for pt in parts], d)

    def zeros(shape, dt):
        return np.zeros(shape, dtype=dt)

    low_packed = zeros((pad_p, n_max, g32), np.uint32)
    codes = zeros((pad_p, n_max, d), np.int32)
    vectors = zeros((pad_p, n_max, d), dtype)
    valid = zeros((pad_p, n_max), bool)
    vector_ids = np.full((pad_p, n_max), -1, np.int32)
    part_mean = zeros((pad_p, d), dtype)
    klt = np.tile(np.eye(d, dtype=dtype), (pad_p, 1, 1))
    low_mean = zeros((pad_p, d), dtype)
    low_std = np.ones((pad_p, d), dtype)
    cells = np.ones((pad_p, d), np.int32)
    # Padding partitions: one cell a dim, so no chunk lanes, all +inf bounds.
    pad_dim, pad_base = lane_layout(np.ones(d), lanes)
    lane_dim = np.tile(pad_dim, (pad_p, 1))
    lane_base = np.tile(pad_base, (pad_p, 1))
    bounds = np.full((pad_p, LANE_CELLS + 1, lanes), np.inf, dtype)

    live_mask = getattr(index, "live_mask", None)
    for i, pt in enumerate(parts):
        live_rows = None if live_mask is None else live_mask[pt.vector_ids]
        pa = part_stack_arrays(pt, n_max=n_max, lanes=lanes, d=d,
                               dtype=dtype, live_rows=live_rows)
        low_packed[i] = pa["low_packed"]
        codes[i] = pa["codes"]
        vectors[i] = pa["vectors"]
        valid[i] = pa["valid"]
        vector_ids[i] = pa["vector_ids"]
        part_mean[i] = pa["part_mean"]
        klt[i] = pa["klt"]
        low_mean[i] = pa["low_mean"]
        low_std[i] = pa["low_std"]
        cells[i] = pa["cells"]
        lane_dim[i] = pa["lane_dim"]
        lane_base[i] = pa["lane_base"]
        bounds[i] = pa["lane_bounds"]
    # The extra lanes in use: the widest partition's chunk lanes, pads out.
    _METRICS.gauge("dataplane.adc.chunk_lanes").set(
        max(chunk_lanes(c) for c in cells))
    place = jnp.asarray if sharding is None else functools.partial(
        jax.device_put, device=sharding)
    return StackedIndex(
        low_packed=place(low_packed),
        codes=place(codes),
        vectors=place(vectors),
        valid=place(valid),
        vector_ids=place(vector_ids),
        part_mean=place(part_mean),
        klt=place(klt),
        low_mean=place(low_mean),
        low_std=place(low_std),
        cells=place(cells),
        lane_dim=place(lane_dim),
        lane_base=place(lane_base),
        lane_bounds=place(bounds),
    )


def pack_query_bits(z: jnp.ndarray) -> jnp.ndarray:
    """Binarize standardized values and pack into uint32 words, MSB-first.

    Works over arbitrary leading batch axes: (..., d) → (..., ceil(d/32)).
    Twin of ``lowbit.pack_bits_u32(binarize(...))``.
    """
    d = z.shape[-1]
    g = -(-d // 32)
    bits = (z > 0).astype(jnp.uint32)
    pad = [(0, 0)] * (z.ndim - 1) + [(0, g * 32 - d)]
    bits = jnp.pad(bits, pad)
    bits = bits.reshape(*z.shape[:-1], g, 32)
    weights = jnp.uint32(1) << jnp.arange(31, -1, -1, dtype=jnp.uint32)
    return jnp.sum(bits * weights, axis=-1, dtype=jnp.uint32)


def lane_select(x: jnp.ndarray, lane_dim: jnp.ndarray) -> jnp.ndarray:
    """Each lane's entry of ``x``: (Q, P, d) × (P, D') → (Q, P, D').

    An exact one-hot selection over the d axis (each sum has one nonzero
    term), for the small per-pair arrays: the query coordinates and cells.
    """
    d = x.shape[-1]
    pick = lane_dim[:, None, :] == jnp.arange(d)[:, None]      # (P, d, D')
    return jnp.sum(jnp.where(pick, x[..., None], 0), axis=-2, dtype=x.dtype)


def lane_tables(qt_lane: jnp.ndarray, bounds: jnp.ndarray,
                lane_cells: jnp.ndarray) -> jnp.ndarray:
    """Per-pair ADC tables in lane layout: (Q, P, 129, D') float32.

    qt_lane: (Q, P, D') each lane's query coordinate; bounds: (P, 129, D')
    (``StackedIndex.lane_bounds``); lane_cells: (P, D') cells in each lane's
    chunk. With lo, hi the edges of cell base + r, entry r is (x−hi)² if
    hi ≤ x, (lo−x)² if lo > x and 0 in the query's own cell: bitwise the
    float32 entry ``adc.build_adc_table`` gives that cell when computed in
    float64. Rows past the chunk, row 128 among them, are 0.
    """
    x = qt_lane[:, :, None, :]                                  # (Q, P, 1, D')
    lo = bounds[None]
    hi = jnp.concatenate([bounds[:, 1:], jnp.full_like(bounds[:, :1], jnp.inf)],
                         axis=1)[None]
    diff = jnp.where(hi <= x, x - hi, jnp.where(lo > x, lo - x, 0.0))
    sq = jnp.where(jnp.isfinite(diff), diff * diff, 0.0)
    row = jnp.arange(LANE_CELLS + 1)[:, None]
    return jnp.where(row < lane_cells[None, :, None, :], sq,
                     0.0).astype(jnp.float32)


def lane_codes(codes: jnp.ndarray, lane_dim: jnp.ndarray,
               lane_base: jnp.ndarray) -> jnp.ndarray:
    """Survivor codes in lane layout: (Q, P, S, d) → (Q, P, S, D') int32.

    A lane's code is its dim's cell less the lane's base when that lies in
    [0, 128), else 128, the zero row. Each lane picks its dim's code by a
    one-hot contraction over d at ``Precision.HIGHEST``, exact in float32
    for codes below 2^24: a matmul where an element-wise gather over the
    survivors would be a scalar stream on the TPU. With D' = d the layout
    is the identity and every code already lies in its one chunk.
    """
    d = codes.shape[-1]
    if lane_dim.shape[-1] == d:
        return codes
    pick = (lane_dim[:, None, :] == jnp.arange(d)[:, None]
            ).astype(jnp.float32)                               # (P, d, D')
    # (Q, P) both batch axes, leading on both sides: no transposed copy.
    pick = jnp.broadcast_to(pick[None], codes.shape[:2] + pick.shape[1:])
    picked = jnp.einsum("qpsd,qpdv->qpsv", codes.astype(jnp.float32), pick,
                        precision=jax.lax.Precision.HIGHEST)
    rel = picked.astype(jnp.int32) - lane_base[None, :, None, :]
    return jnp.where((rel >= 0) & (rel < LANE_CELLS), rel, LANE_CELLS)


# ------------------------------------------------------------ host helpers

def plane_dtype():
    """The plane's float dtype: float64 under x64, which the tests turn on
    so the plane's ids match the NumPy reference bit for bit, float32
    otherwise (every deployment)."""
    return np.float64 if jax.config.jax_enable_x64 else np.float32


def upload(x, dtype=None, sharding=None) -> jax.Array:
    """``jnp.asarray(x, dtype)``: a host array put on the device, its device
    bytes counted in ``dataplane.upload.bytes`` (an array already on the
    device passes through uncounted). With a ``sharding`` each device gets
    its slice straight from the host, and each slice is counted once as it
    is sent: a replicated array counts once per device."""
    if sharding is None:
        arr = jnp.asarray(x, dtype)
    else:
        arr = jax.device_put(np.asarray(x, dtype), sharding)
    if _METRICS.enabled and isinstance(x, np.ndarray):
        _METRICS.counter("dataplane.upload.bytes").inc(
            arr.nbytes if sharding is None else
            sum(shard.data.nbytes for shard in arr.addressable_shards))
    return arr


def build_cand_arrays(
    cands: List[Dict[int, np.ndarray]], qn: int, p: int, n_max: int
) -> np.ndarray:
    """Densify Algorithm 1's per-query candidate dicts into the plane's
    ``cand_mask``: (Q, P, n_max) bool — filter ∧ residency ∧ visit."""
    cand_mask = np.zeros((qn, p, n_max), dtype=bool)
    for qi in range(qn):
        for pid, rows in cands[qi].items():
            cand_mask[qi, pid, rows] = True
    return cand_mask


def stage_counts(n_cand: np.ndarray, config, k: int, profile=None
                 ) -> Tuple[np.ndarray, np.ndarray]:
    """Per-(query, partition) Hamming-keep and refine-take counts.

    Elementwise twin of the NumPy reference's data-dependent formulas in
    ``SquashIndex._search_partition`` (zero where no candidates). With a
    :class:`~repro.core.autotune.CalibrationProfile` the keep fraction is
    per-partition (broadcast over the partition axis) and the floor is the
    profile's calibrated ``min_keep``; otherwise the static config knobs.
    Either floor is raised to k (``autotune.keep_floor``).
    """
    from repro.core import autotune

    frac = autotune.keep_fracs(config, profile, n_cand.shape[1])
    floor = autotune.keep_floor(config, profile, k)
    keep = autotune.keep_counts(n_cand, frac[None, :], floor)
    cap = int(np.ceil(config.refine_ratio * k)) if config.enable_refine else k
    take = np.minimum(cap, keep)
    return keep.astype(np.int32), take.astype(np.int32)


def static_counts(n_max: int, config, k: int, profile=None
                  ) -> Tuple[int, int]:
    """Static upper bounds for keep/take (the fixed ``top_k`` sizes).

    Both per-pair formulas are monotone in the candidate count, so their
    value at ``n_max`` — under the *largest* per-partition keep fraction —
    bounds every (query, partition) pair.
    """
    from repro.core import autotune

    n = max(int(n_max), 1)
    frac = float(config.hamming_perc if profile is None
                 else np.max(profile.keep_frac))
    floor = autotune.keep_floor(config, profile, k)
    keep_s = max(int(autotune.keep_count(n, frac, floor)), 1)
    cap = int(np.ceil(config.refine_ratio * k)) if config.enable_refine else k
    take_s = max(min(cap, keep_s), 1)
    return keep_s, take_s


# ------------------------------------------------------------- traced plane

def batched_stage345(
    queries: jnp.ndarray,
    stacked: StackedIndex,
    cand_mask: jnp.ndarray,
    keep: jnp.ndarray,
    take: jnp.ndarray,
    *,
    k: int,
    keep_s: int,
    take_s: int,
    refine: bool = True,
    use_pallas: Optional[bool] = None,
    interpret: Optional[bool] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Stages 3–5 for a query batch against a partition stack. Traceable.

    Args:
      queries: (Q, d) float.
      stacked: the resident partition stack (P partitions, n_max row budget).
      cand_mask: (Q, P, n_max) bool — filter ∧ residency ∧ Alg.-1 visit.
      keep: (Q, P) int32 — per-pair Hamming survivors (≤ ``keep_s``).
      take: (Q, P) int32 — per-pair refinement candidates (≤ ``take_s``).
      k / keep_s / take_s: static shape parameters (see
        :func:`static_counts`).
      refine: include Stage 5 full-precision re-ranking.
      use_pallas / interpret: kernel dispatch overrides (see kernels/ops.py).
    Returns:
      ids (Q, k) int32 (-1 padding), dists (Q, k) float (+inf padding) —
      merged across all P partitions in one pass.
    """
    qn = queries.shape[0]
    p, n_max = stacked.valid.shape

    # --- Stage 3: low-bit Hamming prune (raw centered space) -------------
    qc = queries[:, None, :] - stacked.part_mean[None]          # (Q, P, d)
    zq = (qc - stacked.low_mean[None]) / stacked.low_std[None]
    qbits = pack_query_bits(zq)                                 # (Q, P, G)
    ham = ops.hamming_stacked(qbits, stacked.low_packed,
                              use_pallas=use_pallas, interpret=interpret)
    alive0 = cand_mask & stacked.valid[None]
    ham = jnp.where(alive0, ham, _BIG_HAMMING)
    neg_h, sel = jax.lax.top_k(-ham, keep_s)                    # (Q, P, keep_s)
    slot = jnp.arange(keep_s, dtype=keep.dtype)
    alive1 = slot[None, None, :] < keep[:, :, None]

    # --- Stage 4: ADC lookup-table lower bounds on survivors -------------
    # The TPU's default f32 matmul pass rounds to bfloat16, which moves
    # queries across quantizer cell boundaries.
    qt = jnp.einsum("qpd,pde->qpe", qc, stacked.klt,
                    precision=jax.lax.Precision.HIGHEST)        # (Q, P, d)
    d = queries.shape[-1]
    lanes = stacked.lane_dim.shape[-1]
    p_idx = jnp.arange(p)[None, :, None]
    kept_codes = stacked.codes[p_idx, sel]                      # (Q,P,keep_s,d)
    # Hot dims' cells spread over 128-cell lanes (module docstring), so the
    # tables are 129 rows tall for any index and every pair runs the kernel.
    lane_cells = jnp.clip(
        lane_select(stacked.cells[None], stacked.lane_dim)[0]
        - stacked.lane_base, 0, LANE_CELLS)                     # (P, D')
    tables = lane_tables(lane_select(qt, stacked.lane_dim),
                         stacked.lane_bounds, lane_cells)
    lb = ops.adc_batch(
        tables.reshape(qn * p, LANE_CELLS + 1, lanes),
        lane_codes(kept_codes, stacked.lane_dim, stacked.lane_base
                   ).reshape(qn * p, keep_s, lanes),
        use_pallas=use_pallas, interpret=interpret,
    ).reshape(qn, p, keep_s)
    lb = jnp.where(alive1, lb, jnp.inf)
    neg_lb, sel2 = jax.lax.top_k(-lb, take_s)                   # (Q, P, take_s)
    slot2 = jnp.arange(take_s, dtype=take.dtype)
    alive2 = slot2[None, None, :] < take[:, :, None]
    rows = jnp.take_along_axis(sel, sel2, axis=-1)              # (Q, P, take_s)

    kk = min(k, take_s)
    if refine:
        # --- Stage 5: full-precision refinement ('EFS' rows) -------------
        full = stacked.vectors[p_idx, rows]                     # (Q,P,take_s,d)
        diff = full - queries[:, None, None, :]
        exact = jnp.sqrt(jnp.sum(diff * diff, axis=-1))
        exact = jnp.where(alive2, exact, jnp.inf)
        neg_e, sel3 = jax.lax.top_k(-exact, kk)
        part_d = -neg_e                                         # (Q, P, kk)
        final_rows = jnp.take_along_axis(rows, sel3, axis=-1)
    else:
        part_d = jnp.where(alive2, -neg_lb, jnp.inf)[..., :kk]
        final_rows = rows[..., :kk]
    part_ids = stacked.vector_ids[p_idx, final_rows]
    part_ids = jnp.where(jnp.isfinite(part_d), part_ids, -1)
    if kk < k:
        part_ids = jnp.pad(part_ids, ((0, 0), (0, 0), (0, k - kk)),
                           constant_values=-1)
        part_d = jnp.pad(part_d, ((0, 0), (0, 0), (0, k - kk)),
                         constant_values=jnp.inf)

    # --- single-pass MPI-style merge over partitions (§2.4.5) ------------
    flat_d = part_d.reshape(qn, p * k)
    flat_i = part_ids.reshape(qn, p * k)
    neg, msel = jax.lax.top_k(-flat_d, k)
    return jnp.take_along_axis(flat_i, msel, axis=1), -neg


def make_plane(
    *,
    k: int,
    keep_s: int,
    take_s: int,
    refine: bool = True,
    use_pallas: Optional[bool] = None,
    interpret: Optional[bool] = None,
    trace_counter: Optional[list] = None,
):
    """Build the jitted batched search callable for one index/config shape.

    The returned function has signature ``(queries, stacked, cand_mask,
    keep, take) -> (ids, dists)`` and retraces only when array *shapes*
    change — i.e. once per (Q, k, index-shape). ``trace_counter`` (a
    one-element list) is incremented on each trace, which tests use to pin
    the one-trace guarantee.
    """

    @jax.jit
    def plane(queries, stacked, cand_mask, keep, take):
        # Python here runs only at trace time (shapes static), so both the
        # test counter and the obs compile metric count jit retraces, not
        # calls. Bucketing by pow2 query-batch size mirrors the trace-cache
        # key the padding scheme aims for.
        if trace_counter is not None:
            trace_counter[0] += 1
        q = int(queries.shape[0])
        bucket = 1 if q <= 1 else 1 << (q - 1).bit_length()
        _METRICS.counter(f"dataplane.jit_compiles.q{bucket}").inc()
        _METRICS.gauge("dataplane.adc.lanes").set(stacked.lane_dim.shape[-1])
        return batched_stage345(
            queries, stacked, cand_mask, keep, take,
            k=k, keep_s=keep_s, take_s=take_s, refine=refine,
            use_pallas=use_pallas, interpret=interpret,
        )

    return plane
