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
codes for the Hamming kernel, ``(Q·P, M+1, d)`` tables × ``(Q·P, keep, d)``
survivor codes for the ADC kernel. The kernels dispatch through
``repro.kernels.ops``: Pallas on TPU, pure-jnp XLA twins on CPU.

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
but row sums reduce in backend-specific order (NumPy pairwise vs XLA), so
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
    "pack_query_bits", "adc_table_batch",
    "query_cells", "adc_lb_direct", "build_cand_arrays", "stage_counts",
    "static_counts", "batched_stage345", "make_plane",
]

# A Python int, not a jnp scalar: a device constant made at import would
# start a jax backend in every process that imports this module.
_BIG_HAMMING = 1 << 30

# Stage 4 formulation switch: dense per-(query, partition) tables feed the
# Pallas ADC kernel, but their (M+1) axis scales with the *hottest*
# dimension's cell count (2^12 at the default max_bits_per_dim) — a dense
# (Q, P, M+1, d) build is gigabytes at batch size. Above this M+1 the plane
# switches to the direct boundary-gather evaluation (two gathers per
# (survivor, dim) — the paper's "advanced indexing", batched).
ADC_TABLE_MAX_M1 = 129


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
    boundaries: jnp.ndarray   # (P, M+1, d) float (+inf padding)
    cells: jnp.ndarray        # (P, d) int32

    @property
    def num_partitions(self) -> int:
        return int(self.low_packed.shape[0])

    @property
    def n_max(self) -> int:
        return int(self.low_packed.shape[1])


jax.tree_util.register_dataclass(
    StackedIndex,
    data_fields=[f.name for f in dataclasses.fields(StackedIndex)],
    meta_fields=[],
)


def part_stack_arrays(pt, *, n_max: int, m1: int, d: int,
                      dtype=np.float32,
                      live_rows: Optional[np.ndarray] = None
                      ) -> Dict[str, np.ndarray]:
    """One partition's numpy slab of the stacked payload (no leading P axis).

    The field values are exactly what :func:`stack_index` writes at that
    partition's row, so a QueryProcessor worker holding only its own
    partition can rebuild ``stack_index(index)[pid:pid+1]`` bit-for-bit from
    (this dict, the global ``n_max``/``m1``) without the rest of the index —
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
        "boundaries": np.full((m1, d), np.inf, dtype),
        "cells": np.asarray(pt.quant.cells, np.int32),
    }
    out["low_packed"][:n] = pt.low.packed
    out["codes"][:n] = pt.codes
    out["vectors"][:n] = pt.vectors
    out["valid"][:n] = True if live_rows is None else np.asarray(
        live_rows, dtype=bool)
    out["vector_ids"][:n] = pt.vector_ids
    mb = pt.quant.boundaries.shape[0]
    out["boundaries"][:mb] = pt.quant.boundaries.astype(dtype)
    return out


def stack_single_part(arrays: Dict[str, np.ndarray]) -> StackedIndex:
    """Build a 1-partition :class:`StackedIndex` from a part's slab arrays."""
    return StackedIndex(**{k: jnp.asarray(v[None]) for k, v in arrays.items()})


def stack_index(index, pad_to_multiple: int = 1,
                dtype=np.float32) -> StackedIndex:
    """Stack a built ``SquashIndex`` into fixed-shape device arrays.

    ``dtype`` sets the float width of the stacked payload: the jax backend
    uses float64 when x64 is enabled so it matches the NumPy reference
    bit-for-bit, float32 otherwise (the deployment configuration).
    """
    parts = index.parts
    p = len(parts)
    pad_p = -(-p // pad_to_multiple) * pad_to_multiple
    n_max = max(pt.size for pt in parts)
    d = index.dim
    g32 = parts[0].low.packed.shape[1]
    m1 = max(pt.quant.boundaries.shape[0] for pt in parts)

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
    boundaries = np.full((pad_p, m1, d), np.inf, dtype)
    cells = np.ones((pad_p, d), np.int32)

    live_mask = getattr(index, "live_mask", None)
    for i, pt in enumerate(parts):
        live_rows = None if live_mask is None else live_mask[pt.vector_ids]
        pa = part_stack_arrays(pt, n_max=n_max, m1=m1, d=d, dtype=dtype,
                               live_rows=live_rows)
        low_packed[i] = pa["low_packed"]
        codes[i] = pa["codes"]
        vectors[i] = pa["vectors"]
        valid[i] = pa["valid"]
        vector_ids[i] = pa["vector_ids"]
        part_mean[i] = pa["part_mean"]
        klt[i] = pa["klt"]
        low_mean[i] = pa["low_mean"]
        low_std[i] = pa["low_std"]
        boundaries[i] = pa["boundaries"]
        cells[i] = pa["cells"]
    return StackedIndex(
        low_packed=jnp.asarray(low_packed),
        codes=jnp.asarray(codes),
        vectors=jnp.asarray(vectors),
        valid=jnp.asarray(valid),
        vector_ids=jnp.asarray(vector_ids),
        part_mean=jnp.asarray(part_mean),
        klt=jnp.asarray(klt),
        low_mean=jnp.asarray(low_mean),
        low_std=jnp.asarray(low_std),
        boundaries=jnp.asarray(boundaries),
        cells=jnp.asarray(cells),
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


def adc_table_batch(qt: jnp.ndarray, boundaries: jnp.ndarray,
                    cells: jnp.ndarray) -> jnp.ndarray:
    """Batched jnp twin of ``adc.build_adc_table``.

    qt: (..., d) transformed queries; boundaries: (..., M+1, d) with +inf
    padding; cells: (..., d). Returns (..., M+1, d) squared edge distances
    with padding cells set to 0 (one-hot/gather never selects them for valid
    codes, and zeros keep the kernels' accumulators finite).
    """
    m1 = boundaries.shape[-2]
    inner = boundaries[..., 1:, :]                          # (..., M, d)
    qcell = jnp.sum(
        (inner <= qt[..., None, :]) & jnp.isfinite(inner), axis=-2
    )                                                       # (..., d)
    cell_idx = jnp.arange(m1)[:, None]                      # (M+1, 1)
    pad_inf = jnp.full(boundaries.shape[:-2] + (1, boundaries.shape[-1]),
                       jnp.inf, boundaries.dtype)
    right = jnp.concatenate([inner, pad_inf], axis=-2)
    left = boundaries
    diff = jnp.where(
        cell_idx < qcell[..., None, :],
        qt[..., None, :] - right,
        jnp.where(cell_idx > qcell[..., None, :],
                  left - qt[..., None, :], 0.0),
    )
    sq = jnp.where(jnp.isfinite(diff), diff * diff, 0.0)
    return jnp.where(cell_idx >= cells[..., None, :], 0.0, sq)


def query_cells(qt: jnp.ndarray, boundaries: jnp.ndarray) -> jnp.ndarray:
    """Per-dimension home cell of each query: (Q, P, d) int32.

    Batched twin of the ``searchsorted`` loop in ``adc.build_adc_table``:
    counts interior boundaries ≤ qt (the +inf padding never counts), via a
    binary search per (query, partition, dim) instead of an O(M·d) scan.
    """
    inner = jnp.swapaxes(boundaries[:, 1:, :], -1, -2)      # (P, d, M)

    def one(a, v):
        return jnp.searchsorted(a, v, side="right")

    per_dim = jax.vmap(one)                                 # (d,M),(d,) → (d,)
    per_part = jax.vmap(per_dim)                            # (P,d,M),(P,d)

    def per_q(qtq):                                         # (P, d) → (P, d)
        return per_part(inner, qtq)

    return jax.vmap(per_q)(qt).astype(jnp.int32)


def adc_lb_direct(qt: jnp.ndarray, qcell: jnp.ndarray, boundaries: jnp.ndarray,
                  codes: jnp.ndarray) -> jnp.ndarray:
    """Squared LB sums via direct boundary gathers (no dense table).

    qt/qcell: (Q, P, d); boundaries: (P, M+1, d); codes: (Q, P, S, d) →
    (Q, P, S) f32. Per (survivor, dim): 0 in the query's own cell, squared
    distance to the facing cell edge otherwise — identical values to the
    dense-table entries (computed in the same dtype, cast f32 before the
    row sum, matching the NumPy reference's float32 tables).
    """
    m1 = boundaries.shape[-2]
    c = codes
    cc = qcell[:, :, None, :]                               # (Q, P, 1, d)
    b = boundaries[None]                                    # (1, P, M+1, d)
    right = jnp.take_along_axis(b, jnp.clip(c + 1, 0, m1 - 1), axis=2)
    left = jnp.take_along_axis(b, jnp.clip(c, 0, m1 - 1), axis=2)
    qtb = qt[:, :, None, :]
    diff = jnp.where(c < cc, qtb - right,
                     jnp.where(c > cc, left - qtb, 0.0))
    sq = jnp.where(jnp.isfinite(diff), diff * diff, 0.0).astype(jnp.float32)
    return jnp.sum(sq, axis=-1, dtype=jnp.float32)


# ------------------------------------------------------------ host helpers

def upload(x, dtype=None) -> jax.Array:
    """``jnp.asarray(x, dtype)``: a host array put on the device, its device
    bytes counted in ``dataplane.upload.bytes`` (an array already on the
    device passes through uncounted)."""
    arr = jnp.asarray(x, dtype)
    if _METRICS.enabled and isinstance(x, np.ndarray):
        _METRICS.counter("dataplane.upload.bytes").inc(arr.nbytes)
    return arr


def build_cand_arrays(
    cands: List[Dict[int, np.ndarray]], qn: int, p: int, n_max: int
) -> Tuple[np.ndarray, np.ndarray]:
    """Densify Algorithm 1's per-query candidate dicts.

    Returns ``cand_mask`` (Q, P, n_max) bool — filter ∧ residency ∧ visit —
    and ``n_cand`` (Q, P) int32 candidate counts.
    """
    cand_mask = np.zeros((qn, p, n_max), dtype=bool)
    n_cand = np.zeros((qn, p), dtype=np.int32)
    for qi in range(qn):
        for pid, rows in cands[qi].items():
            cand_mask[qi, pid, rows] = True
            n_cand[qi, pid] = rows.size
    return cand_mask, n_cand


def stage_counts(n_cand: np.ndarray, config, k: int, profile=None
                 ) -> Tuple[np.ndarray, np.ndarray]:
    """Per-(query, partition) Hamming-keep and refine-take counts.

    Elementwise twin of the NumPy reference's data-dependent formulas in
    ``SquashIndex._search_partition`` (zero where no candidates). With a
    :class:`~repro.core.autotune.CalibrationProfile` the keep fraction is
    per-partition (broadcast over the partition axis) and the floor is the
    profile's calibrated ``min_keep``; otherwise the static config knobs.
    """
    from repro.core import autotune

    frac = autotune.keep_fracs(config, profile, n_cand.shape[1])
    floor = autotune.keep_floor(config, profile)
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
    if profile is None:
        frac = float(config.hamming_perc)
        floor = int(config.min_hamming_keep)
    else:
        frac = float(np.max(profile.keep_frac))
        floor = int(profile.min_keep)
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
    # The plane's only matmul. The TPU's default f32 pass rounds to
    # bfloat16, which moves queries across quantizer cell boundaries.
    qt = jnp.einsum("qpd,pde->qpe", qc, stacked.klt,
                    precision=jax.lax.Precision.HIGHEST)        # (Q, P, d)
    d = queries.shape[-1]
    m1 = stacked.boundaries.shape[1]
    p_idx = jnp.arange(p)[None, :, None]
    kept_codes = stacked.codes[p_idx, sel]                      # (Q,P,keep_s,d)
    if m1 <= ADC_TABLE_MAX_M1:
        # Dense per-pair tables → batched one-hot/MXU lookup kernel.
        tables = adc_table_batch(qt, stacked.boundaries[None],
                                 stacked.cells[None])
        lb = ops.adc_batch(
            tables.reshape(qn * p, m1, d).astype(jnp.float32),
            kept_codes.reshape(qn * p, keep_s, d),
            use_pallas=use_pallas, interpret=interpret,
        ).reshape(qn, p, keep_s)
    else:
        # Tall tables (hot 2^12-cell dims): direct boundary gathers.
        qcell = query_cells(qt, stacked.boundaries)
        lb = jnp.sqrt(adc_lb_direct(qt, qcell, stacked.boundaries,
                                    kept_codes))
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
        return batched_stage345(
            queries, stacked, cand_mask, keep, take,
            k=k, keep_s=keep_s, take_s=take_s, refine=refine,
            use_pallas=use_pallas, interpret=interpret,
        )

    return plane
