"""Pallas TPU kernels: packed binary Hamming distance (paper §2.4.3).

XOR + popcount over uint32 segment words — 32 dimensions per VPU lane. The
query's packed words are tiny and broadcast to every grid step; the database
is BlockSpec-tiled over rows so each block's codes stream HBM→VMEM once.

Two entry points:

* :func:`packed_hamming` — one query vs one code matrix (the seed kernel).
* :func:`packed_hamming_stacked` — the batched query data plane's shape:
  per-(query, partition) packed query words ``(Q, P, G)`` against a stacked
  partition code tensor ``(P, N, G)`` → ``(Q, P, N)``. The grid walks
  (partition, row-block, query-block) over a word-major copy of the codes;
  each row block is re-used across the whole query-block axis, so codes
  stream HBM→VMEM once rather than once per query.

Target: TPU (VPU popcount); validated on CPU via ``interpret=True``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["hamming_kernel", "packed_hamming", "hamming_stacked_kernel",
           "packed_hamming_stacked", "packed_hamming_multi"]

BLOCK_N = 512   # rows per grid step (packed_hamming's only tiled axis).
BLOCK_N_STACKED = 2048  # rows per step of the stacked kernel: its lane axis.
BLOCK_Q = 32    # queries per step of the stacked kernel: its sublane axis.


def hamming_kernel(q_ref, db_ref, out_ref):
    """One block: (BLOCK_N, G) uint32 codes vs (1, G) query → (BLOCK_N,) i32."""
    q = q_ref[...]                       # (1, G)
    db = db_ref[...]                     # (BLOCK_N, G)
    x = jnp.bitwise_xor(db, q)           # broadcast over rows
    pc = jax.lax.population_count(x).astype(jnp.int32)
    out_ref[...] = jnp.sum(pc, axis=-1, dtype=jnp.int32)


@functools.partial(jax.jit, static_argnames=("interpret", "block_n"))
def packed_hamming(q_packed, db_packed, *, interpret: bool = False,
                   block_n: int = BLOCK_N):
    """Hamming distances between one packed query and all packed rows.

    Args:
      q_packed: (G,) uint32 packed query bits.
      db_packed: (N, G) uint32 packed database bits (N padded internally).
    Returns:
      (N,) int32 distances.
    """
    n, g = db_packed.shape
    bn = min(block_n, max(int(n), 1))
    pad = (-n) % bn
    if pad:
        db_packed = jnp.pad(db_packed, ((0, pad), (0, 0)))
    grid = (db_packed.shape[0] // bn,)
    out = pl.pallas_call(
        hamming_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, g), lambda i: (0, 0)),      # query: replicated
            pl.BlockSpec((bn, g), lambda i: (i, 0)),     # db rows: tiled
        ],
        out_specs=pl.BlockSpec((bn,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((db_packed.shape[0],), jnp.int32),
        interpret=interpret,
    )(q_packed[None, :], db_packed)
    return out[:n]


def hamming_stacked_kernel(q_ref, db_ref, out_ref):
    """One (partition, row-block, query-block) step.

    q_ref:   (1, BQ, G) uint32 — this partition's packed query words.
    db_ref:  (1, G, BN) uint32 — this partition's code rows, word-major so
      rows ride the 128-wide lane axis.
    out_ref: (1, BQ, BN) int32.

    One lane-dense (BQ, BN) XOR + popcount per word: the query word is a
    (BQ, 1) column broadcast over lanes, the row word a (1, BN) row
    broadcast over sublanes.
    """
    q = q_ref[0]                          # (BQ, G)
    db = db_ref[0]                        # (G, BN)
    acc = jnp.zeros(out_ref.shape[1:], jnp.int32)
    for w in range(q.shape[1]):
        x = jnp.bitwise_xor(q[:, w:w + 1], db[w:w + 1, :])
        acc = acc + jax.lax.population_count(x).astype(jnp.int32)
    out_ref[0] = acc


@functools.partial(
    jax.jit, static_argnames=("interpret", "block_n", "block_q")
)
def packed_hamming_stacked(q_packed, db_packed, *, interpret: bool = False,
                           block_n: int = BLOCK_N_STACKED,
                           block_q: int = BLOCK_Q):
    """Batched Hamming distances for the stacked multi-partition data plane.

    The kernel runs partition-major — blocks ``(1, BQ, G)``, ``(1, G, BN)``
    and ``(1, BQ, BN)`` — so the last two block dimensions meet the TPU's
    (8, 128) tiling: ``block_q`` must be a multiple of 8 and ``block_n`` of
    128, unless the array is smaller (then the block is the whole axis).

    Args:
      q_packed: (Q, P, G) uint32 — packed query bits, already standardized in
        each partition's binarization space (one word row per (query, part)).
      db_packed: (P, N, G) uint32 — stacked per-partition code rows (N padded
        to the partition row budget; padding rows are masked by the caller).
    Returns:
      (Q, P, N) int32 distances.
    """
    qn, p, g = q_packed.shape
    n = db_packed.shape[1]
    bq = min(block_q, max(int(qn), 1))
    bn = min(block_n, max(int(n), 1))
    q_pm = jnp.pad(jnp.transpose(q_packed, (1, 0, 2)),
                   ((0, 0), (0, (-qn) % bq), (0, 0)))         # (P, Qp, G)
    db_wm = jnp.pad(jnp.swapaxes(db_packed, 1, 2),
                    ((0, 0), (0, 0), (0, (-n) % bn)))         # (P, G, Np)
    qp, np_ = q_pm.shape[1], db_wm.shape[2]
    # Query blocks innermost: each row block is fetched once per partition
    # and re-used across the whole query-block axis.
    grid = (p, np_ // bn, qp // bq)
    out = pl.pallas_call(
        hamming_stacked_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, g), lambda j, l, i: (j, i, 0)),
            pl.BlockSpec((1, g, bn), lambda j, l, i: (j, 0, l)),
        ],
        out_specs=pl.BlockSpec((1, bq, bn), lambda j, l, i: (j, i, l)),
        out_shape=jax.ShapeDtypeStruct((p, qp, np_), jnp.int32),
        interpret=interpret,
    )(q_pm, db_wm)
    return jnp.transpose(out[:, :qn, :n], (1, 0, 2))


def packed_hamming_multi(q_packed, db_packed, *, interpret: bool = False,
                         block_n: int = BLOCK_N_STACKED,
                         block_q: int = BLOCK_Q):
    """(Q, G) queries vs one (N, G) code matrix → (Q, N) distances.

    Thin single-partition view of :func:`packed_hamming_stacked`.
    """
    out = packed_hamming_stacked(
        q_packed[:, None, :], db_packed[None], interpret=interpret,
        block_n=block_n, block_q=block_q,
    )
    return out[:, 0, :]
