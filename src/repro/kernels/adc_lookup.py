"""Pallas TPU kernel: ADC lookup-table LB distances (paper §2.4.4).

The paper's "advanced indexing" — ``Σ_j L[code[i,j], j]`` — is a scalar gather
stream on TPU, which is slow. The TPU-native adaptation (DESIGN.md §2) turns
each block's lookups into dense tile work. The single-table kernel builds a
one-hot and lets the MXU compute a matvec:

    acc[i] = onehot(codes_block)[i, (j,m)] · L_flat[(j,m)]

The batched kernel the data plane runs walks the cells instead and adds
table row ``m`` wherever a code equals ``m`` (a lane-dense select per cell).

Grids tile rows × dims with a VMEM accumulator over the dim axis.

Target: TPU (MXU for the single-table kernel, VPU for the batched one);
validated on CPU via ``interpret=True``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["adc_kernel", "adc_lb_distances", "adc_batch_kernel",
           "adc_lb_distances_batch"]

BLOCK_N = 256
BLOCK_D = 16
BLOCK_N_BATCH = 256   # survivor rows per step of the batched kernel (sublanes)
BLOCK_D_BATCH = 128   # dims per step of the batched kernel (lanes)


def adc_kernel(codes_ref, table_ref, out_ref):
    """One (row-block, dim-block) step: accumulate partial LB sums.

    codes_ref: (BLOCK_N, BLOCK_D) int32 cell indices.
    table_ref: (M1, BLOCK_D) f32 per-dim boundary distance columns.
    out_ref:   (BLOCK_N,) f32 accumulator (summed over dim-block grid axis).
    """
    codes = codes_ref[...]
    table = table_ref[...]                       # (M1, BD)
    m1 = table.shape[0]
    # One-hot over cells: (BN, BD, M1) — flattened to drive the MXU as a
    # (BN, BD·M1) × (BD·M1,) matvec.
    onehot = (codes[:, :, None] == jax.lax.broadcasted_iota(
        jnp.int32, (1, 1, m1), 2)).astype(table.dtype)
    flat = onehot.reshape(codes.shape[0], -1)    # (BN, BD*M1)
    tflat = table.T.reshape(-1)                  # (BD*M1,)
    partial = jnp.dot(flat, tflat, preferred_element_type=jnp.float32)
    dstep = pl.program_id(1)

    @pl.when(dstep == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += partial


@functools.partial(
    jax.jit, static_argnames=("interpret", "block_n", "block_d", "sqrt")
)
def adc_lb_distances(table, codes, *, interpret: bool = False,
                     block_n: int = BLOCK_N, block_d: int = BLOCK_D,
                     sqrt: bool = True):
    """LB distances for all candidate rows.

    Args:
      table: (M+1, d) f32 — per-query boundary-distance table (padding rows
        must be finite; callers zero the +inf padding — one-hot never selects
        rows ≥ C[j] for valid codes anyway).
      codes: (N, d) int32 quantized cells.
    Returns:
      (N,) f32 — sqrt of the per-row table sums (set ``sqrt=False`` for the
      squared form used when only ordering matters).
    """
    n, d = codes.shape
    m1 = table.shape[0]
    bn = min(block_n, max(int(n), 1))
    bd = min(block_d, d)
    pad_n = (-n) % bn
    pad_d = (-d) % bd
    if pad_n or pad_d:
        # Padding dims point at table column 0 of padded columns, which are 0.
        codes = jnp.pad(codes, ((0, pad_n), (0, pad_d)))
        table = jnp.pad(table, ((0, 0), (0, pad_d)))
    np_, dp = codes.shape
    grid = (np_ // bn, dp // bd)
    out = pl.pallas_call(
        adc_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, bd), lambda i, j: (i, j)),
            pl.BlockSpec((m1, bd), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bn,), lambda i, j: (i,)),
        out_shape=jax.ShapeDtypeStruct((np_,), jnp.float32),
        interpret=interpret,
    )(codes, table.astype(jnp.float32))
    out = out[:n]
    return jnp.sqrt(out) if sqrt else out


def adc_batch_kernel(codes_ref, table_ref, out_ref):
    """One (batch, row-block, dim-block) step of the batched ADC lookup.

    codes_ref: (1, BN, BD) int32 cell indices for this batch item.
    table_ref: (1, M1, BD) f32 — this batch item's lookup-table columns.
    out_ref:   (1, BN, 1) f32 accumulator over the dim-block grid axis.

    A select-accumulate over cells instead of a one-hot matvec: for cell m,
    every (row, dim) whose code is m adds table row m. That is the same VPU
    work a one-hot build costs, keeps no (BN, BD·M1) tile in VMEM, and adds
    in float32 throughout, with no matmul whose precision could drop.
    """
    codes = codes_ref[0]                          # (BN, BD)

    def cell(m, acc):
        row = table_ref[0, pl.ds(m, 1), :]        # (1, BD)
        return acc + jnp.where(codes == m, row, 0.0)

    acc = jax.lax.fori_loop(0, table_ref.shape[1], cell,
                            jnp.zeros(codes.shape, jnp.float32))
    partial = jnp.sum(acc, axis=-1, keepdims=True)  # (BN, 1)

    @pl.when(pl.program_id(2) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[0] += partial


@functools.partial(
    jax.jit, static_argnames=("interpret", "block_n", "block_d", "sqrt")
)
def adc_lb_distances_batch(tables, codes, *, interpret: bool = False,
                           block_n: int = BLOCK_N_BATCH,
                           block_d: int = BLOCK_D_BATCH,
                           sqrt: bool = True):
    """LB distances for a batch of (query×partition) lookup problems.

    The batched query data plane evaluates one per-(query, partition) table
    against that pair's Hamming-surviving code rows; the grid walks
    (batch, row-block, dim-block) so every (table, codes) pair streams once.
    Blocks are ``(1, BN, BD)``, ``(1, M+1, BD)`` and ``(1, BN, 1)``: to meet
    the TPU's (8, 128) tiling ``block_n`` is a multiple of 8 and ``block_d``
    of 128, unless the array is smaller (then the block is the whole axis).

    Args:
      tables: (B, M+1, d) f32 per-pair boundary-distance tables (finite
        entries only — callers zero the +inf padding).
      codes: (B, N, d) int32 quantized cells of each pair's survivors.
    Returns:
      (B, N) f32 LB distances (``sqrt=False`` for the squared form).
    """
    b, n, d = codes.shape
    m1 = tables.shape[1]
    bn = min(block_n, max(int(n), 1))
    bd = min(block_d, d)
    pad_n = (-n) % bn
    pad_d = (-d) % bd
    if pad_n or pad_d:
        # Padded dims select cell 0 of an all-zero table column.
        codes = jnp.pad(codes, ((0, 0), (0, pad_n), (0, pad_d)))
        tables = jnp.pad(tables, ((0, 0), (0, 0), (0, pad_d)))
    np_, dp = codes.shape[1], codes.shape[2]
    grid = (b, np_ // bn, dp // bd)
    out = pl.pallas_call(
        adc_batch_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bn, bd), lambda b_, i, j: (b_, i, j)),
            pl.BlockSpec((1, m1, bd), lambda b_, i, j: (b_, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, bn, 1), lambda b_, i, j: (b_, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, np_, 1), jnp.float32),
        interpret=interpret,
    )(codes, tables.astype(jnp.float32))
    out = out[:, :n, 0]
    return jnp.sqrt(out) if sqrt else out
