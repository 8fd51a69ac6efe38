"""Low-bit (binary) OSQ index for fast Hamming pruning (paper §2.4.3).

One bit per dimension: standardize, threshold at 0, pack 32 dims per uint32
lane via the OSQ segment scheme. Query→candidate Hamming distances are
XOR + popcount over packed words; the best ``H_perc`` % of candidates (ascending
Hamming order) survive to the fine-grained ADC stage.

The jnp implementation here is the reference; ``repro.kernels.hamming`` is the
Pallas TPU kernel twin (BlockSpec-tiled popcount on the VPU).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["LowBitIndex", "build_lowbit_index", "binarize", "pack_bits_u32",
           "hamming_distances", "hamming_prune"]


def binarize(x: np.ndarray, mean: np.ndarray, std: np.ndarray) -> np.ndarray:
    """Standardize then threshold around 0 (paper §2.4.3). Returns {0,1} int8."""
    z = (np.asarray(x, dtype=np.float64) - mean) / np.maximum(std, 1e-12)
    return (z > 0).astype(np.int8)


def pack_bits_u32(bits: np.ndarray) -> np.ndarray:
    """Pack (N, d) {0,1} into (N, ceil(d/32)) uint32, MSB-first per word."""
    bits = np.asarray(bits)
    n, d = bits.shape
    g = -(-d // 32)
    padded = np.zeros((n, g * 32), dtype=np.uint8)
    padded[:, :d] = bits != 0
    # Big-endian bytes, MSB-first bits: each 4-byte group is one word.
    return np.packbits(padded, axis=1).view(">u4").astype(np.uint32)


@dataclasses.dataclass
class LowBitIndex:
    """Packed binary codes + standardization stats."""

    packed: np.ndarray  # (N, G32) uint32
    mean: np.ndarray    # (d,)
    std: np.ndarray     # (d,)
    d: int

    def encode_queries(self, q: np.ndarray) -> np.ndarray:
        return pack_bits_u32(binarize(q, self.mean, self.std))


def build_lowbit_index(x: np.ndarray) -> LowBitIndex:
    x = np.asarray(x, dtype=np.float64)
    mean = x.mean(axis=0)
    std = x.std(axis=0)
    packed = pack_bits_u32(binarize(x, mean, std))
    return LowBitIndex(packed=packed, mean=mean, std=std, d=x.shape[1])


def hamming_distances(q_packed, db_packed):
    """Hamming distance between one packed query and all packed rows.

    Args:
      q_packed: (G,) uint32.
      db_packed: (N, G) uint32.
    Returns:
      (N,) int32 — Eq. 2, computed 32 dims per popcount lane.
    """
    x = jnp.bitwise_xor(db_packed, q_packed[None, :])
    return jnp.sum(jax.lax.population_count(x).astype(jnp.int32), axis=-1)


def hamming_prune(q_packed, db_packed, candidate_mask, keep: int):
    """Retain the ``keep`` best candidates by ascending Hamming distance.

    Non-candidates (mask 0) are pushed to +inf so they never survive. Returns
    (indices, distances) of the kept set, both length ``keep``.
    """
    dist = hamming_distances(q_packed, db_packed)
    big = jnp.iinfo(jnp.int32).max
    dist = jnp.where(candidate_mask.astype(bool), dist, big)
    neg_top, idx = jax.lax.top_k(-dist, keep)
    return idx, -neg_top
