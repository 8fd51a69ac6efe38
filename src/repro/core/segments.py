"""Shared segment-based storage + dimensional extraction (paper §2.2.1–2.2.2).

Variable-length per-dimension bit codes are concatenated MSB-first into shared
S-bit segments: ``G_OSQ = ceil(b / S)`` segments per vector versus ``G_SQ = d``
fixed slots under standard SQ. Extraction recovers dimension ``j`` of *all*
rows simultaneously via static shift/mask/OR plans (paper Fig. 3), which are
jittable in JAX and have a Pallas TPU kernel twin in ``repro.kernels.bitpack``.

Bit-order convention: global bit position ``p`` (0-based from the start of the
vector's code stream) lives in segment ``p // S`` at MSB-based offset ``p % S``.
"""

from __future__ import annotations

import dataclasses
from typing import List, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

__all__ = [
    "SegmentLayout",
    "build_layout",
    "pack_codes",
    "extract_dim",
    "extract_all",
    "sq_wastage",
]

_DTYPES = {8: np.uint8, 16: np.uint16, 32: np.uint32}


@dataclasses.dataclass(frozen=True)
class Piece:
    """One contiguous chunk of a dimension's code inside a single segment."""

    seg: int      # segment index
    rshift: int   # right shift to land the piece at the LSB of the segment word
    nbits: int    # piece width
    lshift: int   # left shift to place the piece inside the dim code


@dataclasses.dataclass(frozen=True)
class SegmentLayout:
    """Static packing metadata shared by pack/extract."""

    bits: Tuple[int, ...]          # per-dim bit widths B
    seg_bits: int                  # S
    total_bits: int                # b = sum(B)
    num_segments: int              # G = ceil(b / S)
    offsets: Tuple[int, ...]       # per-dim global start bit
    plans: Tuple[Tuple[Piece, ...], ...]  # per-dim extraction plan

    @property
    def dtype(self):
        return _DTYPES[self.seg_bits]

    @property
    def d(self) -> int:
        return len(self.bits)


def build_layout(bits: Sequence[int], seg_bits: int = 8) -> SegmentLayout:
    if seg_bits not in _DTYPES:
        raise ValueError(f"seg_bits must be one of {sorted(_DTYPES)}")
    bits = tuple(int(b) for b in bits)
    offsets = []
    plans: List[Tuple[Piece, ...]] = []
    pos = 0
    for bj in bits:
        offsets.append(pos)
        pieces: List[Piece] = []
        covered = 0
        while covered < bj:
            p = pos + covered
            seg = p // seg_bits
            in_seg = p % seg_bits            # MSB-based offset inside segment
            take = min(bj - covered, seg_bits - in_seg)
            # piece occupies segment bits [in_seg, in_seg+take) (MSB-based)
            rshift = seg_bits - in_seg - take
            lshift = bj - covered - take     # placement inside the dim code
            pieces.append(Piece(seg=seg, rshift=rshift, nbits=take, lshift=lshift))
            covered += take
        plans.append(tuple(pieces))
        pos += bj
    total = pos
    g = -(-total // seg_bits) if total else 0
    return SegmentLayout(
        bits=bits,
        seg_bits=seg_bits,
        total_bits=total,
        num_segments=g,
        offsets=tuple(offsets),
        plans=tuple(plans),
    )


def pack_codes(layout: SegmentLayout, codes: np.ndarray) -> np.ndarray:
    """Pack (N, d) integer codes into (N, G) segments of ``layout.dtype``.

    Each piece of each dim's plan (the bits of one dim that fall in one
    segment, as :func:`extract_dim` reads them back) is ORed into its
    segment word; bits of a code above its dim's width are dropped.
    """
    codes = np.asarray(codes)
    n, d = codes.shape
    if d != layout.d:
        raise ValueError(f"dim mismatch {d} != {layout.d}")
    by_dim = np.ascontiguousarray(codes.T).astype(np.uint32)   # (d, N)
    out = np.zeros((layout.num_segments, n), dtype=np.uint32)
    for j, plan in enumerate(layout.plans):
        for piece in plan:
            out[piece.seg] |= (((by_dim[j] >> np.uint32(piece.lshift))
                                & np.uint32((1 << piece.nbits) - 1))
                               << np.uint32(piece.rshift))
    return np.ascontiguousarray(out.T).astype(layout.dtype)


def extract_dim(segments, layout: SegmentLayout, j: int):
    """Extract dimension ``j`` for all rows (paper Fig. 3). JAX-jittable.

    Left/right-shift semantics from the paper are realized as a single
    combined right shift + mask per overlapped segment, followed by a left
    shift into the residue position and a bitwise OR across segments.
    """
    segs = jnp.asarray(segments)
    wide = segs.astype(jnp.uint32)
    out = jnp.zeros(segs.shape[:-1], dtype=jnp.uint32)
    for piece in layout.plans[j]:
        chunk = (wide[..., piece.seg] >> piece.rshift) & ((1 << piece.nbits) - 1)
        out = out | (chunk << piece.lshift)
    return out.astype(jnp.int32)


def extract_all(segments, layout: SegmentLayout):
    """Extract every dimension: (N, G) segments -> (N, d) int32 codes."""
    cols = [extract_dim(segments, layout, j) for j in range(layout.d)]
    return jnp.stack(cols, axis=-1)


def sq_wastage(bits: Sequence[int], seg_bits: int = 8) -> dict:
    """Paper Fig. 2 quantities: bit wastage of standard SQ vs OSQ."""
    bits = np.asarray(list(bits), dtype=np.int64)
    b = int(bits.sum())
    g_osq = -(-b // seg_bits)
    g_sq = int(bits.shape[0])  # one S-bit slot per dim
    waste_sq = int(np.maximum(seg_bits - bits, 0).sum())
    waste_osq = g_osq * seg_bits - b
    return {
        "total_bits": b,
        "segments_osq": g_osq,
        "segments_sq": g_sq,
        "bits_sq": g_sq * seg_bits,
        "bits_osq": g_osq * seg_bits,
        "waste_sq": waste_sq,
        "waste_osq": waste_osq,
        "saving_ratio": (g_sq * seg_bits) / max(g_osq * seg_bits, 1),
    }
