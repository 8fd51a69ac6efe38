"""Peak table and the work each stage requires, from the cell's shapes.

The counts are of what a stage has to do, not of the passes a kernel makes,
so the yardstick stays put when a kernel is fused, re-tiled or replaced:

* Stage 3 (low-bit Hamming prune) reads the 1-bit stack, P·n_max·d/8
  bytes, and writes one int32 distance per (query, partition, row).
* Stage 4 (ADC lower bounds) makes one lookup-add per (query, partition,
  survivor, dimension): Q·P·keep_s·d. It reads the survivors' int32 codes
  and each pair's (M+1, d) float32 table, and writes one float32 per
  survivor.

A roofline time is the larger of ops over the chip's peak rate and bytes
over its HBM bandwidth. The published peaks are the MXU's; these stages run
on the vector unit, whose rate is lower, so the ops term is a floor and a
share read against it can only be understated.
"""

from __future__ import annotations

import json
import math
import os
from typing import Dict, NamedTuple

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


def peaks(device_kind: str) -> Dict[str, float]:
    """The published peaks of one chip of ``device_kind``; unknown raises."""
    with open(PEAKS_FILE) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(table)}")
    return table[device_kind]


class Work(NamedTuple):
    ops: float
    bytes: float


def keep_survivors(n_max: int, keep_percent: float, floor: int) -> int:
    """Static Hamming survivors per (query, partition): §2.4's H_perc."""
    return min(n_max, max(min(floor, n_max),
                          math.ceil(n_max * keep_percent / 100.0)))


def hamming_work(q: int, p: int, n_max: int, d: int) -> Work:
    """Stage 3 per plane call: XOR + popcount of each query word per row."""
    words = math.ceil(d / 32)
    return Work(ops=2.0 * q * p * n_max * words,
                bytes=p * n_max * d / 8 + 4.0 * q * p * n_max)


def adc_work(q: int, p: int, keep_s: int, d: int, m1: int) -> Work:
    """Stage 4 per plane call: one lookup-add per survivor and dimension."""
    survivors = q * p * keep_s
    return Work(ops=float(survivors * d),
                bytes=4.0 * survivors * d + 4.0 * q * p * m1 * d
                + 4.0 * survivors)


def roofline_seconds(work: Work, peak: Dict[str, float]):
    """(least seconds, "bytes" or "ops": the term that bounds it)."""
    t_ops = work.ops / peak["bf16_flops_per_s"]
    t_bytes = work.bytes / peak["hbm_bytes_per_s"]
    return (t_bytes, "bytes") if t_bytes >= t_ops else (t_ops, "ops")
