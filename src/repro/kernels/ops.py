"""Public jit'd wrappers around the Pallas kernels.

On a TPU backend these dispatch compiled kernels; on any other backend they
run the same kernel bodies under ``interpret=True``. The switch is automatic
from ``jax.default_backend()``, overridable for tests.

The *batched* entry points (``hamming_stacked``, ``adc_batch``) feed the hot
query data plane (``repro.core.dataplane``), so they add a second switch:
``use_pallas``. On TPU the Pallas kernels run compiled; elsewhere the default
is the pure-jnp oracle from :mod:`repro.kernels.ref` — XLA fuses it well,
whereas the Pallas interpreter is an emulator and orders of magnitude slower.
Tests pass ``use_pallas=True, interpret=True`` to exercise the kernel bodies
on the CPU, and ``tests/test_tpu_compile.py`` compiles them for a described
v5e chip. Only the batched kernels are tiled for the TPU compiler;
``packed_hamming``, ``adc_lb_distances`` and ``extract_codes`` are refused by
it and serve tests and ``bench_kernels`` alone.
"""

from __future__ import annotations

from typing import Optional

import jax

from repro.core.segments import SegmentLayout
from repro.kernels import adc_lookup, bitpack, hamming, ref

__all__ = ["hamming_distances", "hamming_stacked", "adc_distances",
           "adc_batch", "extract_codes", "ssd_intra"]


def _interpret(override: Optional[bool]) -> bool:
    if override is not None:
        return override
    return jax.default_backend() != "tpu"


def _use_pallas(override: Optional[bool]) -> bool:
    if override is not None:
        return override
    return jax.default_backend() == "tpu"


def hamming_distances(q_packed, db_packed, *, interpret: Optional[bool] = None):
    """(G,) uint32 query vs (N, G) uint32 rows → (N,) int32 Hamming."""
    return hamming.packed_hamming(
        q_packed, db_packed, interpret=_interpret(interpret)
    )


def hamming_stacked(q_packed, db_packed, *, use_pallas: Optional[bool] = None,
                    interpret: Optional[bool] = None):
    """(Q, P, G) query words vs (P, N, G) stacked rows → (Q, P, N) int32."""
    if _use_pallas(use_pallas):
        return hamming.packed_hamming_stacked(
            q_packed, db_packed, interpret=_interpret(interpret)
        )
    return ref.hamming_stacked_ref(q_packed, db_packed)


def adc_distances(table, codes, *, sqrt: bool = True,
                  interpret: Optional[bool] = None):
    """(M+1, d) table + (N, d) codes → (N,) LB distances."""
    return adc_lookup.adc_lb_distances(
        table, codes, sqrt=sqrt, interpret=_interpret(interpret)
    )


def adc_batch(tables, codes, *, sqrt: bool = True,
              use_pallas: Optional[bool] = None,
              interpret: Optional[bool] = None):
    """(B, M+1, d) tables + (B, N, d) codes → (B, N) LB distances."""
    if _use_pallas(use_pallas):
        return adc_lookup.adc_lb_distances_batch(
            tables, codes, sqrt=sqrt, interpret=_interpret(interpret)
        )
    return ref.adc_lb_batch_ref(tables, codes, sqrt=sqrt)


def extract_codes(segments, layout: SegmentLayout, *,
                  interpret: Optional[bool] = None):
    """(N, G) packed segments → (N, d) int32 codes."""
    return bitpack.extract_codes(
        segments, layout, interpret=_interpret(interpret)
    )


def ssd_intra(c_mat, b_mat, da, x, *, interpret: Optional[bool] = None):
    """(G,lc,N)/(G,lc,N)/(G,H,lc)/(G,H,lc,P) → (G,H,lc,P) SSD intra-chunk."""
    from repro.kernels import ssd
    return ssd.ssd_intra_block(c_mat, b_mat, da, x,
                               interpret=_interpret(interpret))
