"""Pure-jnp oracles for every Pallas kernel (allclose targets in tests)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.segments import SegmentLayout, extract_all

__all__ = ["hamming_ref", "hamming_stacked_ref", "adc_lb_ref",
           "adc_lb_batch_ref", "extract_ref"]


def hamming_ref(q_packed, db_packed):
    """Oracle for kernels.hamming.packed_hamming."""
    x = jnp.bitwise_xor(db_packed, q_packed[None, :])
    return jnp.sum(jax.lax.population_count(x).astype(jnp.int32), axis=-1)


def hamming_stacked_ref(q_packed, db_packed):
    """Oracle for kernels.hamming.packed_hamming_stacked.

    q_packed: (Q, P, G) uint32; db_packed: (P, N, G) uint32 → (Q, P, N) i32.
    Also the XLA fast path the CPU jax backend dispatches to (kernels/ops.py).
    """
    x = jnp.bitwise_xor(db_packed[None], q_packed[:, :, None, :])
    return jnp.sum(jax.lax.population_count(x).astype(jnp.int32), axis=-1,
                   dtype=jnp.int32)


def adc_lb_ref(table, codes, sqrt: bool = True):
    """Oracle for kernels.adc_lookup.adc_lb_distances (gather formulation)."""
    t = jnp.asarray(table, dtype=jnp.float32)
    c = jnp.asarray(codes)
    picked = t[c, jnp.arange(c.shape[1])[None, :]]
    s = jnp.sum(picked, axis=-1)
    return jnp.sqrt(s) if sqrt else s


def adc_lb_batch_ref(tables, codes, sqrt: bool = True):
    """Oracle for kernels.adc_lookup.adc_lb_distances_batch.

    tables: (B, M+1, d) f32; codes: (B, N, d) int32 → (B, N) f32.
    Also the XLA fast path the CPU jax backend dispatches to (kernels/ops.py).
    """
    t = jnp.asarray(tables, dtype=jnp.float32)
    c = jnp.asarray(codes)
    picked = jnp.take_along_axis(t, c, axis=1)         # (B, N, d)
    s = jnp.sum(picked, axis=-1)
    return jnp.sqrt(s) if sqrt else s


def extract_ref(segments, layout: SegmentLayout):
    """Oracle for kernels.bitpack.extract_codes."""
    return extract_all(segments, layout)

