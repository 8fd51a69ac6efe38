"""Compile the data plane's kernels for a described TPU v5e, without a chip.

The CPU suite runs the Pallas kernels under ``interpret=True``, which accepts
block shapes the TPU compiler refuses. These tests compile them — and the
whole jitted plane, single-chip and partition-sharded over four chips — for a
``v5e:2x2`` topology at the sift1m deployment's shapes, so a tiling, VMEM or
memory regression fails here instead of on the chip. Nothing runs.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library, and every xdist worker imports
this file.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import dataplane, distributed
from repro.kernels import adc_lookup, hamming, ops

# sift1m at full size (N = 1M, d = 128) over P = 10 partitions, a Q = 16
# batch, default SquashConfig: 12-bit hot dims give M+1 = 4097 cells, cut
# into D' = 256 Stage 4 lanes of 128 cells (up to 128 chunk lanes), the
# Hamming keep is 10% of the partition and the refine take R·k = 20.
Q, PARTS, N_MAX, D, LANES, G = 16, 10, 100_000, 128, 256, 4
KEEP_S, TAKE_S, K = 10_000, 20, 10
ROWS = dataplane.LANE_CELLS + 1


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def f32():
    """float32 with x64 off, and no persistent cache entries that a
    chip-less process could not read back; both restored afterwards."""
    from jax.experimental.compilation_cache import compilation_cache

    x64 = jax.config.jax_enable_x64
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_x64", False)
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_x64", x64)
    jax.config.update("jax_enable_compilation_cache", cache)
    compilation_cache.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _stacked(p, n_max, lanes, sharding, d=D, g=G) -> dataplane.StackedIndex:
    f, i = jnp.float32, jnp.int32
    return dataplane.StackedIndex(
        low_packed=_sds((p, n_max, g), jnp.uint32, sharding),
        codes=_sds((p, n_max, d), i, sharding),
        vectors=_sds((p, n_max, d), f, sharding),
        valid=_sds((p, n_max), jnp.bool_, sharding),
        vector_ids=_sds((p, n_max), i, sharding),
        part_mean=_sds((p, d), f, sharding),
        klt=_sds((p, d, d), f, sharding),
        low_mean=_sds((p, d), f, sharding),
        low_std=_sds((p, d), f, sharding),
        cells=_sds((p, d), i, sharding),
        lane_dim=_sds((p, lanes), i, sharding),
        lane_base=_sds((p, lanes), i, sharding),
        lane_bounds=_sds((p, ROWS, lanes), f, sharding),
    )


def _kernel_calls(text: str) -> int:
    return text.count('custom_call_target="tpu_custom_call"')


def test_hamming_stacked_compiles_at_sift1m_shape(f32, one_chip):
    fn = jax.jit(lambda q, db: ops.hamming_stacked(q, db, use_pallas=True,
                                                   interpret=False))
    compiled = fn.lower(_sds((Q, PARTS, G), jnp.uint32, one_chip),
                        _sds((PARTS, 100_352, G), jnp.uint32,
                             one_chip)).compile()
    assert _kernel_calls(compiled.as_text()) == 1
    assert compiled.out_info.shape == (Q, PARTS, 100_352)


def test_adc_batch_compiles_at_129_cells(f32, one_chip):
    """The lane table (128 cells and the zero row) with D' = d lanes, at a
    keep of 1024 survivors for each of Q·P = 160 (query, partition) pairs."""
    fn = jax.jit(lambda t, c: ops.adc_batch(t, c, use_pallas=True,
                                            interpret=False))
    compiled = fn.lower(_sds((160, 129, D), jnp.float32, one_chip),
                        _sds((160, 1024, D), jnp.int32, one_chip)).compile()
    assert _kernel_calls(compiled.as_text()) == 1
    assert compiled.out_info.shape == (160, 1024)


def test_adc_batch_compiles_at_256_lanes(f32, one_chip):
    """Two lane blocks: d = 128 lanes and up to 128 chunk lanes of hot dims."""
    fn = jax.jit(lambda t, c: ops.adc_batch(t, c, use_pallas=True,
                                            interpret=False))
    compiled = fn.lower(_sds((160, ROWS, LANES), jnp.float32, one_chip),
                        _sds((160, 1024, LANES), jnp.int32, one_chip)
                        ).compile()
    assert _kernel_calls(compiled.as_text()) == 1
    assert compiled.out_info.shape == (160, 1024)


def _compile_plane(lanes, sharding):
    plane = dataplane.make_plane(k=K, keep_s=KEEP_S, take_s=TAKE_S,
                                 use_pallas=True, interpret=False)
    return plane.lower(
        _sds((Q, D), jnp.float32, sharding),
        _stacked(PARTS, N_MAX, lanes, sharding),
        _sds((Q, PARTS, N_MAX), jnp.bool_, sharding),
        _sds((Q, PARTS), jnp.int32, sharding),
        _sds((Q, PARTS), jnp.int32, sharding),
    ).compile()


def test_whole_plane_compiles_and_fits_one_chip(f32, one_chip):
    """M+1 = 4097 on D' = 256 lanes: Stage 3 and Stage 4 both run their
    Pallas kernels, and nothing else does."""
    compiled = _compile_plane(LANES, one_chip)
    assert _kernel_calls(compiled.as_text()) == 2   # Hamming + ADC
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used < 12 * 2**30, used                  # of a v5e's 16 GiB


def test_whole_plane_compiles_with_identity_lanes(f32, one_chip):
    """Every dim at 128 cells or fewer (the 7-bit index): D' = d."""
    compiled = _compile_plane(D, one_chip)
    assert _kernel_calls(compiled.as_text()) == 2
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 12 * 2**30


def test_sharded_plane_compiles_on_four_chips(f32, topo, monkeypatch):
    """Partitions sharded over a (data=1, model=4) mesh; P padded 10 → 12.

    The shard body asks ``jax.default_backend()`` (the CPU here) whether to
    run the Pallas kernels, so the test steers that choice to the chip's.
    """
    monkeypatch.setattr(ops, "_use_pallas",
                        lambda o: True if o is None else o)
    monkeypatch.setattr(ops, "_interpret",
                        lambda o: False if o is None else o)
    mesh = Mesh(np.array(topo.devices[:4]).reshape(1, 4), ("data", "model"))
    search = distributed.make_search_fn(mesh, k=K, keep_s=KEEP_S,
                                        take_s=TAKE_S)
    parts = 12
    by_query = NamedSharding(mesh, P("data"))
    by_pair = NamedSharding(mesh, P("data", "model"))
    compiled = jax.jit(search).lower(
        _sds((Q, D), jnp.float32, by_query),
        _sds((Q, parts, N_MAX), jnp.bool_, by_pair),
        _sds((Q, parts), jnp.int32, by_pair),
        _sds((Q, parts), jnp.int32, by_pair),
        _stacked(parts, N_MAX, LANES, NamedSharding(mesh, P("model"))),
    ).compile()
    text = compiled.as_text()
    assert "all-gather" in text
    assert _kernel_calls(text) == 2                 # Hamming + ADC
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 12 * 2**30


# GIST1M over four chips (bench/configs/gist1m-sharded4.json): N = 1M,
# d = 960 (G = 30 words of 1-bit codes) over P = 12 partitions, three a
# chip; n_max is the balanced k-means cap, ceil(1.05 · N / P), and the keep
# 10% of it. Builds of the stand-in at d = 960 use up to ~230 chunk lanes a
# partition, so D' = 1280.
GIST_PARTS, GIST_N_MAX, GIST_D, GIST_G, GIST_LANES = 12, 87_500, 960, 30, 1280
GIST_KEEP_S = 8_750


def test_gist1m_sharded_plane_compiles_on_four_chips(f32, topo, monkeypatch):
    """The served sharded plane at GIST1M's shapes: both Pallas kernels at
    30 words a row and 1280 lanes, one all-gather, and each chip's
    arguments plus temporaries within 12 GiB of its 16."""
    monkeypatch.setattr(ops, "_use_pallas",
                        lambda o: True if o is None else o)
    monkeypatch.setattr(ops, "_interpret",
                        lambda o: False if o is None else o)
    mesh = Mesh(np.array(topo.devices[:4]).reshape(1, 4), ("data", "model"))
    search = distributed.make_search_fn(mesh, k=K, keep_s=GIST_KEEP_S,
                                        take_s=TAKE_S)
    by_query = NamedSharding(mesh, P("data"))
    by_pair = NamedSharding(mesh, P("data", "model"))
    compiled = jax.jit(search).lower(
        _sds((Q, GIST_D), jnp.float32, by_query),
        _sds((Q, GIST_PARTS, GIST_N_MAX), jnp.bool_, by_pair),
        _sds((Q, GIST_PARTS), jnp.int32, by_pair),
        _sds((Q, GIST_PARTS), jnp.int32, by_pair),
        _stacked(GIST_PARTS, GIST_N_MAX, GIST_LANES,
                 NamedSharding(mesh, P("model")), d=GIST_D, g=GIST_G),
    ).compile()
    text = compiled.as_text()
    assert text.count("all-gather-start") + text.count(" all-gather(") >= 1
    assert _kernel_calls(text) == 2                 # Hamming + ADC
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used < 12 * 2**30, (mem.argument_size_in_bytes,
                               mem.temp_size_in_bytes)
