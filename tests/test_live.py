"""Mutation regression tier: the LiveIndex subsystem (ISSUE 9).

Pins the live-index parity contract — insert/delete/compact keep ids *and*
``SearchStats`` bitwise-identical across the numpy, jax and serverless
backends — plus the stale-retention fixes the subsystem exposed in the
DRE/cache layer:

* tombstoned ids are never returned, even by hand-built Stage 3 requests;
* search during the tombstone phase ≡ search after compaction;
* per-partition generations stale warm-container fetch/derived keys;
* ``invalidate_cache()`` denies both fetch-level and derived DRE hits;
* ``ResultCache`` invalidation is segment-granular (only touched
  partitions' entries evict) and a zero-capacity cache rejects up front;
* ``ContainerPool.release`` is idempotent under many idle containers and
  ``derived_hit`` routes accounting through the lease delta exactly once.

Auto-marked ``mutation`` (conftest); the process/socket parity tests are
additionally marked ``transport`` so tier-1 (``-m "not transport"``) skips
the worker-spawning ones.
"""

import numpy as np
import pytest

from repro.core import dataplane
from repro.core.dre import ContainerPool, ResultCache
from repro.core.live import LiveIndex, SegmentBlock
from repro.core.pipeline import SearchStats, SquashConfig, SquashIndex
from repro.data import synthetic
from repro.serverless import RuntimeConfig, ServerlessRuntime
from repro.serverless import workers as wk

from _traffic import assert_guarantees, cell_predicates


def _build(num_partitions=5, scale=0.002, seed=9, **cfg_kw):
    ds = synthetic.make_vector_dataset("sift1m", scale=scale, num_queries=6,
                                       seed=seed)
    cfg = SquashConfig(num_partitions=num_partitions, kmeans_iters=4,
                       lloyd_iters=6, **cfg_kw)
    index = SquashIndex.build(ds.vectors, ds.attributes, cfg, seed=seed)
    return ds, index


def _stats_eq(a: SearchStats, b: SearchStats) -> bool:
    return a.__dict__ == b.__dict__


def _all_backends(index, queries, preds, k=10):
    """(ids, dists, stats) from numpy and jax, asserted bitwise-identical."""
    rn = index.search(queries, preds, k=k, backend="numpy")
    rj = index.search(queries, preds, k=k, backend="jax")
    np.testing.assert_array_equal(rn[0], rj[0])
    np.testing.assert_allclose(rn[1], rj[1], rtol=0, atol=1e-9)
    assert _stats_eq(rn[2], rj[2])
    return rn


# ------------------------------------------------------------- wrap basics

def test_wrap_sets_mask_and_double_wrap_raises(rng):
    _, index = _build()
    live = LiveIndex(index)
    assert index.live_mask is not None and index.live_mask.all()
    assert index.live_owner is live
    assert live.version == 0
    assert live.dirty_partitions() == ()
    with pytest.raises(ValueError):
        LiveIndex(index)


def test_frozen_wrap_is_search_invisible(rng):
    """Wrapping alone (no mutation) changes nothing about search."""
    ds, index = _build()
    before = index.search(ds.queries, [], k=10, backend="jax")
    LiveIndex(index)
    after = _all_backends(index, ds.queries, [], k=10)
    np.testing.assert_array_equal(before[0], after[0])
    assert _stats_eq(before[2], after[2])


# ---------------------------------------------------------------- inserts

def test_insert_appends_tail_segment_and_is_searchable(rng):
    ds, index = _build()
    live = LiveIndex(index)
    segs0 = {pid: live.segments_of(pid) for pid in range(live.num_partitions)}
    new_vecs = ds.vectors[:4] + 1e-4 * rng.normal(size=(4, index.dim))
    new_ids = live.insert(new_vecs, ds.attributes[:4])
    assert new_ids.tolist() == list(range(ds.vectors.shape[0],
                                          ds.vectors.shape[0] + 4))
    # the touched partitions grew a tail block under generation + 1
    touched = set(index.partitioning.assign[new_ids].tolist())
    for pid in range(live.num_partitions):
        segs = live.segments_of(pid)
        if pid in touched:
            assert len(segs) == len(segs0[pid]) + 1
            tail = segs[-1]
            assert isinstance(tail, SegmentBlock)
            assert tail.hi - tail.lo == int(
                (index.partitioning.assign[new_ids] == pid).sum())
            assert tail.generation == live.generations[pid]
        else:
            assert segs == segs0[pid]
    # near-duplicates of existing rows must surface as top hits
    ids, _, _ = _all_backends(index, new_vecs, [], k=10)
    for row, gid in zip(ids, new_ids):
        assert gid in row


def test_insert_attr_encoding_matches_build_codes(rng):
    """Re-inserting rows with build-time attribute values reproduces their
    original attribute codes exactly (Stage 1 parity for new rows)."""
    ds, index = _build()
    live = LiveIndex(index)
    src = rng.choice(ds.vectors.shape[0], size=8, replace=False)
    new_ids = live.insert(ds.vectors[src], ds.attributes[src])
    np.testing.assert_array_equal(index.attr_index.codes[new_ids],
                                  index.attr_index.codes[src])


def test_insert_parity_with_predicates(rng):
    ds, index = _build()
    preds = synthetic.default_predicates(ds.attr_cardinality)
    live = LiveIndex(index)
    live.insert(ds.vectors[:6] + 1e-3, ds.attributes[:6])
    _all_backends(index, ds.queries, preds, k=10)


# ---------------------------------------------------------------- deletes

def test_deleted_ids_never_returned_any_backend(rng):
    ds, index = _build()
    live = LiveIndex(index)
    first = index.search(ds.queries, [], k=10, backend="jax")
    victims = np.unique(first[0][:, :3].ravel())
    assert live.delete(victims) == victims.size
    assert live.delete(victims) == 0          # idempotent
    ids, _, _ = _all_backends(index, ds.queries, [], k=10)
    assert np.intersect1d(ids.ravel(), victims).size == 0
    assert live.live_count() == ds.vectors.shape[0] - victims.size


def test_delete_changes_filter_pass_only_through_mask(rng):
    """Stage 1 counts live rows: filter_pass drops by exactly the number of
    predicate-passing tombstones, identically on both backends."""
    ds, index = _build()
    live = LiveIndex(index)
    r0 = index.search(ds.queries, [], k=10, backend="numpy")
    victims = r0[0][:, 0]
    live.delete(victims)
    r1 = _all_backends(index, ds.queries, [], k=10)
    lost = np.unique(victims).size * ds.queries.shape[0]
    assert r0[2].filter_pass - r1[2].filter_pass == lost


def test_stage3_numpy_defense_masks_hand_built_rows(rng):
    """A raw ``_search_partition`` call naming tombstoned local rows still
    never returns them (defense in depth beyond Stage 1)."""
    ds, index = _build()
    live = LiveIndex(index)
    pid = 0
    part = index.parts[pid]
    dead = part.vector_ids[: max(3, part.size // 4)]
    live.delete(dead)
    stats = SearchStats()
    ids, _ = index._search_partition(
        part, pid, ds.queries[0], np.arange(part.size), k=10, stats=stats)
    assert np.intersect1d(ids, dead).size == 0

    # all-dead request degenerates to an empty stream, not an error
    live.delete(part.vector_ids)
    ids2, d2 = index._search_partition(
        part, pid, ds.queries[0], np.arange(part.size), k=10,
        stats=SearchStats())
    assert ids2.size == 0 and d2.size == 0


def test_stage3_jax_valid_fold_masks_tombstones(rng):
    """The stacked device payload folds the tombstone bitmap into ``valid``,
    so even a full candidate mask cannot surface a dead row."""
    ds, index = _build()
    live = LiveIndex(index)
    first = index.search(ds.queries, [], k=10, backend="jax")
    live.delete(first[0][:, 0])
    stacked = dataplane.stack_index(index)
    for pid, part in enumerate(index.parts):
        valid = np.asarray(stacked.valid[pid][: part.size])
        np.testing.assert_array_equal(valid,
                                      index.live_mask[part.vector_ids])


def test_qp_bundle_folds_tombstones(rng):
    """Serverless QP slabs ship tombstones pre-folded: a hand-built request
    naming dead rows cannot return them from a worker either."""
    import jax.numpy as jnp

    ds, index = _build()
    live = LiveIndex(index)
    first = index.search(ds.queries, [], k=10, backend="jax")
    live.delete(first[0][:, 0])
    for pid, part in enumerate(index.parts):
        bundle = wk.build_qp_bundle(index, pid, jnp.float64)
        valid = np.asarray(bundle["part_arrays"]["valid"][: part.size])
        np.testing.assert_array_equal(valid,
                                      index.live_mask[part.vector_ids])


# ------------------------------------------------------------- compaction

def test_compact_clean_partition_is_noop(rng):
    _, index = _build()
    live = LiveIndex(index)
    gens0 = list(live.generations)
    assert live.compact(0) is False
    assert live.generations == gens0
    assert live.version == 0


def test_drop_only_compact_is_bitwise_invisible(rng):
    """The tentpole gate (in-process half): search during the tombstone
    phase ≡ search after compaction — ids, dists and every stage counter."""
    ds, index = _build()
    live = LiveIndex(index)
    first = index.search(ds.queries, [], k=10, backend="jax")
    live.delete(np.unique(first[0][:, :2].ravel()))
    during = _all_backends(index, ds.queries, [], k=10)
    for pid in live.dirty_partitions():
        assert live.compact(pid, requantize=False) is True
    assert live.dirty_partitions() == ()
    after = _all_backends(index, ds.queries, [], k=10)
    np.testing.assert_array_equal(during[0], after[0])
    np.testing.assert_array_equal(during[1], after[1])
    assert _stats_eq(during[2], after[2])
    # dead rows are physically gone and sentinel-assigned
    n_resident = sum(pt.size for pt in index.parts)
    assert n_resident == live.live_count()
    assert (index.partitioning.assign == live.sentinel).sum() == \
        ds.vectors.shape[0] - live.live_count()


def test_requantize_compact_exact_results_match(rng):
    """Requantization changes codes but not geometry: under exhaustive
    refinement (take = keep = all candidates) the exact top-k is identical
    before and after the OSQ re-run."""
    ds, index = _build(num_partitions=5, hamming_perc=100.0,
                       refine_ratio=8.0)
    live = LiveIndex(index)
    first = index.search(ds.queries, [], k=10, backend="jax")
    live.delete(np.unique(first[0][:, :2].ravel()))
    during = _all_backends(index, ds.queries, [], k=10)
    for pid in live.dirty_partitions():
        assert live.compact(pid, requantize=True) is True
    after = _all_backends(index, ds.queries, [], k=10)
    np.testing.assert_array_equal(during[0], after[0])
    np.testing.assert_allclose(during[1], after[1], rtol=0, atol=1e-9)
    # segment ledger collapsed to one block under the bumped generation
    for pid in range(live.num_partitions):
        segs = live.segments_of(pid)
        assert len(segs) == 1
        assert segs[0].generation == live.generations[pid]


def test_generations_bump_on_every_mutation(rng):
    ds, index = _build()
    live = LiveIndex(index)
    v0 = live.version
    new = live.insert(ds.vectors[:2] + 1e-3, ds.attributes[:2])
    touched = set(index.partitioning.assign[new].tolist())
    assert live.version == v0 + 1
    for pid in range(live.num_partitions):
        assert live.generations[pid] == (1 if pid in touched else 0)
    gens = list(live.generations)
    live.delete(new[:1])
    pid = int(index.partitioning.assign[new[0]])
    assert live.generations[pid] == gens[pid] + 1
    assert live.compact(pid, requantize=False) is True
    assert live.generations[pid] == gens[pid] + 2
    _, events = live.events_since(0)
    assert [e.kind for e in events] == ["insert", "delete", "compact"]
    cursor, tail = live.events_since(events[1].seq)
    assert [e.kind for e in tail] == ["compact"] and cursor == live.version


def test_residency_bitmap_tolerates_sentinel(rng):
    ds, index = _build()
    live = LiveIndex(index)
    first = index.search(ds.queries, [], k=10, backend="jax")
    live.delete(first[0][:, 0])
    for pid in live.dirty_partitions():
        live.compact(pid, requantize=False)
    pv = index.partitioning.residency_bitmap()
    assert pv.shape[1] == index.partitioning.assign.shape[0]
    # compacted-away rows are resident nowhere; live rows in exactly one pid
    resident = pv.any(axis=0)
    np.testing.assert_array_equal(resident, index.live_mask)


# ---------------------------------------------- serverless runtime parity

@pytest.mark.parametrize("width", [3, 9, 16])
def test_serverless_search_under_mutation_parity_local(seed, width):
    """The tentpole acceptance gate, local transport: the same runtime
    (warm pools, caches) tracks insert → delete → compact and stays
    bitwise-identical to a fresh in-process search at every step, under
    the cells' predicates at three widths. The tombstone rule lives in the
    one host planner both paths call."""
    ds, index = _build()
    preds = cell_predicates(width, seed)
    attrs = np.concatenate([ds.attributes, ds.attributes[:3]])
    live = LiveIndex(index)
    rt = ServerlessRuntime(live, RuntimeConfig(cache_enabled=False))
    try:
        r0 = rt.search(ds.queries, preds, k=10)
        ref0 = index.search(ds.queries, preds, k=10, backend="jax")
        np.testing.assert_array_equal(r0.ids, ref0[0])
        assert _stats_eq(r0.stats, ref0[2])
        assert_guarantees(r0.ids, ds.attributes, preds, 10, index.live_mask)

        live.insert(ds.vectors[:3] + 1e-3, ds.attributes[:3])
        first = r0.ids[:, 0][r0.ids[:, 0] >= 0]
        live.delete(first)
        during = rt.search(ds.queries, preds, k=10)
        refd = index.search(ds.queries, preds, k=10, backend="jax")
        np.testing.assert_array_equal(during.ids, refd[0])
        assert _stats_eq(during.stats, refd[2])
        assert np.intersect1d(during.ids.ravel(), first).size == 0
        assert_guarantees(during.ids, attrs, preds, 10, index.live_mask)

        for pid in live.dirty_partitions():
            live.compact(pid, requantize=False)
        after = rt.search(ds.queries, preds, k=10)
        np.testing.assert_array_equal(after.ids, during.ids)
        np.testing.assert_array_equal(after.dists, during.dists)
        assert _stats_eq(after.stats, during.stats)
    finally:
        rt.close()


def test_mutation_forces_refetch_untouched_stay_warm(rng):
    """Per-partition generations in the fetch keys: after a delete the
    touched partitions' warm containers refetch, untouched ones keep their
    fetch-level DRE hits."""
    ds, index = _build(num_partitions=5)
    live = LiveIndex(index)
    rt = ServerlessRuntime(live, RuntimeConfig())
    try:
        r1 = rt.search(ds.queries, [], k=10)
        r2 = rt.search(ds.queries, [], k=10)
        assert r2.trace.dre.s3_gets == 0
        assert r2.trace.dre.dre_hits == r2.trace.dre.invocations

        victim = int(r1.ids[0, -1])
        live.delete([victim])
        r3 = rt.search(ds.queries, [], k=10)
        assert r3.trace.dre.s3_gets > 0, "touched partition must refetch"
        assert r3.trace.dre.dre_hits > 0, "untouched partitions stay warm"
    finally:
        rt.close()


def test_cache_survives_drop_only_compact(rng):
    """Drop-only compaction is bitwise-invisible, so the §5.6 cache keeps
    its entries — and serving them is still correct."""
    ds, index = _build()
    live = LiveIndex(index)
    rt = ServerlessRuntime(live, RuntimeConfig(cache_enabled=True))
    try:
        r0 = rt.search(ds.queries, [], k=10)
        live.delete(r0.ids[:, 0])
        r1 = rt.search(ds.queries, [], k=10)   # repopulates post-delete
        for pid in live.dirty_partitions():
            live.compact(pid, requantize=False)
        r2 = rt.search(ds.queries, [], k=10)
        assert r2.trace.cache_hits == ds.queries.shape[0]
        np.testing.assert_array_equal(r2.ids, r1.ids)
        ref = index.search(ds.queries, [], k=10, backend="jax")
        np.testing.assert_array_equal(r2.ids, ref[0])
    finally:
        rt.close()


def test_cache_invalidation_is_segment_granular(rng):
    """A delete only evicts cache entries whose dependency set touches the
    mutated partitions; other entries keep serving hits."""
    ds, index = _build(num_partitions=6)
    live = LiveIndex(index)
    rt = ServerlessRuntime(live, RuntimeConfig(cache_enabled=True))
    try:
        cents = index.partitioning.centroids
        r = rt.search(cents, [], k=10)
        assert (r.ids >= 0).all(), "test needs fully-filled entries"
        assign = index.partitioning.assign
        deps = [frozenset(assign[row].tolist()) for row in r.ids]
        pair = next(((i, j) for i in range(len(deps))
                     for j in range(len(deps)) if not (deps[i] & deps[j])),
                    None)
        assert pair is not None, "need two queries with disjoint deps"
        qa, qb = pair
        # delete a result of query A → A's entry evicts, B's survives
        live.delete([int(r.ids[qa, -1])])
        ra = rt.search(cents[qa][None, :], [], k=10)
        assert ra.trace.cache_hits == 0
        rb = rt.search(cents[qb][None, :], [], k=10)
        assert rb.trace.cache_hits == 1
        np.testing.assert_array_equal(rb.ids[0], r.ids[qb])
        assert rt.result_cache.targeted_evictions > 0
    finally:
        rt.close()


def test_insert_evicts_only_displaced_entries(rng):
    """An insert far from every cached query's kth-neighbor radius evicts
    nothing; a near-duplicate of a cached top hit evicts that entry."""
    ds, index = _build()
    live = LiveIndex(index)
    rt = ServerlessRuntime(live, RuntimeConfig(cache_enabled=True))
    try:
        r = rt.search(ds.queries, [], k=10)
        assert (r.ids >= 0).all()
        far = ds.vectors.max(axis=0) + 100.0
        live.insert(far[None, :], ds.attributes[:1])
        r2 = rt.search(ds.queries, [], k=10)
        assert r2.trace.cache_hits == ds.queries.shape[0]

        near = ds.vectors[r.ids[0, 0]] + 1e-6
        live.insert(near[None, :], ds.attributes[r.ids[0, 0]][None, :])
        r3 = rt.search(ds.queries[:1], [], k=10)
        assert r3.trace.cache_hits == 0, "displaced entry must re-derive"
        ref = index.search(ds.queries[:1], [], k=10, backend="jax")
        np.testing.assert_array_equal(r3.ids, ref[0])
    finally:
        rt.close()


# ------------------------------------------- DRE stale-retention satellites

def test_invalidate_denies_fetch_and_derived_hits(rng):
    """Acceptance: a warm container acquired before ``invalidate_cache()``
    scores neither a fetch-level nor a derived DRE hit afterwards — the
    version lives in *both* key layers."""
    ds, index = _build()
    rt = ServerlessRuntime(index, RuntimeConfig())
    try:
        rt.search(ds.queries, [], k=10)
        warm = rt.search(ds.queries, [], k=10)
        assert warm.trace.dre.dre_hits == warm.trace.dre.invocations
        assert warm.trace.dre.derived_hits == warm.trace.invocations("qp")
        rt.invalidate_cache()
        cold = rt.search(ds.queries, [], k=10)
        assert cold.trace.dre.dre_hits == 0, "fetch-level hit on stale key"
        assert cold.trace.dre.s3_gets == cold.trace.dre.invocations
        assert cold.trace.dre.derived_hits == 0
        assert all(n.setup_s > 0 for n in cold.trace.nodes if n.kind == "qp")
    finally:
        rt.close()


def test_derived_hit_routes_through_lease_delta_once():
    """Satellite 2: ``derived_hit`` counts in the lease's per-call delta and
    the pool's cumulative stats exactly once each, so merging lease deltas
    reproduces the pool totals without double accounting."""
    pool = ContainerPool(warm_prob=1.0, seed=3)
    merged_total = 0
    l1 = pool.acquire("key-v0", 1024)
    pool.retain_derived(l1, "derived-v0")
    pool.release(l1)
    merged_total += l1.stats.derived_hits

    l2 = pool.acquire("key-v0", 1024)
    assert pool.derived_hit(l2, "derived-v0") is True
    assert l2.stats.derived_hits == 1
    assert pool.stats.derived_hits == 1
    pool.release(l2)
    merged_total += l2.stats.derived_hits

    l3 = pool.acquire("key-v0", 1024)
    assert pool.derived_hit(l3, "missing") is False
    assert l3.stats.derived_hits == 0
    pool.release(l3)
    merged_total += l3.stats.derived_hits

    assert merged_total == pool.stats.derived_hits == 1


def test_result_cache_zero_capacity_rejects_up_front():
    """Satellite 3: capacity=0 must refuse admission (oversize_skips), not
    admit-then-evict (which polluted the eviction counter)."""
    cache = ResultCache(capacity=0)
    cache.put("k", (np.arange(4), np.arange(4.0)))
    assert cache.get("k") is None
    assert cache.oversize_skips == 1
    assert cache.evictions == 0
    assert cache.current_bytes == 0


def test_result_cache_eviction_and_deps_bookkeeping():
    cache = ResultCache(capacity=2)
    cache.put("a", (np.arange(2), np.arange(2.0)), parts=[0])
    cache.put("b", (np.arange(2), np.arange(2.0)), parts=[1])
    assert cache.deps("a") == frozenset({0})
    cache.put("c", (np.arange(2), np.arange(2.0)), parts=[2])  # evicts "a"
    assert cache.evictions == 1 and cache.deps("a") is None
    dropped = cache.invalidate_partitions([1])
    assert dropped == 1 and cache.get("b") is None
    assert cache.get("c") is not None          # untouched survives
    assert cache.targeted_evictions == 1
    # legacy (deps-less) entries evict on any partition invalidation
    cache.put("d", (np.arange(2), np.arange(2.0)))
    assert cache.invalidate_partitions([5]) == 1
    assert cache.get("d") is None


def test_container_pool_double_release_many_containers():
    """Satellite 4: releasing the same lease twice among many idle
    containers must not duplicate the free list (set-backed membership)."""
    pool = ContainerPool(warm_prob=1.0, seed=0)
    leases = [pool.acquire(f"k{i}", 64) for i in range(200)]
    for lease in leases:
        pool.release(lease)
    pool.release(leases[0])                    # double release
    pool.release(leases[123])
    assert len(pool._free) == len(set(pool._free)) == 200
    # the pool hands out 200 distinct containers again, no aliasing
    again = [pool.acquire(f"k{i}", 64) for i in range(200)]
    cids = [lease.container_id for lease in again]
    assert len(set(cids)) == 200
    for lease in again:
        pool.release(lease)


# ----------------------------------------------------------- service swap

def test_swap_index_keeps_runtime_and_drains_state(rng):
    """``swap_index`` rebinds the existing runtime (warm pools survive as
    objects) instead of discarding it, while staling every cache layer."""
    from repro.serve.vector_service import ServiceConfig, VectorSearchService

    ds, index = _build()
    svc = VectorSearchService(index, ServiceConfig(
        backend="serverless", cache_enabled=True))
    try:
        svc.query(ds.queries, [], k=10)
        svc.query(ds.queries, [], k=10)
        assert svc.last_trace.cache_hits == ds.queries.shape[0]
        rt_before = svc.runtime()
        pools_before = (rt_before.qa_pool,
                        tuple(rt_before.qp_pools.values()))

        rebuilt_cfg = SquashConfig(num_partitions=4, kmeans_iters=4,
                                   lloyd_iters=6)
        rebuilt = SquashIndex.build(ds.vectors[::-1].copy(), ds.attributes,
                                    rebuilt_cfg, seed=21)
        live = LiveIndex(rebuilt)
        svc.swap_index(live)                   # LiveIndex wrapper accepted
        assert svc.index is rebuilt
        assert svc.runtime() is rt_before, "runtime must survive the swap"
        assert rt_before.qa_pool is pools_before[0]

        ids, _, _ = svc.query(ds.queries, [], k=10, backend="serverless")
        assert svc.last_trace.cache_hits == 0
        ref = rebuilt.search(ds.queries, [], k=10, backend="jax")
        np.testing.assert_array_equal(ids, ref[0])

        # the swapped-in live index mutates through the same runtime
        live.delete(ids[:, 0])
        ids2, _, _ = svc.query(ds.queries, [], k=10, backend="serverless")
        assert np.intersect1d(ids2.ravel(), ids[:, 0]).size == 0
        ref2 = rebuilt.search(ds.queries, [], k=10, backend="jax")
        np.testing.assert_array_equal(ids2, ref2[0])
    finally:
        svc.close()


# -------------------------------------------------- real-transport parity

@pytest.mark.transport
@pytest.mark.parametrize("transport", ["process", "socket"])
def test_search_under_mutation_parity_real_transports(transport, rng):
    """The tentpole gate over real worker fleets: mutation → fresh bundles,
    ids and stats bitwise-identical to the in-process reference."""
    ds, index = _build(num_partitions=3)
    live = LiveIndex(index)
    rt = ServerlessRuntime(live, RuntimeConfig(
        branching=2, max_level=1, transport=transport, qa_workers=2))
    try:
        r0 = rt.search(ds.queries, [], k=10)
        ref0 = index.search(ds.queries, [], k=10, backend="jax")
        np.testing.assert_array_equal(r0.ids, ref0[0])
        assert _stats_eq(r0.stats, ref0[2])

        live.delete(r0.ids[:, 0])
        during = rt.search(ds.queries, [], k=10)
        refd = index.search(ds.queries, [], k=10, backend="jax")
        np.testing.assert_array_equal(during.ids, refd[0])
        assert _stats_eq(during.stats, refd[2])
        assert np.intersect1d(during.ids.ravel(), r0.ids[:, 0]).size == 0

        for pid in live.dirty_partitions():
            live.compact(pid, requantize=False)
        after = rt.search(ds.queries, [], k=10)
        np.testing.assert_array_equal(after.ids, during.ids)
        assert _stats_eq(after.stats, during.stats)
    finally:
        rt.close()
