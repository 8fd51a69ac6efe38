"""Tests for the serverless subsystem: Alg. 2 tree, DRE, cost model, and the
event-driven Coordinator → QueryAllocator → QueryProcessor runtime."""

import os

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import cost_model, dre, invocation


# ----------------------------------------------------------------- Algorithm 2

def test_tree_size_formula():
    # Paper §5.3 configurations: (F, l_max) → N_QA.
    assert invocation.tree_size(10, 1) == 10
    assert invocation.tree_size(4, 2) == 20
    assert invocation.tree_size(4, 3) == 84
    assert invocation.tree_size(5, 3) == 155
    assert invocation.tree_size(6, 3) == 258
    assert invocation.tree_size(4, 4) == 340


@pytest.mark.parametrize("f,lmax", [(10, 1), (4, 2), (4, 3), (5, 3), (6, 3), (4, 4)])
def test_tree_covers_all_ids_exactly_once(f, lmax):
    tree = invocation.build_tree(f, lmax)
    n_qa = invocation.tree_size(f, lmax)
    seen = [kid for kids in tree.values() for kid in kids]
    assert sorted(seen) == list(range(n_qa)), "every QA invoked exactly once"


@pytest.mark.parametrize("f,lmax", [(4, 3), (5, 3), (4, 4)])
def test_subtree_id_contiguity(f, lmax):
    """The invariant that enables response routing: the sub-tree rooted at x
    (next sibling x + J_S) contains exactly the ids y with x < y < x + J_S."""
    tree = invocation.build_tree(f, lmax)

    def collect(nid):
        out = []
        for kid in tree.get(nid, []):
            out.append(kid)
            out.extend(collect(kid))
        return out

    for nid, kids in tree.items():
        if nid == -1:
            continue
        sub = collect(nid)
        if sub:
            assert min(sub) == nid + 1
            assert sorted(sub) == list(range(nid + 1, nid + 1 + len(sub)))


def test_fanout_bounded_by_branching_factor():
    for f, lmax in [(4, 3), (6, 3), (10, 1)]:
        tree = invocation.build_tree(f, lmax)
        assert max(len(k) for k in tree.values()) <= f


def test_tree_beats_sequential_invocation():
    sim = invocation.InvocationSim(branching=4, max_level=3)
    assert sim.makespan() < sim.sequential_makespan() / 5.0


# ------------------------------------------------------------------------ DRE

def test_dre_eliminates_repeat_fetches():
    pool = dre.ContainerPool(warm_prob=1.0, seed=0)
    for _ in range(10):
        pool.invoke("sift1m/part0", data_bytes=10_000_000, use_dre=True)
    assert pool.stats.s3_gets == 1, "warm containers must reuse the singleton"
    assert pool.stats.dre_hits == 9


def test_no_dre_refetches_every_time():
    pool = dre.ContainerPool(warm_prob=1.0, seed=0)
    for _ in range(10):
        pool.invoke("sift1m/part0", data_bytes=10_000_000, use_dre=False)
    assert pool.stats.s3_gets == 10


def test_dre_dataset_mismatch_refetches():
    pool = dre.ContainerPool(warm_prob=1.0, seed=0)
    pool.invoke("sift1m/part0", 1000)
    pool.invoke("gist1m/part0", 1000)  # different dataset in same container
    assert pool.stats.s3_gets == 2


def test_result_cache():
    cache = dre.ResultCache()
    from repro.core.attributes import Predicate

    q = np.array([1.0, 2.0])
    preds = [Predicate(attr=0, op="<", lo=3.0)]
    key = cache.key(q, preds, 10)
    assert cache.get(key) is None
    cache.put(key, ("ids", "dists"))
    assert cache.get(key) == ("ids", "dists")
    assert cache.hit_rate == 0.5


def test_result_cache_exact_keys_no_float_aliasing():
    """Regression: the old key rounded coordinates to 6 decimals, so queries
    differing at the 8th decimal aliased to one entry and the second query
    silently got the first query's neighbors."""
    cache = dre.ResultCache()
    q1 = np.array([1.0, 2.0])
    q2 = np.array([1.0, 2.00000001])       # differs at the 8th decimal
    k1 = cache.key(q1, [], 10)
    k2 = cache.key(q2, [], 10)
    assert k1 != k2
    cache.put(k1, "neighbors-of-q1")
    assert cache.get(k2) is None, "distinct query must not hit q1's entry"
    # dtype normalization: equal values hash equal regardless of input dtype
    assert cache.key(np.array([1.0, 2.0], np.float32), [], 10) == k1


def test_result_cache_key_canonicalizes_predicates():
    from repro.core.attributes import Predicate

    q = np.array([0.5])
    a = Predicate(attr=0, op="<", lo=3.0)
    b = Predicate(attr=1, op="IN", values=(2.0, 1.0))
    b_sorted = Predicate(attr=1, op="IN", values=(1.0, 2.0))
    cache = dre.ResultCache()
    # predicate order and IN value order are spelling, not semantics
    assert cache.key(q, [a, b], 10) == cache.key(q, [b_sorted, a], 10)
    # different k, different operand, different group → different keys
    assert cache.key(q, [a, b], 10) != cache.key(q, [a, b], 11)
    assert cache.key(q, [a], 10) != cache.key(
        q, [Predicate(attr=0, op="<", lo=3.1)], 10)
    grouped = Predicate(attr=0, op="<", lo=3.0, group=1)
    assert cache.key(q, [a], 10) != cache.key(q, [grouped], 10)


def test_result_cache_lru_get_refreshes_recency():
    """Regression: eviction used to pop insertion order with no refresh on
    get — a hot entry inserted first was evicted before a stale one."""
    cache = dre.ResultCache(capacity=2)
    cache.put("hot", 1)
    cache.put("stale", 2)
    assert cache.get("hot") == 1           # refresh: hot is now most recent
    cache.put("new", 3)                    # evicts 'stale', not 'hot'
    assert cache.get("hot") == 1
    assert cache.get("stale") is None
    assert cache.get("new") == 3
    assert cache.evictions == 1


def test_result_cache_byte_budget_accounting():
    row = np.zeros(128)                    # 1 KiB of float64 payload
    cache = dre.ResultCache(max_bytes=4096)
    for i in range(8):
        cache.put(("q", i), row.copy())
    assert cache.current_bytes <= 4096
    assert len(cache) < 8 and cache.evictions > 0
    # an entry larger than the whole budget is never admitted
    cache.put(("huge",), np.zeros(4096))
    assert ("huge",) not in cache
    cache.invalidate()
    assert len(cache) == 0 and cache.current_bytes == 0


def test_result_cache_oversize_put_preserves_existing_entry():
    """Regression: putting an over-budget value under a live key used to
    evict the old entry first and then cache nothing — the cache silently
    lost an entry it could have kept serving."""
    cache = dre.ResultCache(max_bytes=4096)
    cache.put("q", np.zeros(16))
    cache.put("q", np.zeros(4096))         # over the whole budget: rejected
    got = cache.get("q")
    assert got is not None and got.shape == (16,), (
        "over-budget put must leave the existing entry intact")
    assert cache.oversize_skips == 1
    assert cache.evictions == 0
    assert cache.current_bytes <= 4096


def test_container_pool_double_release_is_idempotent():
    """Regression: releasing one lease twice put its container id into the
    free list twice, so two concurrent acquires shared one container."""
    pool = dre.ContainerPool(warm_prob=1.0, seed=0)
    lease = pool.acquire("ds/p0", 1000)
    pool.release(lease)
    pool.release(lease)                    # double release: no-op
    a = pool.acquire("ds/p0", 1000)
    b = pool.acquire("ds/p0", 1000)        # concurrent wave
    assert a.container_id != b.container_id, (
        "double-released container handed to two in-flight leases")


def test_container_pool_dre_off_does_not_seed_retention():
    """Regression (off→on sequence): a DRE-off invocation used to install
    the singleton anyway, so a later DRE-on call scored a hit it never paid
    for."""
    pool = dre.ContainerPool(warm_prob=1.0, seed=0)
    pool.invoke("sift1m/part0", 1000, use_dre=False)
    warm, hit = pool.invoke("sift1m/part0", 1000, use_dre=True)
    assert warm and not hit, "first DRE-on call must pay the fetch"
    warm, hit = pool.invoke("sift1m/part0", 1000, use_dre=True)
    assert hit, "second DRE-on call hits the retention it paid for"
    assert pool.stats.s3_gets == 2


def test_container_pool_derived_state_retention():
    pool = dre.ContainerPool(warm_prob=1.0, seed=0)
    lease = pool.acquire("ds/p0", 1000)
    assert not pool.derived_hit(lease, ("stacked", 0))
    pool.retain_derived(lease, ("stacked", 0))
    pool.release(lease)
    lease2 = pool.acquire("ds/p0", 1000)
    assert lease2.container_id == lease.container_id
    assert pool.derived_hit(lease2, ("stacked", 0))
    assert not pool.derived_hit(lease2, ("stacked", 1)), "key-specific"
    assert not pool.derived_hit(lease2, ("stacked", 0), use_dre=False)
    assert pool.stats.derived_hits == 1


def test_container_pool_stale_lease_cannot_resurrect_derived_state():
    """Regression (lease accounting): a lease still in flight when
    ``clear_derived()`` runs (invalidate_cache/swap_index) must not re-add
    derived state on its way out — the resurrected entry would be keyed to a
    dead index_version and leak forever, and a buggy version-less key would
    be served as a false hit for the new index."""
    pool = dre.ContainerPool(warm_prob=1.0, seed=0)
    stale = pool.acquire("ds/p0", 1000)
    pool.retain_derived(stale, ("stacked", 0, 0))
    pool.clear_derived()                      # invalidation while leased
    pool.retain_derived(stale, ("stacked", 0, 0))   # in-flight retain: dropped
    pool.release(stale)
    fresh = pool.acquire("ds/p0", 1000)
    assert fresh.container_id == stale.container_id
    assert not pool.derived_hit(fresh, ("stacked", 0, 0)), (
        "stale lease resurrected cleared derived state")
    # the new-epoch lease retains normally
    pool.retain_derived(fresh, ("stacked", 0, 1))
    pool.release(fresh)
    again = pool.acquire("ds/p0", 1000)
    assert pool.derived_hit(again, ("stacked", 0, 1))


# ----------------------------------------------------------------- cost model

def test_cost_model_exports_daily_cost_curve():
    """``daily_cost_curve`` is public API (Fig. 8 consumers import it)."""
    assert "daily_cost_curve" in cost_model.__all__


def test_cost_model_components():
    fleet = cost_model.LambdaFleet(
        n_qa=84, n_qp=500, t_qa_s=84 * 0.5, t_qp_s=500 * 0.3, t_co_s=1.0,
        s3_gets=584, efs_read_bytes=2 * 10 * 128 * 4 * 1000,
    )
    c = cost_model.squash_query_cost(fleet)
    assert c["total"] == pytest.approx(
        c["lambda_invocation"] + c["lambda_runtime"] + c["s3"] + c["efs"]
    )
    # Eq. 5: (N_QA + N_QP + 1) · C_inv
    assert c["lambda_invocation"] == pytest.approx(585 * 2.0e-7)
    assert c["lambda_runtime"] > 0


def test_serverless_cheaper_at_low_volume_crossover_at_high():
    """Fig. 8 shape: SQUASH scales with volume, servers are flat — there is a
    crossover somewhere in the millions of queries/day."""
    fleet = cost_model.LambdaFleet(
        n_qa=84, n_qp=400, t_qa_s=84 * 0.4, t_qp_s=400 * 0.25, t_co_s=1.0,
        s3_gets=484, efs_read_bytes=20 * 512 * 1000,
    )
    per_batch = cost_model.squash_query_cost(fleet)["total"]  # 1000 queries
    volumes = [10_000, 100_000, 1_000_000, 10_000_000, 100_000_000]
    squash_daily = cost_model.daily_cost_curve(per_batch, 1000, volumes)
    server_daily = cost_model.server_baseline_cost(hours=24.0)
    assert squash_daily[0] < server_daily, "cheap at low volume"
    assert squash_daily[-1] > server_daily, "servers win at huge volume"
    # Paper §5.4: crossover around 1M–3.5M queries/day for the large server;
    # our synthetic fleet times put it within an order of magnitude of that.
    crossover = next(v for v, c in zip(volumes, squash_daily) if c > server_daily)
    assert 1_000_000 <= crossover <= 100_000_000


@given(
    n_qa=st.integers(1, 500), n_qp=st.integers(0, 2000),
    t=st.floats(0.0, 10.0),
)
@settings(max_examples=25, deadline=None)
def test_cost_monotonicity(n_qa, n_qp, t):
    base = cost_model.LambdaFleet(n_qa=n_qa, n_qp=n_qp, t_qa_s=t, t_qp_s=t)
    more = cost_model.LambdaFleet(n_qa=n_qa + 1, n_qp=n_qp, t_qa_s=t, t_qp_s=t)
    assert (
        cost_model.squash_query_cost(more)["total"]
        >= cost_model.squash_query_cost(base)["total"]
    )


# ======================================================== serverless runtime

from repro.core.attributes import Predicate  # noqa: E402
from repro.core.pipeline import SquashConfig, SquashIndex  # noqa: E402
from repro.data import synthetic  # noqa: E402
from _traffic import assert_guarantees, cell_predicates  # noqa: E402
from repro.serverless import (PayloadOverflowError, RuntimeConfig,  # noqa: E402
                              ServerlessRuntime, decode_message,
                              encode_message)


@pytest.fixture(scope="module")
def built():
    ds = synthetic.make_vector_dataset("sift1m", scale=0.004, num_queries=12,
                                       seed=7)
    preds = synthetic.default_predicates(ds.attr_cardinality)
    cfg = SquashConfig(num_partitions=5, kmeans_iters=4, lloyd_iters=6)
    index = SquashIndex.build(ds.vectors, ds.attributes, cfg, seed=7)
    return ds, preds, index


def _runtime(index, **kw):
    kw.setdefault("branching", 3)
    kw.setdefault("max_level", 2)
    return ServerlessRuntime(index, RuntimeConfig(**kw))


def test_codec_roundtrip():
    msg = {
        "qidx": np.arange(7, dtype=np.int32),
        "queries": np.random.default_rng(0).normal(size=(7, 16)),
        "rows": np.array([], dtype=np.int32),
        "k": 10,
        "preds": [{"attr": 0, "op": "B", "lo": 1.0, "hi": 2.0,
                   "values": [], "group": None}],
    }
    out = decode_message(encode_message(msg))
    assert out["k"] == 10 and out["preds"] == msg["preds"]
    np.testing.assert_array_equal(out["qidx"], msg["qidx"])
    np.testing.assert_array_equal(out["queries"], msg["queries"])
    assert out["rows"].dtype == np.int32 and out["rows"].shape == (0,)


@pytest.mark.parametrize("k", [1, 10, 100])
@pytest.mark.parametrize("width", [3, 9, 16])
def test_runtime_matches_jax_backend_bitwise(built, seed, width, k):
    """Acceptance: Coordinator → QA → QP ids are bitwise-identical to
    SquashIndex.search(backend='jax'), stats counters equal, under the
    cells' predicates at three widths: both paths plan through the one
    host planner."""
    ds, _, index = built
    preds = cell_predicates(width, seed)
    rt = _runtime(index)
    res = rt.search(ds.queries, preds, k=k)
    ids_j, d_j, s_j = index.search(ds.queries, preds, k=k, backend="jax")
    np.testing.assert_array_equal(res.ids, ids_j)
    np.testing.assert_array_equal(np.isfinite(res.dists), np.isfinite(d_j))
    fin = np.isfinite(d_j)
    np.testing.assert_array_equal(res.dists[fin], d_j[fin])
    assert res.stats == s_j
    assert_guarantees(res.ids, ds.attributes, preds, k)


@pytest.mark.parametrize("width", [3, 9, 16])
def test_worker_qa_view_plans_like_in_process_allocator(built, seed, width):
    """A worker's allocator over ``_QAIndexView`` (built from the bundle as
    a worker receives it) makes the in-process allocator's plan: the same QP requests,
    filter_pass, partitions_visited and escalations."""
    from repro.serverless import nodes as nd
    from repro.serverless import payload as pl
    from repro.serverless import workers as wk

    ds, _, index = built
    preds = cell_predicates(width, seed)
    creq = {"qidx": np.arange(ds.queries.shape[0], dtype=np.int32),
            "queries": ds.queries, "k": 10,
            "preds": pl.predicates_to_json(preds)}
    n = ds.queries.shape[0]
    here = wk.qa_compute(nd.QueryAllocator(index), creq, 0, n)
    bundle, _ = pl.decode_init(pl.encode_init(wk.build_qa_bundle(index), 0))
    view = wk._QAIndexView(bundle)
    there = wk.qa_compute(nd.QueryAllocator(view), creq, 0, n)
    for key in ("filter_pass", "partitions_visited", "escalations"):
        assert here[key] == there[key], key
    assert here["partitions_visited"] > 0
    assert sorted(here["plans"]) == sorted(there["plans"])
    for pid, req in here["plans"].items():
        for field in ("qidx", "rows", "row_offsets", "keep", "take"):
            np.testing.assert_array_equal(req[field],
                                          there["plans"][pid][field])


def test_runtime_unfiltered_and_empty_predicates(built):
    ds, _, index = built
    rt = _runtime(index)
    res = rt.search(ds.queries, [], k=5)
    ids_j, _, _ = index.search(ds.queries, [], k=5, backend="jax")
    np.testing.assert_array_equal(res.ids, ids_j)
    impossible = [Predicate(attr=0, op="=", lo=1e9)]
    res2 = rt.search(ds.queries[:4], impossible, k=5)
    assert (res2.ids == -1).all() and np.isinf(res2.dists).all()
    assert res2.trace.invocations("qp") == 0


def test_tree_fanout_every_qa_invoked_once(built):
    """Fan-out correctness: each of the N_QA allocators is invoked exactly
    once per batch (no chunking), the coordinator once, and per-node traces
    carry a consistent timeline."""
    ds, preds, index = built
    rt = _runtime(index, branching=3, max_level=2)
    res = rt.search(ds.queries, preds, k=10)
    t = res.trace
    qa_nodes = [n for n in t.nodes if n.kind == "qa"]
    assert len(qa_nodes) == invocation.tree_size(3, 2) == 12
    assert sorted(n.node for n in qa_nodes) == sorted(
        f"qa:{i}" for i in range(12))
    assert t.invocations("co") == 1
    for n in t.nodes:
        assert n.t_issue <= n.t_start <= n.t_end
        assert n.billed_s >= n.compute_s
    assert t.makespan_s >= max(n.t_end for n in t.nodes)
    # every query lands in exactly one QA's own slice
    assert sum(n.own_queries for n in qa_nodes) == ds.queries.shape[0]


def test_filter_count_escalation_path(built):
    """§2.5 single-pass guarantee: a highly selective predicate forces
    Alg. 1 past the Eq. 1 threshold cut; the runtime reports the escalated
    visits and still matches the reference plane."""
    ds, _, index = built
    narrow = [Predicate(attr=0, op="=", lo=float(ds.attributes[0, 0])),
              Predicate(attr=1, op="=", lo=float(ds.attributes[0, 1]))]
    rt = _runtime(index)
    res = rt.search(ds.queries, narrow, k=10)
    ids_j, _, s_j = index.search(ds.queries, narrow, k=10, backend="jax")
    np.testing.assert_array_equal(res.ids, ids_j)
    assert res.stats == s_j
    assert res.trace.escalations > 0, "narrow predicate must escalate"
    # escalation is bounded by the visited count
    assert res.trace.escalations <= res.stats.partitions_visited


def test_payload_overflow_error_policy(built):
    ds, preds, index = built
    rt = _runtime(index, max_payload_bytes=4096, overflow="error")
    with pytest.raises(PayloadOverflowError):
        rt.search(ds.queries, preds, k=10)


def test_payload_overflow_chunking_preserves_results(built):
    ds, preds, index = built
    rt = _runtime(index, max_payload_bytes=4096, overflow="chunk")
    res = rt.search(ds.queries, preds, k=10)
    ids_j, _, _ = index.search(ds.queries, preds, k=10, backend="jax")
    np.testing.assert_array_equal(res.ids, ids_j)
    # chunking means strictly more invocations than the unchunked tree
    base = _runtime(index).search(ds.queries, preds, k=10)
    assert len(res.trace.nodes) > len(base.trace.nodes)
    for n in res.trace.nodes:
        assert n.request_bytes <= 4096


def test_response_payload_pagination(built):
    """Oversized responses (large k) are budgeted too: under the chunk
    policy they paginate — extra warm round-trips in the trace — and the
    merged results still match the reference plane."""
    ds, preds, index = built
    rt = _runtime(index, max_payload_bytes=4096, overflow="chunk")
    res = rt.search(ds.queries, preds, k=200)
    ids_j, _, _ = index.search(ds.queries, preds, k=200, backend="jax")
    np.testing.assert_array_equal(res.ids, ids_j)
    paged = [n for n in res.trace.nodes if n.response_chunks > 1]
    assert paged, "k=200 responses must exceed the 4 KB budget"


def test_single_query_payload_cannot_chunk(built):
    """A payload that cannot split below one query raises even under the
    chunk policy."""
    ds, preds, index = built
    rt = _runtime(index, max_payload_bytes=256, overflow="chunk")
    with pytest.raises(PayloadOverflowError):
        rt.search(ds.queries[:2], preds, k=10)


def test_dre_warm_reuse_across_batches(built):
    """Second batch on a warm fleet: zero S3 GETs, all DRE hits, smaller
    makespan and cost (Fig. 6 shape)."""
    ds, preds, index = built
    rt = _runtime(index, warm_prob=1.0)
    r1 = rt.search(ds.queries, preds, k=10)
    r2 = rt.search(ds.queries, preds, k=10)
    assert r1.trace.dre.s3_gets > 0
    assert r2.trace.dre.s3_gets == 0
    assert r2.trace.dre.dre_hits == r2.trace.dre.invocations
    assert r2.trace.makespan_s < r1.trace.makespan_s
    np.testing.assert_array_equal(r1.ids, r2.ids)


def test_dre_disabled_refetches(built):
    ds, preds, index = built
    rt = _runtime(index, use_dre=False)
    rt.search(ds.queries, preds, k=10)
    r2 = rt.search(ds.queries, preds, k=10)
    # every QA/QP invocation refetches even on warm containers
    assert r2.trace.dre.s3_gets == r2.trace.dre.invocations
    assert r2.trace.dre.dre_hits == 0


def test_cost_and_fleet_assembly(built):
    ds, preds, index = built
    rt = _runtime(index, qa_compute_s=0.1, qp_compute_s=0.2, co_compute_s=0.01)
    res = rt.search(ds.queries, preds, k=10)
    t = res.trace
    c = t.cost
    assert c["total"] == pytest.approx(
        c["lambda_invocation"] + c["lambda_runtime"] + c["s3"] + c["efs"])
    assert c["total"] > 0 and c["lambda_runtime"] > 0
    assert t.fleet.n_qa == t.invocations("qa")
    assert t.fleet.n_qp == t.invocations("qp")
    assert t.fleet.s3_gets == t.dre.s3_gets
    assert t.fleet.efs_read_bytes == t.efs_read_bytes
    assert t.efs_reads == res.stats.refined
    assert t.payload_bytes == t.request_bytes + t.response_bytes > 0
    # billed time covers at least the configured compute
    assert t.fleet.t_qp_s >= 0.2 * t.invocations("qp")


def test_sequential_strawman_slower_than_tree(built):
    """Fig. 7 via the runtime: the CO-invokes-everything strawman's makespan
    exceeds the Alg. 2 tree's for the same fleet and workload."""
    ds, preds, index = built
    fixed = dict(qa_compute_s=0.05, qp_compute_s=0.05, co_compute_s=0.01)
    tree = _runtime(index, branching=3, max_level=2, **fixed)
    seq = _runtime(index, branching=3, max_level=2, sequential=True, **fixed)
    r_tree = tree.search(ds.queries, preds, k=10)
    r_seq = seq.search(ds.queries, preds, k=10)
    np.testing.assert_array_equal(r_tree.ids, r_seq.ids)
    assert r_seq.trace.makespan_s > r_tree.trace.makespan_s


def test_runtime_single_query_and_large_k(built):
    ds, preds, index = built
    rt = _runtime(index)
    for qn, k in ((1, 10), (3, 50)):
        res = rt.search(ds.queries[:qn], preds, k=k)
        ids_j, _, _ = index.search(ds.queries[:qn], preds, k=k, backend="jax")
        np.testing.assert_array_equal(res.ids, ids_j)


def test_service_serverless_backend(built):
    from repro.serve.vector_service import ServiceConfig, VectorSearchService

    ds, preds, index = built
    svc = VectorSearchService(index, ServiceConfig(backend="auto"))
    ids, _, _ = svc.query(ds.queries, preds, backend="serverless")
    ids_j, _, _ = index.search(ds.queries, preds, k=10, backend="jax")
    np.testing.assert_array_equal(ids, ids_j)
    assert svc.last_trace is not None
    assert svc.last_trace.cost["total"] > 0
    assert svc.queries_served["serverless"] == ds.queries.shape[0]


# ============================================== §5.6 result cache in the runtime


def test_cache_on_off_bitwise_parity_repeated_batches(built):
    """Acceptance: with caching enabled, repeated-workload ids/dists are
    bitwise-identical to a cache-off run, while the repeat pass shows
    strictly fewer invocations, payload bytes and §3.5 dollars."""
    ds, preds, index = built
    off = _runtime(index)
    on = _runtime(index, cache_enabled=True)
    off1 = off.search(ds.queries, preds, k=10)
    off2 = off.search(ds.queries, preds, k=10)
    on1 = on.search(ds.queries, preds, k=10)
    on2 = on.search(ds.queries, preds, k=10)
    for a, b in ((off1, on1), (off2, on2)):
        np.testing.assert_array_equal(a.ids, b.ids)
        np.testing.assert_array_equal(a.dists, b.dists)
    # cold pass: every query misses, then populates
    assert on1.trace.cache_hits == 0
    assert on1.trace.cache_misses == ds.queries.shape[0]
    # repeat pass: all served at the CO, fleet never launches
    assert on2.trace.cache_hits == ds.queries.shape[0]
    assert on2.trace.cache_misses == 0
    assert on2.trace.invocations() < off2.trace.invocations()
    assert on2.trace.invocations("qa") == 0
    assert on2.trace.invocations("qp") == 0
    assert on2.trace.payload_bytes < off2.trace.payload_bytes
    assert on2.trace.cost["total"] < off2.trace.cost["total"]
    assert on2.trace.cache_hit_rate == 1.0
    # the CO's own trace marks the served queries
    co = [n for n in on2.trace.nodes if n.kind == "co"]
    assert sum(n.cache_hits for n in co) == ds.queries.shape[0]


def test_cache_cold_pass_fleet_matches_cache_off(built):
    """A cold cache (0 hits) must not change the modeled fleet: only *hits*
    may thin the Fig. 7 whole-fleet launch, so a small batch that leaves
    some subtrees query-empty still launches them, exactly like cache-off."""
    ds, preds, index = built
    off = _runtime(index, branching=4, max_level=2)
    on = _runtime(index, branching=4, max_level=2, cache_enabled=True)
    r_off = off.search(ds.queries[:2], preds, k=10)
    r_on = on.search(ds.queries[:2], preds, k=10)
    assert r_on.trace.invocations() == r_off.trace.invocations()
    assert r_on.trace.invocations("qa") == r_off.trace.invocations("qa")
    np.testing.assert_array_equal(r_on.ids, r_off.ids)


def test_cache_mixed_hit_miss_slices(built):
    """Partially-repeated batch: the hit slice never reaches the fleet, the
    miss slice traverses the tree, and the merged result is bitwise equal
    to a cache-off run of the same batch."""
    ds, preds, index = built
    half = ds.queries.shape[0] // 2
    mixed = np.concatenate([ds.queries[:half], ds.queries[:half] + 0.25])
    off = _runtime(index)
    on = _runtime(index, cache_enabled=True)
    on.search(ds.queries[:half], preds, k=10)        # populate first half
    r_on = on.search(mixed, preds, k=10)
    r_off = off.search(mixed, preds, k=10)
    np.testing.assert_array_equal(r_on.ids, r_off.ids)
    np.testing.assert_array_equal(r_on.dists, r_off.dists)
    assert r_on.trace.cache_hits == half
    assert r_on.trace.cache_misses == half
    assert 0.0 < r_on.trace.cache_hit_rate < 1.0
    assert r_on.trace.invocations("qp") <= r_off.trace.invocations("qp")
    assert r_on.trace.payload_bytes < r_off.trace.payload_bytes
    # different k must not hit entries stored under k=10
    r_k5 = on.search(mixed[:2], preds, k=5)
    assert r_k5.trace.cache_hits == 0


def test_cache_respects_predicates(built):
    """Same query under a different filter is a different result — the
    canonical predicate tuple must keep them apart, while a reordered
    spelling of the same filter still hits."""
    ds, preds, index = built
    if len(preds) < 2:
        pytest.skip("needs >= 2 predicates to reorder")
    on = _runtime(index, cache_enabled=True)
    on.search(ds.queries[:4], preds, k=10)
    r_reordered = on.search(ds.queries[:4], list(reversed(preds)), k=10)
    assert r_reordered.trace.cache_hits == 4
    r_unfiltered = on.search(ds.queries[:4], [], k=10)
    assert r_unfiltered.trace.cache_hits == 0
    ids_j, _, _ = index.search(ds.queries[:4], [], k=10, backend="jax")
    np.testing.assert_array_equal(r_unfiltered.ids, ids_j)


def test_cache_invalidation_serves_fresh_results(built):
    ds, preds, index = built
    on = _runtime(index, cache_enabled=True)
    on.search(ds.queries, preds, k=10)
    on.invalidate_cache()
    r = on.search(ds.queries, preds, k=10)
    assert r.trace.cache_hits == 0 and r.trace.cache_misses == ds.queries.shape[0]
    ids_j, _, _ = index.search(ds.queries, preds, k=10, backend="jax")
    np.testing.assert_array_equal(r.ids, ids_j)


def test_qp_derived_state_retention_in_runtime(built):
    """Warm QP containers retain derived (device-resident) state beyond the
    fetched bytes: the first wave pays setup on every QP invocation, the
    second wave skips it on retained containers; DRE-off always pays."""
    ds, preds, index = built
    rt = _runtime(index, warm_prob=1.0)
    r1 = rt.search(ds.queries, preds, k=10)
    r2 = rt.search(ds.queries, preds, k=10)
    assert r1.trace.dre.derived_hits == 0
    assert r2.trace.dre.derived_hits == r2.trace.invocations("qp") > 0
    qp1 = [n for n in r1.trace.nodes if n.kind == "qp"]
    qp2 = [n for n in r2.trace.nodes if n.kind == "qp"]
    assert all(n.setup_s > 0 for n in qp1)
    assert all(n.setup_s == 0 for n in qp2)
    off = _runtime(index, warm_prob=1.0, use_dre=False)
    off.search(ds.queries, preds, k=10)
    r_off = off.search(ds.queries, preds, k=10)
    assert r_off.trace.dre.derived_hits == 0
    assert all(n.setup_s > 0 for n in r_off.trace.nodes if n.kind == "qp")


def test_invalidate_cache_resets_derived_retention(built):
    """Runtime-level twin of the stale-lease regression: after
    ``invalidate_cache()`` the next wave re-pays QP setup on every container
    (no resurrected derived state), then retention resumes normally."""
    ds, preds, index = built
    rt = _runtime(index, warm_prob=1.0)
    rt.search(ds.queries, preds, k=10)
    rt.invalidate_cache()
    r = rt.search(ds.queries, preds, k=10)
    assert r.trace.dre.derived_hits == 0
    assert all(n.setup_s > 0 for n in r.trace.nodes if n.kind == "qp")
    r2 = rt.search(ds.queries, preds, k=10)
    assert r2.trace.dre.derived_hits == r2.trace.invocations("qp") > 0


def test_service_cache_config_and_invalidation_on_rebuild(built):
    """Service-level wiring: ServiceConfig(cache_enabled=True) reaches the
    runtime, and swap_index invalidates so a rebuilt index can never serve
    stale cached neighbors."""
    from repro.serve.vector_service import ServiceConfig, VectorSearchService

    ds, preds, index = built
    svc = VectorSearchService(index, ServiceConfig(
        backend="serverless", cache_enabled=True))
    svc.query(ds.queries, preds, k=10)
    ids_a, _, _ = svc.query(ds.queries, preds, k=10)
    assert svc.last_trace.cache_hits == ds.queries.shape[0]
    assert svc.result_cache is not None and svc.result_cache.hits > 0

    # rebuild the index on perturbed vectors → same queries, new neighbors
    cfg = SquashConfig(num_partitions=5, kmeans_iters=4, lloyd_iters=6)
    rebuilt = SquashIndex.build(ds.vectors[::-1].copy(), ds.attributes,
                                cfg, seed=11)
    svc.swap_index(rebuilt)
    ids_b, _, _ = svc.query(ds.queries, preds, k=10)
    assert svc.last_trace.cache_hits == 0, "stale cache served after rebuild"
    ids_j, _, _ = rebuilt.search(ds.queries, preds, k=10, backend="jax")
    np.testing.assert_array_equal(ids_b, ids_j)


def test_cache_with_payload_chunking(built):
    """Cache split composes with the chunk overflow policy: a chunked CO
    request still serves hits per chunk and stays bitwise-correct."""
    ds, preds, index = built
    on = _runtime(index, cache_enabled=True, max_payload_bytes=4096,
                  overflow="chunk")
    r1 = on.search(ds.queries, preds, k=10)
    r2 = on.search(ds.queries, preds, k=10)
    ids_j, _, _ = index.search(ds.queries, preds, k=10, backend="jax")
    np.testing.assert_array_equal(r1.ids, ids_j)
    np.testing.assert_array_equal(r2.ids, ids_j)
    assert r2.trace.cache_hits == ds.queries.shape[0]
    assert r2.trace.invocations("qp") == 0


def test_worker_fleet_runs_on_the_cpu_whatever_the_parent(built, monkeypatch):
    """Process/socket workers never inherit the parent's platform: a parent
    on the chip holds it, and a worker reaching for it would fail or hang."""
    _, _, index = built
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    inits = _runtime(index)._worker_inits()
    assert {init.platform for init, _ in inits.values()} == {"cpu"}


def test_configure_jax_pins_the_platform_after_jax_import(tmp_path):
    """A spawned worker has imported jax (which read JAX_PLATFORMS then)
    before its WorkerInit arrives; the pin must still take effect."""
    import subprocess
    import sys

    script = tmp_path / "worker_platform.py"
    script.write_text(
        "import jax\n"
        "from repro.serverless import workers as wk\n"
        "wk.configure_jax(wk.WorkerInit(role='qa', fn='qa', pid=None,\n"
        "                 x64=False, platform='cpu', bundle={}))\n"
        "print(jax.devices()[0].platform)\n")
    env = dict(os.environ, JAX_PLATFORMS="no_such_platform",
               PYTHONPATH=os.pathsep.join(sys.path))
    out = subprocess.run([sys.executable, str(script)], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().splitlines()[-1] == "cpu"
