"""Benchmark driver: one section per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--full] [--skip-roofline]
  PYTHONPATH=src python -m benchmarks.run --smoke   # tiny post-test gate

Paper-artifact map (DESIGN.md §7):
  Fig. 2  → bench_compression     Fig. 6  → bench_dre
  Fig. 8  → bench_cost            Fig. 9  → bench_qps
  Fig. 10 → bench_scaling         §5.3    → bench_recall (+ autotune)
  Alg. 2  → bench_invocation      kernels → bench_kernels
  §5.6 + Table 3 → bench_cache (the one cache bench: runtime result
              cache on a Zipf workload + the Table 3 cache-ratio study)
  §Roofline → roofline (subprocess: needs 512 XLA host devices before
              jax init, so it cannot share this interpreter)
"""

from __future__ import annotations

import argparse
import os
import subprocess
import sys
import time

# Path bootstrap: make `repro` importable from a bare checkout
# (`python -m benchmarks.run --smoke` without PYTHONPATH=src).
_SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                    "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)


def smoke() -> int:
    """Tiny-shape sanity gate: both query data planes, asserted parity.

    Builds a small index, runs identical query batches through the numpy
    and jax backends (selective + empty predicates), and asserts identical
    ids plus equal recall against brute force. Intended as a fast
    post-test CI step: ``python -m benchmarks.run --smoke``.
    """
    import jax
    import numpy as np

    jax.config.update("jax_enable_x64", True)

    from repro.core.attributes import Predicate
    from repro.core.pipeline import SquashConfig, SquashIndex
    from repro.data import synthetic

    t0 = time.time()
    ds = synthetic.make_vector_dataset("sift1m", scale=0.004, num_queries=16,
                                       seed=7)
    preds = synthetic.default_predicates(ds.attr_cardinality)
    cfg = SquashConfig(num_partitions=6, kmeans_iters=4, lloyd_iters=6)
    idx = SquashIndex.build(ds.vectors, ds.attributes, cfg, seed=7)
    gt_ids, _ = synthetic.ground_truth(ds, preds, k=10)

    def recall_of(ids):
        per_q = []
        for qi in range(ds.queries.shape[0]):
            g = set(gt_ids[qi][gt_ids[qi] >= 0].tolist())
            if g:
                per_q.append(len(g & set(ids[qi].tolist())) / len(g))
        return float(np.mean(per_q))

    recalls = {}
    results = {}
    for backend in ("numpy", "jax"):
        ids, dists, stats = idx.search(ds.queries, preds, k=10,
                                       backend=backend)
        results[backend] = (ids, dists, stats)
        recalls[backend] = recall_of(ids)
    ids_n, _, stats_n = results["numpy"]
    ids_j, _, stats_j = results["jax"]
    assert np.array_equal(ids_n, ids_j), "backend ids diverged"
    assert recalls["numpy"] == recalls["jax"], f"recall drift: {recalls}"
    assert stats_n == stats_j, f"stats drift: {stats_n} vs {stats_j}"

    empty = [Predicate(attr=0, op="=", lo=1e9)]
    for backend in ("numpy", "jax"):
        ids, _, _ = idx.search(ds.queries[:4], empty, k=5, backend=backend)
        assert (ids == -1).all(), f"{backend}: empty predicate leaked ids"

    # Serverless-runtime gate: the full Coordinator → QA → QP path over the
    # same tiny index must return the jax plane's ids bit-for-bit and emit
    # latency / payload / DRE / cost traces.
    from repro.serverless import RuntimeConfig, ServerlessRuntime

    rt = ServerlessRuntime(idx, RuntimeConfig(branching=3, max_level=2))
    res = rt.search(ds.queries, preds, k=10)
    assert np.array_equal(res.ids, ids_j), "serverless runtime ids diverged"
    assert res.stats == stats_j, (
        f"serverless stats drift: {res.stats} vs {stats_j}")
    tr = res.trace
    assert tr.makespan_s > 0 and tr.payload_bytes > 0
    assert tr.cost["total"] > 0 and tr.dre.invocations > 0
    assert tr.invocations("qa") == 12 and tr.invocations("co") == 1

    # Transport-parity gate: the same choreography over the real
    # multi-process worker pool must return the jax plane's ids bit-for-bit
    # with equal stats, on a measured (not virtual) clock, with zero crash
    # retries. CI wraps --smoke in a hard `timeout` so a hung worker pool
    # fails the job fast instead of stalling it.
    rt_proc = ServerlessRuntime(idx, RuntimeConfig(
        branching=2, max_level=1, transport="process", qa_workers=1,
        invoke_timeout_s=120.0))
    try:
        res_p = rt_proc.search(ds.queries, preds, k=10)
        assert np.array_equal(res_p.ids, ids_j), "process-transport ids diverged"
        assert res_p.stats == stats_j, (
            f"process-transport stats drift: {res_p.stats} vs {stats_j}")
        tp = res_p.trace
        assert tp.transport == "process" and tp.measured_makespan_s > 0
        assert tp.worker_retries == 0, "workers crashed during the smoke wave"
        assert tp.dre.invocations > 0 and tp.cost["total"] > 0
        warm_p = rt_proc.search(ds.queries, preds, k=10).trace
        assert warm_p.dre.s3_gets == 0, "live workers must serve warm"
    finally:
        rt_proc.close()

    # Socket-parity gate: the same choreography again, this time over the
    # TCP worker fleet (auto-spawned loopback hosts, length-prefixed codec
    # frames, heartbeats). Ids and stats must stay bitwise-identical, every
    # served node must report the host:port that ran it, and the smoke wave
    # must complete with zero reconnect-driven retries.
    rt_sock = ServerlessRuntime(idx, RuntimeConfig(
        branching=2, max_level=1, transport="socket", qa_workers=1,
        invoke_timeout_s=120.0))
    try:
        res_s = rt_sock.search(ds.queries, preds, k=10)
        assert np.array_equal(res_s.ids, ids_j), "socket-transport ids diverged"
        assert res_s.stats == stats_j, (
            f"socket-transport stats drift: {res_s.stats} vs {stats_j}")
        ts = res_s.trace
        assert ts.transport == "socket" and ts.measured_makespan_s > 0
        assert ts.worker_retries == 0, "socket links dropped during smoke wave"
        assert ts.worker_hosts, "socket trace must carry worker hosts"
        assert all(n.worker_host for n in ts.nodes if n.kind != "co"), (
            "served socket QA/QP nodes must record their host")
        warm_s = rt_sock.search(ds.queries, preds, k=10).trace
        assert warm_s.dre.s3_gets == 0, "live socket hosts must serve warm"
    finally:
        rt_sock.close()

    # §5.6 result-cache gate: with the cache enabled, both the cold pass and
    # the fully-repeated pass must stay bitwise-identical to the jax plane,
    # while the repeat pass shows strictly fewer invocations, payload bytes
    # and §3.5 dollars (hits never enter the QA/QP fleet).
    rt_c = ServerlessRuntime(idx, RuntimeConfig(branching=3, max_level=2,
                                                cache_enabled=True))
    c1 = rt_c.search(ds.queries, preds, k=10)
    c2 = rt_c.search(ds.queries, preds, k=10)
    assert np.array_equal(c1.ids, ids_j), "cache-on cold ids diverged"
    assert np.array_equal(c2.ids, ids_j), "cache-served ids diverged"
    t2 = c2.trace
    assert t2.cache_hits == ds.queries.shape[0] and t2.cache_misses == 0
    assert len(t2.nodes) < len(tr.nodes)
    assert t2.payload_bytes < tr.payload_bytes
    assert t2.cost["total"] < tr.cost["total"]

    # Observability gate (repro.obs): the same choreography with tracing ON
    # must stay bitwise-identical to the jax plane across all three
    # transports, while persisting one JSONL trace record per transport —
    # CO/QA/QP spans stitched parent→child, worker-side sub-spans from both
    # real substrates — and a metrics registry that yields latency
    # quantiles. Fleet telemetry (PR 10) rides the same pass: pipe workers
    # and socket hosts must surface in ``fleet_snapshot()`` under pid/host
    # labels with worker-side counters the client-local registry never
    # sees, the rolling SLO gate must pass over the exported records, and
    # every record's per-node dollar attribution must sum back to its §3.5
    # cost total. The trace file and the merged metrics snapshot are
    # uploaded as CI artifacts.
    import json as _json
    import math as _math

    from repro.obs.metrics import REGISTRY as obs_registry
    from repro.obs.export import read_jsonl
    from repro.obs.slo import SloTracker, default_policy

    trace_path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "results", "SMOKE_trace.jsonl")
    metrics_path = os.path.join(os.path.dirname(trace_path),
                                "SMOKE_metrics.json")
    if os.path.exists(trace_path):
        os.remove(trace_path)
    obs_registry.reset()
    try:
        fleet = {}
        for transport in ("local", "process", "socket"):
            rt_o = ServerlessRuntime(idx, RuntimeConfig(
                branching=2, max_level=1, transport=transport, qa_workers=1,
                invoke_timeout_s=120.0, obs_enabled=True,
                obs_trace_path=trace_path))
            try:
                res_o = rt_o.search(ds.queries, preds, k=10)
                assert np.array_equal(res_o.ids, ids_j), (
                    f"{transport}: obs-enabled ids diverged")
                assert res_o.stats == stats_j, (
                    f"{transport}: obs-enabled stats drift")
                fleet[transport] = obs_registry.fleet_snapshot()
            finally:
                rt_o.close()
        # Fleet-aggregation gate. The registry accumulates across the loop:
        # after the local pass there must be no remote sources; the process
        # pass must add pid-labelled pipe workers; the socket pass must add
        # host:port/pid-labelled hosts — each carrying worker.* instruments
        # that exist in the merged view but never client-locally.
        assert not fleet["local"]["remote"], (
            f"local transport leaked remote sources: "
            f"{sorted(fleet['local']['remote'])}")
        pid_src = [s for s in fleet["process"]["remote"]
                   if s.startswith("pid:")]
        assert pid_src, "pipe workers missing from fleet_snapshot()"
        host_src = [s for s in fleet["socket"]["remote"]
                    if "/pid:" in s and ":" in s.split("/", 1)[0]]
        assert host_src, (
            f"socket hosts missing from fleet_snapshot(): "
            f"{sorted(fleet['socket']['remote'])}")
        for label, sources in (("pipe", pid_src), ("host", host_src)):
            served = sum(
                fleet["socket"]["remote"][s]["counters"].get(
                    "worker.requests", 0) for s in sources)
            assert served > 0, f"{label} workers reported no requests"
        merged_c = fleet["socket"]["merged"]["counters"]
        local_c = fleet["socket"]["local"]["counters"]
        assert merged_c.get("worker.requests", 0) > 0
        assert "worker.requests" not in local_c, (
            "worker-side counters must not exist client-locally")
        assert "worker.handle_s" in fleet["socket"]["merged"]["histograms"]
        records = read_jsonl(trace_path)
        assert len(records) == 3, f"expected 3 trace records, got {len(records)}"
        by_transport = {r["meta"]["transport"]: r for r in records}
        for transport in ("process", "socket"):
            spans = by_transport[transport]["spans"]
            kinds = {s["attrs"].get("kind") for s in spans
                     if s["attrs"].get("kind")}
            assert kinds == {"co", "qa", "qp"}, (
                f"{transport}: missing node kinds in trace: {kinds}")
            wnames = {s["name"] for s in spans
                      if s["name"].startswith("worker.")}
            assert {"worker.compute", "worker.serialize"} <= wnames, (
                f"{transport}: worker-side sub-spans missing: {wnames}")
            ids_in_run = {s["id"] for s in spans}
            assert all(s["parent"] is None or s["parent"] in ids_in_run
                       for s in spans), f"{transport}: dangling span parent"
        snap = obs_registry.snapshot()
        h = snap["histograms"]["transport.process.invoke_s"]
        assert h["p50"] is not None and h["p99"] is not None
        obs_p50, obs_p99 = h["p50"], h["p99"]
        # Rolling-SLO gate: the monitors must evaluate p50/p99 (and the
        # retry/error budgets) from the live record stream, conclusively,
        # and the permissive default policy must pass a healthy smoke run.
        slo_tracker = SloTracker.from_records(records)
        slo_report = default_policy().evaluate(slo_tracker)
        assert slo_report.conclusive, (
            f"SLO monitors missing data: {slo_report.summary()}")
        assert slo_report.ok, f"SLO gate failed: {slo_report.summary()}"
        # Cost-attribution gate: per-node dollars must sum back to each
        # run's Eqs. 3–8 total (exact by construction, checked to float
        # noise), and every exported record must carry a fleet snapshot.
        for r in records:
            rows = r["run_trace"]["dollars_attributed"]
            total = r["run_trace"]["cost"]["total"]
            attributed = _math.fsum(x["total"] for x in rows)
            assert rows and abs(attributed - total) <= 1e-9 * total, (
                f"{r['meta']['transport']}: attributed ${attributed} != "
                f"run total ${total}")
            assert r.get("metrics") is not None, (
                f"{r['meta']['transport']}: record missing fleet metrics")
        with open(metrics_path, "w") as f:
            _json.dump({"fleet": obs_registry.fleet_snapshot(),
                        "slo": slo_report.to_json(),
                        "slo_monitors": slo_tracker.snapshot()},
                       f, indent=2, default=float)
    finally:
        obs_registry.disable()
        obs_registry.reset()

    # Search-under-mutation gate (live index, ISSUE 9): on its own small
    # build — streaming insert + delete, then drop-only compaction, must be
    # bitwise-invisible: ids AND SearchStats identical during-vs-after,
    # numpy ≡ jax ≡ serverless at every step, tombstones never returned,
    # and the §5.6 cache keeps serving entries compaction didn't touch.
    from repro.core.live import LiveIndex

    ds_m = synthetic.make_vector_dataset("sift1m", scale=0.002,
                                         num_queries=8, seed=13)
    idx_m = SquashIndex.build(
        ds_m.vectors, ds_m.attributes,
        SquashConfig(num_partitions=5, kmeans_iters=4, lloyd_iters=6),
        seed=13)
    live = LiveIndex(idx_m)
    rt_m = ServerlessRuntime(live, RuntimeConfig(cache_enabled=True))
    m0 = rt_m.search(ds_m.queries, [], k=10)
    live.insert(ds_m.vectors[:4] + 1e-3, ds_m.attributes[:4])
    live.delete(m0.ids[:, 0])
    m_during = rt_m.search(ds_m.queries, [], k=10)
    ref_n = idx_m.search(ds_m.queries, [], k=10, backend="numpy")
    ref_j = idx_m.search(ds_m.queries, [], k=10, backend="jax")
    assert np.array_equal(ref_n[0], ref_j[0]), "mutated numpy/jax diverged"
    assert ref_n[2] == ref_j[2], "mutated numpy/jax stats drift"
    assert np.array_equal(m_during.ids, ref_j[0]), "mutated serverless diverged"
    assert m_during.stats == ref_j[2], "mutated serverless stats drift"
    assert np.intersect1d(m_during.ids.ravel(), m0.ids[:, 0]).size == 0, (
        "tombstoned ids leaked into results")
    for pid in live.dirty_partitions():
        live.compact(pid, requantize=False)
    m_after = rt_m.search(ds_m.queries, [], k=10)
    assert np.array_equal(m_after.ids, m_during.ids), (
        "search during compaction != search after")
    assert np.array_equal(m_after.dists, m_during.dists)
    assert m_after.trace.cache_hits == ds_m.queries.shape[0], (
        "drop-only compaction must not evict untouched cache entries")
    ref_a = idx_m.search(ds_m.queries, [], k=10, backend="jax")
    assert np.array_equal(ref_a[0], m_during.ids)
    assert ref_a[2] == m_during.stats, "compaction changed stage counters"

    # Recall-targeted autotune gate: the calibrated per-partition profile
    # must hold recall at-or-above the static configuration's while
    # evaluating strictly fewer ADC candidates, with all three backends
    # still bitwise-identical under the same profile.
    static_recall = recalls["numpy"]
    static_adc = stats_n.adc_evals
    idx.autotune(recall_target=0.95, k=10, sample=48, seed=7)
    ids_tn, _, st_tn = idx.search(ds.queries, preds, k=10, backend="numpy")
    ids_tj, _, st_tj = idx.search(ds.queries, preds, k=10, backend="jax")
    assert np.array_equal(ids_tn, ids_tj), "autotuned backend ids diverged"
    assert st_tn == st_tj, f"autotuned stats drift: {st_tn} vs {st_tj}"
    rt_t = ServerlessRuntime(idx, RuntimeConfig(branching=3, max_level=2))
    res_t = rt_t.search(ds.queries, preds, k=10)
    assert np.array_equal(res_t.ids, ids_tj), "autotuned serverless diverged"
    tuned_recall = recall_of(ids_tn)
    assert tuned_recall >= min(0.95, static_recall), (
        f"autotuned recall {tuned_recall:.3f} fell below gate")
    assert st_tn.adc_evals < static_adc, (
        f"autotune must prune more: {st_tn.adc_evals} vs {static_adc}")

    print(f"[smoke] OK in {time.time() - t0:.1f}s — recall@10="
          f"{recalls['jax']:.3f}, ids identical across numpy/jax/serverless"
          f" (±cache, local AND process AND socket transport; process "
          f"measured {tp.measured_makespan_s:.2f}s cold / "
          f"{warm_p.measured_makespan_s:.2f}s warm; socket measured "
          f"{ts.measured_makespan_s:.2f}s cold over "
          f"{len(ts.worker_hosts)} host(s)); runtime: "
          f"{tr.invocations('qa')} QA + "
          f"{tr.invocations('qp')} QP, ${tr.cost['total']:.6f}/batch; "
          f"cached repeat: {len(t2.nodes)} invocation(s), "
          f"${t2.cost['total']:.6f}/batch; autotuned: recall@10="
          f"{tuned_recall:.3f} at {st_tn.adc_evals}/{static_adc} ADC evals; "
          f"obs: 3-transport trace at {os.path.relpath(trace_path)}, "
          f"process invoke p50={obs_p50 * 1e3:.1f}ms p99={obs_p99 * 1e3:.1f}ms"
          f"; fleet: {len(pid_src)} pipe + {len(host_src)} host source(s) "
          f"aggregated, SLO gate PASS "
          f"(p99={slo_tracker.snapshot()['latency_p99_s']:.2f}s), "
          f"metrics snapshot at {os.path.relpath(metrics_path)}"
          f"; live-index mutation gate: search during ≡ after compaction, "
          f"{live.live_count()} live rows")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="full-size runs (default: quick)")
    ap.add_argument("--skip-roofline", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny both-backends parity gate, then exit")
    ap.add_argument("--only", default=None,
                    help="comma-separated bench names")
    args = ap.parse_args(argv)
    if args.smoke:
        return smoke()
    quick = not args.full

    from benchmarks import (bench_ablations, bench_baselines, bench_cache,
                            bench_compression, bench_cost, bench_dre,
                            bench_invocation, bench_kernels, bench_kv_quant,
                            bench_qps, bench_recall, bench_scaling)
    suite = {
        "compression": bench_compression,
        "invocation": bench_invocation,
        "dre": bench_dre,
        # The one cache bench: §5.6 Zipf workload + Table 3 cache ratios
        # (the seed's separate bench_caching is folded into bench_cache).
        "cache": bench_cache,
        "cost": bench_cost,
        "kernels": bench_kernels,
        "recall": bench_recall,
        "qps": bench_qps,
        "scaling": bench_scaling,
        "baselines": bench_baselines,
        "ablations": bench_ablations,
        "kv_quant": bench_kv_quant,
    }
    only = set(args.only.split(",")) if args.only else None
    failures = []
    t_start = time.time()
    results_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "..", "results")
    for name, mod in suite.items():
        if only and name not in only:
            continue
        try:
            mod.run(quick=quick)
            # Persistence guarantee: every bench must leave its paper
            # artifact behind — a bench that runs green but writes nothing
            # breaks the trajectory (plots/CI consume these files).
            artifact = os.path.join(results_dir, f"BENCH_{name}.json")
            if not os.path.exists(artifact):
                raise FileNotFoundError(
                    f"bench ran but wrote no {os.path.basename(artifact)}")
        except Exception as e:
            print(f"[bench:{name}] FAILED: {type(e).__name__}: {e}")
            failures.append(name)
    if not args.skip_roofline and (only is None or "roofline" in only):
        print("\n" + "=" * 72 + "\nRoofline (subprocess, 512 host devices)\n"
              + "=" * 72)
        cmd = [sys.executable, "-m", "benchmarks.roofline",
               "--json", "roofline_quick.json"]
        env = dict(os.environ)
        env["PYTHONPATH"] = env.get("PYTHONPATH", "src")
        # Host devices only: this parent may hold the chip.
        env["JAX_PLATFORMS"] = "cpu"
        rc = subprocess.call(cmd, env=env)
        if rc != 0:
            failures.append("roofline")
    dt = time.time() - t_start
    print(f"\n[benchmarks] done in {dt:.0f}s; "
          f"{'ALL OK' if not failures else 'FAILURES: ' + ','.join(failures)}")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
