"""Paper Fig. 9 — queries/second under FaaS parallelism.

No AWS in this container, so QPS is *derived*: per-stage compute is measured
on this host (QA-side filtering + Alg. 1, QP-side pipeline per partition),
then the serverless makespan is assembled from the invocation-tree simulator
(Alg. 2) exactly as the paper's run-time entities compose:

  makespan ≈ tree_launch + QA work + max_p(QP work) + merge
  QPS      = batch_queries / makespan per QA wave · N_QA-way parallelism

A single-server baseline (the paper's c7i comparison) runs the same pipeline
serially with process-level parallelism bounded by host cores.
"""

from __future__ import annotations

import numpy as np

from benchmarks.common import header, save_json, timed
from repro.core import attributes as am, partitions as pm
from repro.core.invocation import InvocationSim, tree_size
from repro.core.pipeline import SquashConfig, SquashIndex
from repro.data.synthetic import default_predicates, make_vector_dataset
from repro.serve.vector_service import ServiceConfig, VectorSearchService

FAAS_CONFIGS = {10: (10, 1), 20: (4, 2), 84: (4, 3), 155: (5, 3),
                258: (6, 3), 340: (4, 4)}

BACKEND_BATCH = 64  # Q for the numpy-vs-jax data-plane shootout


def backend_shootout(quick: bool) -> dict:
    """Single-host data-plane comparison: numpy loop vs batched jax plane.

    Same index, same Q=64 query batch, same predicates — best-of-3 wall
    time per backend (jax timed post-trace, i.e. DRE-warm), identical-ids
    check.
    """
    scale = 0.005 if quick else 0.02
    ds = make_vector_dataset("sift1m", scale=scale, num_queries=BACKEND_BATCH)
    preds = default_predicates(ds.attr_cardinality)
    idx = SquashIndex.build(ds.vectors, ds.attributes,
                            SquashConfig(num_partitions=10))
    svc = VectorSearchService(idx, ServiceConfig(backend="auto"))
    svc.warmup(BACKEND_BATCH)                        # trace the jax plane
    (ids_j, _, _), t_jax = timed(svc.query, ds.queries, preds, backend="jax")
    (ids_n, _, _), t_np = timed(svc.query, ds.queries, preds,
                                backend="numpy")
    qps_np, qps_jax = BACKEND_BATCH / t_np, BACKEND_BATCH / t_jax
    row = {
        "n": ds.n, "queries": BACKEND_BATCH,
        "qps_numpy": qps_np, "qps_jax": qps_jax,
        "speedup": qps_jax / max(qps_np, 1e-9),
        "ids_identical": bool(np.array_equal(ids_j, ids_n)),
    }
    print(f"  backends @Q={BACKEND_BATCH}: numpy={qps_np:8.0f} qps  "
          f"jax={qps_jax:8.0f} qps  ({row['speedup']:.1f}x, ids "
          f"{'identical' if row['ids_identical'] else 'DIVERGED'})")
    return row


def measure_stage_times(preset: str, quick: bool):
    scale = 0.01 if preset.endswith("1m") else 0.001
    nq = 32 if quick else 100
    ds = make_vector_dataset(preset, scale=scale, num_queries=nq)
    preds = default_predicates(ds.attr_cardinality)
    p = 10 if preset.endswith("1m") else 20
    cfg = SquashConfig(num_partitions=p)
    idx = SquashIndex.build(ds.vectors, ds.attributes, cfg)

    # QA-side: predicate parse + filter mask + Algorithm 1.
    def qa_side():
        r = am.build_r_lookup(idx.attr_index, preds)
        f_one = np.asarray(am.filter_mask(r, idx.attr_index.codes))
        return pm.select_partitions(
            ds.queries.astype(np.float64), idx.partitioning.centroids, f_one,
            [pt.vector_ids for pt in idx.parts], idx.partitioning.threshold,
            10)

    (visit, cands), t_qa = timed(qa_side, repeats=2)

    # QP-side: full per-partition pipeline for the busiest partition.
    stats_probe = idx.search(ds.queries[:4], preds, 10)[2]
    _, t_all = timed(idx.search, ds.queries, preds, 10, repeats=1)
    t_qp_total = max(t_all - t_qa, 1e-4)
    visits = max(int(visit.sum()), 1)
    t_qp_per_visit = t_qp_total / visits
    return {
        "dataset": preset, "n": ds.n, "queries": nq,
        "t_qa_s": t_qa, "t_qp_per_visit_s": t_qp_per_visit,
        "visits_per_query": visits / nq, "partitions": p,
    }


def serverless_qps(meas: dict, n_qa: int, batch: int = 1000) -> dict:
    f, lmax = FAAS_CONFIGS[n_qa]
    sim = InvocationSim(branching=f, max_level=lmax, node_compute=0.0)
    t_tree = sim.makespan()
    q_per_qa = batch / n_qa
    scale_q = q_per_qa / meas["queries"]
    t_qa = meas["t_qa_s"] * scale_q
    # each QA launches one QP per visited partition; QPs run in parallel,
    # each processing its share of the QA's queries
    t_qp = meas["t_qp_per_visit_s"] * meas["visits_per_query"] * q_per_qa \
        / meas["partitions"] * 4.0   # 1770MB Lambda ≈ 1/4 of a host core-set
    t_merge = 0.002 * np.log2(max(n_qa, 2))
    makespan = t_tree + t_qa + t_qp + t_merge
    return {"n_qa": n_qa, "makespan_s": makespan, "qps": batch / makespan}


def run(quick: bool = True) -> dict:
    header("Fig. 9 — QPS (derived from measured stage times + Alg. 2 sim)")
    presets = ["sift1m", "gist1m"] if quick else ["sift1m", "gist1m",
                                                  "sift10m", "deep10m"]
    out = []
    backends = backend_shootout(quick)
    for preset in presets:
        meas = measure_stage_times(preset, quick)
        best = None
        for n_qa in FAAS_CONFIGS:
            r = serverless_qps(meas, n_qa)
            r.update(dataset=preset)
            out.append(r)
            if best is None or r["qps"] > best["qps"]:
                best = r
        # server baseline: same pipeline, host-bound parallelism (≈8 workers)
        t_serial = (meas["t_qa_s"] + meas["t_qp_per_visit_s"]
                    * meas["visits_per_query"] * meas["queries"]
                    / meas["partitions"]) / meas["queries"]
        server_qps = 8.0 / max(t_serial, 1e-6)
        out.append({"dataset": preset, "n_qa": 0, "makespan_s": None,
                    "qps": server_qps, "server_baseline": True})
        print(f"  {preset:8s} best FaaS QPS={best['qps']:.0f} (N_QA="
              f"{best['n_qa']}), server-8core QPS={server_qps:.0f} → "
              f"{best['qps'] / server_qps:.1f}x")
    save_json("BENCH_qps", {"rows": out, "backend_shootout": backends})
    return {"rows": out, "backend_shootout": backends}


if __name__ == "__main__":
    run()
