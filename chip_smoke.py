#!/usr/bin/env python3
"""Smoke run of the served filtered-search path on one TPU chip.

    python chip_smoke.py [--seed N]          # one chip
    python chip_smoke.py --four-chips        # partition-sharded, four chips

One process, float32 (x64 off), no child processes. The default run has two
phases, each served through ``VectorSearchService(backend="jax")`` →
``SquashIndex.search`` → ``dataplane.batched_stage345`` with its Pallas
kernels compiled:

* ``sift1m`` — the sift1m stand-in at its full size (N = 1M, d = 128, A = 4
  attributes under the §5.1 predicates at ~8% selectivity) and the default
  ``SquashConfig`` (P = 10, 4 bits/dim, up to 12 bits on a hot dim, so
  M+1 = 4097 and Stage 4 spreads the hot dims over chunk lanes, D' > d).
* ``sift1m-7bit`` — N cut to 100k, at most 7 bits per dim, so M+1 <= 129
  and every dim fits one lane (D' = d).

Both run the Pallas Hamming and ADC kernels.

Each serves three requests of 16 queries, checks that the compiled plane
holds the Pallas kernels, and holds the chip's answers to the NumPy
reference plane (``backend="numpy"``) and to brute-force ground truth within
the tolerances below. ``--four-chips`` instead serves through
``SquashConfig(shards=4)``, the stack sharded over a (data=1, model=4)
mesh, in two phases:

* ``four-chips`` — the full-size sift1m index: the sharded plane's ids must
  equal the one-chip plane's, and a second request must not retrace.
* ``gist-four-chips`` — GIST1M's shapes (d = 960, so 30 words of 1-bit
  codes, P = 12 partitions, three a chip) with N cut to 100k: the sharded
  plane's answers on one batch are held to the NumPy plane's and to ground
  truth within the tolerances below.

Timings printed on the way are smoke timings, not benchmark metrics. The
last line of stdout is the JSON verdict; without a TPU, or when any check
fails, the script exits non-zero before printing it. The persistent compile
cache lives where ``JAX_COMPILATION_CACHE_DIR`` says, else in
``<repo>/.jax_cache``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))

K = 10
BATCH = 16                 # queries per request
REQUESTS = 3
# Agreement with the NumPy reference plane on the same queries.
RECALL_TOL = 0.01          # |recall@10(chip) - recall@10(reference)|
MIN_ID_OVERLAP = 0.99      # mean |top-10 ids ∩ reference top-10| / 10
DIST_RTOL = 1e-4           # distances of ids both planes return


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def timed(phase: str, name: str, fn, *args, **kw):
    """Run ``fn`` and print its wall-clock seconds."""
    t0 = time.perf_counter()
    out = fn(*args, **kw)
    log(f"[{phase}] {name}: {time.perf_counter() - t0:.3f} s")
    return out


def kernel_calls(index, stacked, q: int):
    """Pallas kernels in the compiled plane the service runs for a Q batch,
    and the ``dataplane.adc.lanes`` gauge its trace sets."""
    import jax
    import jax.numpy as jnp

    from repro.core import dataplane
    from repro.obs.metrics import REGISTRY

    keep_s, take_s = dataplane.static_counts(stacked.n_max, index.config, K,
                                             index.profile)
    plane = dataplane.make_plane(k=K, keep_s=keep_s, take_s=take_s,
                                 refine=index.config.enable_refine)
    p, n_max, d = stacked.num_partitions, stacked.n_max, index.dim
    sds = jax.ShapeDtypeStruct
    REGISTRY.enable()
    try:
        text = plane.lower(sds((q, d), jnp.float32), stacked,
                           sds((q, p, n_max), jnp.bool_),
                           sds((q, p), jnp.int32),
                           sds((q, p), jnp.int32)).compile().as_text()
        lanes = REGISTRY.snapshot()["gauges"]["dataplane.adc.lanes"]
    finally:
        REGISTRY.disable()
        REGISTRY.reset()
    return text.count('custom_call_target="tpu_custom_call"'), lanes


def agreement(phase: str, ids, dists, ref_ids, ref_dists, gt_ids) -> None:
    """Hold one plane's top-k to the reference's and to ground truth."""
    import numpy as np

    from benchmarks.common import recall_at_k

    r_chip = recall_at_k(ids, gt_ids)
    r_ref = recall_at_k(ref_ids, gt_ids)
    overlap, rel = [], [0.0]
    for qi in range(ids.shape[0]):
        mine = {int(i): float(x) for i, x in zip(ids[qi], dists[qi]) if i >= 0}
        ref = {int(i): float(x)
               for i, x in zip(ref_ids[qi], ref_dists[qi]) if i >= 0}
        both = mine.keys() & ref.keys()
        overlap.append(len(both) / max(len(ref), 1))
        rel += [abs(mine[i] - ref[i]) / max(abs(ref[i]), 1e-30) for i in both]
    mean_overlap = float(np.mean(overlap))
    log(f"[{phase}] recall@{K}: chip {r_chip:.4f}, reference {r_ref:.4f}; "
        f"id overlap {mean_overlap:.4f}; ids identical: "
        f"{np.array_equal(ids, ref_ids)}; max distance rel. diff "
        f"{max(rel):.3e}")
    check(abs(r_chip - r_ref) <= RECALL_TOL,
          f"{phase}: recall {r_chip} vs reference {r_ref}")
    check(mean_overlap >= MIN_ID_OVERLAP,
          f"{phase}: id overlap {mean_overlap} < {MIN_ID_OVERLAP}")
    check(max(rel) <= DIST_RTOL,
          f"{phase}: distance rel. diff {max(rel)} > {DIST_RTOL}")


def build(phase: str, *, scale: float, seed: int, config):
    """Dataset, §5.1 predicates and the built index."""
    from repro.core.pipeline import SquashIndex
    from repro.data import synthetic

    ds = timed(phase, "generate", synthetic.make_vector_dataset, "sift1m",
               scale=scale, num_queries=BATCH * REQUESTS, seed=seed)
    preds = synthetic.default_predicates(ds.attr_cardinality,
                                         ds.attributes.shape[1])
    index = timed(phase, "build", SquashIndex.build, ds.vectors, ds.attributes,
                  config, seed=seed)
    return ds, preds, index


def stack(phase: str, index):
    import jax

    from repro.core import dataplane

    stacked = timed(phase, "stack + upload",
                    lambda: jax.block_until_ready(index.device_stack()))
    m1 = max(pt.quant.boundaries.shape[0] for pt in index.parts)
    lanes = int(stacked.lane_dim.shape[-1])
    chunks = max(dataplane.chunk_lanes(pt.quant.cells) for pt in index.parts)
    log(f"[{phase}] N={sum(pt.size for pt in index.parts)} d={index.dim} "
        f"P={stacked.num_partitions} n_max={stacked.n_max} M+1={m1} "
        f"stage4 lanes D'={lanes} (chunk lanes in use: up to {chunks})")
    return stacked


def serve_phase(phase: str, *, scale: float, seed: int, config) -> None:
    """Serve three requests on the chip and hold them to the reference."""
    import numpy as np

    from repro.data import synthetic
    from repro.serve import ServiceConfig, VectorSearchService

    ds, preds, index = build(phase, scale=scale, seed=seed, config=config)
    stacked = stack(phase, index)
    svc = VectorSearchService(index, ServiceConfig(backend="jax"))
    out = []
    for r in range(REQUESTS):
        batch = ds.queries[r * BATCH:(r + 1) * BATCH]
        label = "request 1 (compile)" if r == 0 else f"request {r + 1} (warm)"
        out.append(timed(phase, label, svc.query, batch, preds, k=K))
    check(svc.queries_served["jax"] == BATCH * REQUESTS,
          f"{phase}: served {svc.queries_served}")
    ids = np.concatenate([o[0] for o in out])
    dists = np.concatenate([o[1] for o in out])

    calls, lanes = timed(phase, "kernel check", kernel_calls, index, stacked,
                         BATCH)
    log(f"[{phase}] Pallas kernels in the compiled plane: {calls}; "
        f"dataplane.adc.lanes gauge: {lanes}")
    check(calls == 2, f"{phase}: {calls} tpu_custom_call, want 2")

    ref_ids, ref_dists, _ = timed(phase, "numpy reference", index.search,
                                  ds.queries, preds, k=K, backend="numpy")
    gt_ids, _ = timed(phase, "ground truth", synthetic.ground_truth, ds,
                      preds, K)
    agreement(phase, ids, dists, ref_ids, ref_dists, gt_ids)


def sharded_copy(index, shards: int):
    """The same built index, served with its stack sharded over ``shards``
    chips (its own stack and plane caches)."""
    import dataclasses

    from repro.core.pipeline import SquashIndex

    return SquashIndex(dataclasses.replace(index.config, shards=shards),
                       index.partitioning, index.parts, index.attr_index,
                       index.dim)


def four_chip_phase(seed: int, scale: float = 1.0) -> None:
    """The served sharded plane on a (1, 4) mesh against the one-chip plane,
    on the sift1m index (full size at ``scale`` 1)."""
    import jax
    import numpy as np

    from repro.core.pipeline import SquashConfig
    from repro.serve import ServiceConfig, VectorSearchService

    phase = "four-chips"
    check(len(jax.devices()) >= 4, f"{phase}: {len(jax.devices())} devices")
    ds, preds, index = build(phase, scale=scale, seed=seed,
                             config=SquashConfig())
    sharded = sharded_copy(index, 4)
    timed(phase, "place (sharded stack)",
          lambda: jax.block_until_ready(sharded.device_stack()))
    svc = VectorSearchService(sharded, ServiceConfig(backend="jax"))
    queries = ds.queries[:BATCH]
    ids4, d4, _ = timed(phase, "sharded request 1 (compile)", svc.query,
                        queries, preds, k=K)
    traces = sharded._trace_counter[0]
    timed(phase, "sharded request 2 (warm)", svc.query,
          ds.queries[BATCH:2 * BATCH], preds, k=K)
    check(sharded._trace_counter[0] == traces, f"{phase}: retraced")
    ids1, d1, _ = timed(phase, "one-chip search (compile)", index.search,
                        queries, preds, k=K, backend="jax")
    ref_ids, _, _ = timed(phase, "numpy reference", index.search, queries,
                          preds, k=K, backend="numpy")
    same = np.array_equal(ids4, ids1)
    close = np.allclose(d4, d1, rtol=DIST_RTOL)
    log(f"[{phase}] ids identical: {same}; distances allclose: {close}; "
        f"ids identical to the NumPy plane: {np.array_equal(ids4, ref_ids)}")
    check(same and close, f"{phase}: sharded and one-chip planes differ")


def gist_four_chip_phase(seed: int, scale: float = 0.1) -> None:
    """The served sharded plane at GIST1M's shapes against the NumPy plane."""
    import jax
    import numpy as np

    from repro.core.pipeline import SquashConfig, SquashIndex
    from repro.data import synthetic
    from repro.serve import ServiceConfig, VectorSearchService

    phase = "gist-four-chips"
    ds = timed(phase, "generate", synthetic.make_vector_dataset, "gist1m",
               scale=scale, num_queries=BATCH, seed=seed)
    preds = synthetic.default_predicates(ds.attr_cardinality,
                                         ds.attributes.shape[1])
    index = timed(phase, "build", SquashIndex.build, ds.vectors,
                  ds.attributes, SquashConfig(num_partitions=12, shards=4),
                  seed=seed)
    stacked = stack(phase, index)
    check(str(stacked.codes.sharding.spec) == "PartitionSpec('model',)",
          f"{phase}: stack placed as {stacked.codes.sharding}")
    svc = VectorSearchService(index, ServiceConfig(backend="jax"))
    ids, dists, _ = timed(phase, "request 1 (compile)", svc.query,
                          ds.queries, preds, k=K)
    timed(phase, "request 2 (warm)", svc.query, ds.queries, preds, k=K)
    ref_ids, ref_dists, _ = timed(phase, "numpy reference", index.search,
                                  ds.queries, preds, k=K, backend="numpy")
    gt_ids, _ = timed(phase, "ground truth", synthetic.ground_truth, ds,
                      preds, K)
    agreement(phase, ids, dists, ref_ids, ref_dists, gt_ids)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="serve the sharded four-chip plane and compare it "
                         "with one chip and the NumPy plane")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{devices[0].platform!r}); nothing was run", file=sys.stderr)
        return 1
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(REPO, ".jax_cache"))
    jax.config.update("jax_enable_x64", False)
    sys.path.insert(0, os.path.join(REPO, "src"))
    from repro.core.pipeline import SquashConfig

    log(f"device: {devices[0].device_kind} x{len(devices)}")
    if args.four_chips:
        four_chip_phase(args.seed)
        gist_four_chip_phase(args.seed)
    else:
        serve_phase("sift1m", scale=1.0, seed=args.seed,
                    config=SquashConfig())
        serve_phase("sift1m-7bit", scale=0.1, seed=args.seed,
                    config=SquashConfig(max_bits_per_dim=7))
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
