"""The partition-sharded plane on the served path, on four host devices.

``VectorSearchService(backend="jax")`` over ``SquashConfig(shards=4)``
places the stack once over a (data = 1, model = 4) mesh and serves through
the shard_map plane of ``repro.core.distributed``. One subprocess (the
device count must be set before jax starts) builds seeded d = 960 data, as
GIST1M has, at a few thousand rows, serves every case below with x64 on,
and reports what it saw; the tests here read that report. Cases:
``num_partitions`` 12 (three a device) and 10 (padded to 12), each under a
narrow and a wide predicate set.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PARTITIONS = (12, 10)
# Range width per attribute (of 16 values): joint selectivity (w/16)^4.
WIDTHS = {"narrow": 7, "wide": 13}
# Sharded and one-device planes run the same float64 ops per partition;
# the NumPy plane sums in its own order.
DIST_RTOL = 1e-9

_SCRIPT = textwrap.dedent("""
    import glob, json, os, sys, tempfile
    import numpy as np
    import jax
    jax.config.update("jax_enable_x64", True)
    from jax.profiler import ProfileData
    from repro.core import distributed
    from repro.core.attributes import Predicate
    from repro.core.pipeline import SquashConfig, SquashIndex
    from repro.obs.metrics import REGISTRY
    from repro.serve import ServiceConfig, VectorSearchService

    assert len(jax.devices()) == 4, jax.devices()
    parts, widths = json.loads(sys.argv[1]), json.loads(sys.argv[2])
    rng = np.random.default_rng(2**31 + 5)
    n, d, q = 3000, 960, 16
    centres = rng.normal(0, 4, (24, d))
    which = rng.integers(0, 24, n + 2 * q)
    x = centres[which] + rng.normal(0, 1, (n + 2 * q, d))
    base, queries = x[:n], x[n:]
    attrs = rng.integers(0, 16, (n, 4)).astype(np.float64)
    report = {}
    for p in parts:
        kw = dict(num_partitions=p, kmeans_iters=4, lloyd_iters=6)
        sharded = SquashIndex.build(base, attrs, SquashConfig(shards=4, **kw),
                                    seed=7)
        single = SquashIndex.build(base, attrs, SquashConfig(**kw), seed=7)
        REGISTRY.reset()
        REGISTRY.enable()
        trace = tempfile.mkdtemp()
        jax.profiler.start_trace(trace)
        try:
            stack = sharded.device_stack()
        finally:
            jax.profiler.stop_trace()
        gauges = REGISTRY.snapshot()["gauges"]
        REGISTRY.disable()
        REGISTRY.reset()
        pd = ProfileData.from_file(glob.glob(
            os.path.join(trace, "**", "*.xplane.pb"), recursive=True)[0])
        place = sum(ev.name == "squash.plane.place" for pl in pd.planes
                    if not pl.name.startswith("/device:")
                    for line in pl.lines for ev in line.events)
        svc = VectorSearchService(sharded, ServiceConfig(backend="jax"))
        for name, w in widths.items():
            lo = rng.integers(0, 16 - w + 1, 4)
            preds = [Predicate(attr=a, op="B", lo=float(lo[a]),
                               hi=float(lo[a] + w - 1)) for a in range(4)]
            ids, dists, _ = svc.query(queries[:q], preds, k=10)
            traces = sharded._trace_counter[0]
            ids2, dists2, _ = svc.query(queries[q:], preds, k=10)
            ref = [sharded.search(queries[s:s + q], preds, k=10,
                                  backend="numpy") for s in (0, q)]
            one = [single.search(queries[s:s + q], preds, k=10,
                                 backend="jax") for s in (0, q)]
            dist_ids, dist_d = distributed.distributed_search(
                sharded, queries[:q], preds, k=10)
            passing = np.ones(n, bool)
            for a in range(4):
                passing &= (attrs[:, a] >= lo[a]) & (attrs[:, a] <= lo[a] + w - 1)
            got = np.concatenate([ids, ids2])
            report[f"{p}-{name}"] = {
                "passing": int(passing.sum()),
                "ids": got.tolist(),
                "dists": np.concatenate([dists, dists2]).tolist(),
                "numpy_ids": np.concatenate([r[0] for r in ref]).tolist(),
                "numpy_dists": np.concatenate([r[1] for r in ref]).tolist(),
                "single_ids": np.concatenate([r[0] for r in one]).tolist(),
                "single_dists": np.concatenate([r[1] for r in one]).tolist(),
                "dist_ids": dist_ids.tolist(),
                "dist_dists": dist_d.tolist(),
                "violations": int((~passing[got[got >= 0]]).sum()),
                "same_stack": sharded.device_stack() is stack,
                "traces_after_first": traces,
                "traces_after_second": sharded._trace_counter[0],
                "stack_partitions": stack.num_partitions,
                "stack_sharding": str(stack.codes.sharding.spec),
                "gauges": gauges,
                "place_spans": place,
            }
    print("REPORT " + json.dumps(report))
""")


@pytest.fixture(scope="module")
def report():
    env = dict(os.environ)
    env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=4 "
                        + env.get("XLA_FLAGS", ""))
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-c", _SCRIPT, json.dumps(list(PARTITIONS)),
         json.dumps(WIDTHS)],
        capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    line = next(s for s in out.stdout.splitlines() if s.startswith("REPORT "))
    return json.loads(line[len("REPORT "):])


CASES = [f"{p}-{w}" for p in PARTITIONS for w in WIDTHS]


@pytest.mark.parametrize("case", CASES)
def test_sharded_ids_match_numpy_and_one_device(report, case):
    r = report[case]
    assert r["passing"] >= 10
    assert r["ids"] == r["numpy_ids"]
    assert r["ids"] == r["single_ids"]
    for other in ("numpy_dists", "single_dists"):
        for got, want in zip(r["dists"], r[other]):
            for a, b in zip(got, want):
                assert abs(a - b) <= DIST_RTOL * abs(b), (other, a, b)


@pytest.mark.parametrize("case", CASES)
def test_every_sharded_answer_passes_the_filter(report, case):
    r = report[case]
    assert r["violations"] == 0
    assert all(i >= 0 for row in r["ids"] for i in row)   # k rows pass


@pytest.mark.parametrize("case", CASES)
def test_second_request_neither_restacks_nor_retraces(report, case):
    r = report[case]
    assert r["same_stack"]
    assert r["traces_after_second"] == r["traces_after_first"]


@pytest.mark.parametrize("case", CASES)
def test_distributed_search_is_the_served_path(report, case):
    r = report[case]
    assert r["dist_ids"] == r["ids"][:16]
    assert r["dist_dists"] == r["dists"][:16]


@pytest.mark.parametrize("parts", PARTITIONS)
def test_placement_is_sharded_and_observed(report, parts):
    r = report[f"{parts}-narrow"]
    assert r["stack_partitions"] == 12                # 10 pads to 12
    assert r["stack_sharding"] == "PartitionSpec('model',)"
    assert r["gauges"]["dataplane.shards"] == 4
    assert r["gauges"]["dataplane.shard_partitions"] == 3
    assert r["place_spans"] == 1
