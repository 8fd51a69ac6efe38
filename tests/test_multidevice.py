"""Real multi-device integration: run the search on a (data = 2, model = 2)
mesh of 4 XLA host devices in a subprocess (the flag must be set before jax
init, so these tests shell out) and check it against the one-host pipeline.
"""

import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script: str) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=4 "
                        + env.get("XLA_FLAGS", ""))
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                         capture_output=True, text=True, env=env,
                         timeout=420)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_distributed_search_on_4device_mesh():
    print(_run("""
        import jax, numpy as np
        from jax.sharding import Mesh
        from repro.core.distributed import distributed_search
        from repro.core.pipeline import SquashConfig, SquashIndex
        from repro.data.synthetic import (default_predicates, ground_truth,
                                          make_vector_dataset)
        ds = make_vector_dataset("sift1m", scale=0.003, num_queries=8)
        preds = default_predicates(ds.attr_cardinality)
        idx = SquashIndex.build(ds.vectors, ds.attributes,
                                SquashConfig(num_partitions=4))
        devs = np.array(jax.devices()).reshape(2, 2)
        mesh = Mesh(devs, ("data", "model"))
        ids, dists = distributed_search(idx, ds.queries, preds, k=5,
                                        mesh=mesh)
        ids_ref, d_ref, _ = idx.search(ds.queries, preds, 5)
        for a, b in zip(ids_ref, ids):
            assert set(a.tolist()) == set(b.tolist())
        print("distributed-search-4dev OK")
    """))
