"""The four-chip cell on the CPU at a small size, and its all-gather reader.

``gist1m-sharded4.batch16`` serves through ``SquashConfig(shards=4)``; here
four host devices stand in for the chips, in a subprocess (the device count
is fixed before jax starts). The configuration keeps its d = 960, its
partitions and its shards; N and the build's iterations are cut. A sound
run comes out correct; an answer altered under the timed path, and the
bfloat16 control in the program's place, do not.
"""

import json
import os
import subprocess
import sys
import textwrap
from types import SimpleNamespace

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

from squashbench import harness, traces  # noqa: E402

CELL = "gist1m-sharded4.batch16"

_SCRIPT = textwrap.dedent("""
    import json, sys, time
    import numpy as np
    import jax
    jax.config.update("jax_enable_x64", False)
    sys.path.insert(0, sys.argv[1])
    from squashbench import harness
    from repro.core.pipeline import SquashIndex

    assert len(jax.devices()) == 4
    cell = harness.load_cell(sys.argv[2])
    cell.config["dataset"].update(n=4000, clusters=23)
    cell.config["index"].update(kmeans_iters=3, lloyd_iters=4)
    cell.traffic.update(query_pool=128)
    if sys.argv[3] == "altered":
        real = SquashIndex.search

        def broken(self, *a, **kw):
            ids, dists, stats = real(self, *a, **kw)
            ids = ids.copy()
            ids[:, 0] = (ids[:, 0] + 1) % 4000
            return ids, dists, stats

        SquashIndex.search = broken
    r = harness.run_cell(cell, seed=2**31 + 23, seconds=0.4, trace=False,
                         t_start=time.perf_counter(),
                         control=sys.argv[3] == "control",
                         devices=jax.devices()[:4])
    print("RESULT " + json.dumps(r))
    print("checks", r["checks"], file=sys.stderr)
""")


def _run(mode: str) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=4 "
                        + env.get("XLA_FLAGS", ""))
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run([sys.executable, "-c", _SCRIPT, BENCH, CELL, mode],
                         capture_output=True, text=True, env=env,
                         timeout=600, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-3000:]
    line = next(s for s in out.stdout.splitlines() if s.startswith("RESULT "))
    return json.loads(line[len("RESULT "):])


@pytest.mark.parametrize("mode", ["sound", "altered", "control"])
def test_sharded_cell_is_correct_only_when_sound(mode):
    r = _run(mode)
    assert r["device"]["count"] == 4
    if mode == "control":
        assert not r["correct"]
        assert r["checks"]["dist_rel_err"]["value"] > \
            r["checks"]["dist_rel_err"]["limit"]
        return
    assert r["diagnostics"]["shapes"]["p"] == 12
    assert r["diagnostics"]["shapes"]["d"] == 960
    if mode == "sound":
        assert r["correct"], r["checks"]
        assert r["failed"] == 0
        assert r["diagnostics"]["compiles_in_window"] == 0
        assert {"qps", "setup_s", "peak_hbm_gib"} <= set(r["metrics"])
    else:
        assert not r["correct"], r["checks"]


def test_cell_spans_four_chips_with_its_metrics():
    cell = harness.load_cell(CELL)
    assert cell.chips == 4 and cell.config["index"]["shards"] == 4
    assert cell.config["index"]["num_partitions"] % 4 == 0
    names = {m["name"] for m in cell.per_layer}
    assert {"allgather_ms.batch", "idle_share.batch", "hamming_roofline",
            "adc_roofline", "visits_per_query.batch"} <= names


def _view(op_seconds, chips=4, requests=10):
    reduced = traces.Reduced(window_s=1.0, busy_s=0.5, chips=chips,
                             op_seconds=op_seconds, device_ops=[],
                             idle_gaps=[])
    return SimpleNamespace(trace=reduced,
                           served=SimpleNamespace(end=[0.0] * requests))


def test_allgather_reader_sums_the_collective_per_chip_and_request():
    read = harness.load_reader("allgather_ms.batch")
    ops = {"jit_search/all-gather": 0.004, "jit_search/all-gather.1": 0.002,
           "jit_search/all-gather-start.2": 0.001,
           "jit_search/packed_hamming_stacked.1": 3.0}
    assert read(_view(ops)) == pytest.approx(1e3 * 0.007 / 4 / 10)
    assert read(_view({"jit_plane/sort": 1.0})) is None     # one chip
    assert read(SimpleNamespace(trace=None, served=None)) is None
