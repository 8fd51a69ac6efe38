"""The whole run of a cell, on the CPU at a small size, without the look
for a chip: sound runs come out correct; the control and each fault the
cells can have, planted under the timed path, come out not correct.

Faults a search cell can have: half of a batch left unanswered, and an
answer altered where it is produced. A step that returns its state
unchanged (training) and the exchange between chips (no cell spans chips)
do not apply.
"""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))

from squashbench import harness  # noqa: E402

# (configuration, traffic mix) pairs, read from their files directly so the
# small runs cover every mix under bench/ whichever cells BENCHMARK.json
# holds.
CELLS = ("sift1m.batch16", "sift1m-7bit.batch16", "sift1m-7bit.online")
LATENCY = ({"name": "p50_ms", "unit": "ms"}, {"name": "p95_ms", "unit": "ms"})


def small(workload: str) -> harness.Cell:
    config, traffic = workload.rsplit(".", 1)
    with open(os.path.join(BENCH, "configs", config + ".json")) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "traffic", traffic + ".json")) as f:
        traffic = json.load(f)
    config["dataset"].update(n=6000, clusters=23)
    config["index"].update(num_partitions=4, kmeans_iters=3, lloyd_iters=4)
    traffic.update(query_pool=128)
    rates = [{"name": "qps", "unit": "queries/s"}]
    if traffic["loop"] == "open":
        traffic["rate_per_s"] = 20.0
        rates = list(LATENCY)
    return harness.Cell(
        name=workload, chips=1, config=config, traffic=traffic,
        end_to_end=rates + [{"name": "peak_hbm_gib", "unit": "GiB"},
                            {"name": "setup_s", "unit": "s"}],
        per_layer=[])


def run(workload, **kw):
    return harness.run_cell(small(workload), seed=2**31 + 17, seconds=0.4,
                            trace=False, t_start=time.perf_counter(), **kw)


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(workload):
    r = run(workload)
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert list(r)[-1] == "checks"
    names = {m for m in r["metrics"]}
    assert "setup_s" in names and "peak_hbm_gib" in names
    assert ("qps" in names) != ("p95_ms" in names)
    assert r["diagnostics"]["compiles_in_window"] == 0


def _half_unanswered(ids, dists):
    h = max(1, ids.shape[0] // 2)
    ids[h:], dists[h:] = -1, np.inf
    if ids.shape[0] == 1:            # one query: half of its answer
        ids[0, ids.shape[1] // 2:] = -1
        dists[0, dists.shape[1] // 2:] = np.inf
    return ids, dists


def _answer_altered(ids, dists):
    ids[:, 0] = (ids[:, 0] + 1) % 6000
    return ids, dists


@pytest.mark.parametrize("fault", [_half_unanswered, _answer_altered],
                         ids=["half_unanswered", "answer_altered"])
@pytest.mark.parametrize("workload", CELLS)
def test_fault_under_the_timed_path_is_caught(workload, fault, monkeypatch):
    from repro.core.pipeline import SquashIndex

    real = SquashIndex.search

    def broken(self, *a, **kw):
        ids, dists, stats = real(self, *a, **kw)
        ids, dists = fault(ids.copy(), dists.copy())
        return ids, dists, stats

    monkeypatch.setattr(SquashIndex, "search", broken)
    r = run(workload)
    assert not r["correct"], r["checks"]


@pytest.mark.parametrize("workload", CELLS)
def test_control_is_not_correct(workload):
    r = run(workload, control=True)
    assert not r["correct"]
    assert r["checks"]["dist_rel_err"]["value"] > \
        r["checks"]["dist_rel_err"]["limit"]


def test_no_accelerator_exits_nonzero_and_prints_no_result(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "sift1m.batch16", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_every_metric_has_a_reader_and_every_cell_its_files():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for m in bench["per_layer"]:
        assert callable(harness.load_reader(m["name"]))
    for w in bench["workloads"]:
        cell = harness.load_cell(w["name"])
        assert cell.end_to_end and cell.per_layer
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
