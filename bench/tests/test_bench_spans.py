"""Idle time put down to host spans (``squashbench.hostspans``) and the
span report's readings.

Hand-built traces check the naming of gaps under nested spans and the
clipping to ``bench.window``; the recorded v5e trace (harness spans only)
checks that the gaps keep the names and lengths ``traces.reduce`` gives
them; a small cell on the CPU checks the whole report with the program's
obs registry on and off.
"""

import json
import os
import sys
import time
from types import SimpleNamespace as NS

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

import span_report  # noqa: E402
from squashbench import harness, hostspans, traces  # noqa: E402

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                       "v5e_7bit_n100k.xplane.pb.gz")


def _trace(ops, spans):
    """A ProfileData stand-in: one chip running ``ops`` [(start, end)] and
    one host thread holding ``spans`` [(name, start, end)]."""
    def ev(name, s, e):
        return NS(name=name, start_ns=s, duration_ns=e - s)

    device = NS(name="/device:TPU:0", lines=[
        NS(name=traces.OPS_LINE,
           events=[ev("%fusion.1 = f32[8] fusion()", s, e) for s, e in ops]),
        NS(name=traces.MODULES_LINE, events=[ev("jit_plane(1)", 0, 10**6)])])
    host = NS(name="/host:CPU", lines=[
        NS(name="python", events=[ev(n, s, e) for n, s, e in spans])])
    return NS(planes=[host, device])


def test_gap_inside_nested_spans_goes_to_the_innermost():
    pd = _trace(ops=[(0, 100), (400, 1000)], spans=[
        ("bench.window", 0, 1000), ("bench.request", 50, 950),
        ("squash.request", 60, 940), ("squash.alg1", 110, 390)])
    red = hostspans.reduce(pd)
    assert red.idle_gaps == [("squash.alg1", pytest.approx(300e-9))]
    assert red.idle_by_span == pytest.approx({"squash.alg1": 300e-9})
    assert red.named_share() == 1.0


def test_gap_split_between_leaves_goes_to_their_parent():
    # Neither leaf covers half of [100, 400); squash.request covers it all
    # and is shorter than bench.request.
    pd = _trace(ops=[(0, 100), (400, 1000)], spans=[
        ("bench.window", 0, 1000), ("bench.request", 50, 950),
        ("squash.request", 60, 940), ("squash.stage1", 90, 240),
        ("squash.alg1", 260, 420)])
    assert hostspans.reduce(pd).idle_gaps == [
        ("squash.request", pytest.approx(300e-9))]


def test_uncovered_gap_is_other():
    spans = [("squash.alg1", 10, 20)]
    assert hostspans.name_gap(spans, 30, 40) == "other"
    # Most coverage wins where no span covers half; the shorter on a tie.
    spans = [("squash.a", 0, 14), ("squash.b", 17, 30), ("squash.c", 17, 60)]
    assert hostspans.name_gap(spans, 10, 20) == "squash.a"
    assert hostspans.name_gap(spans[1:], 10, 20) == "squash.b"


def test_span_seconds_and_idle_are_clipped_to_the_window():
    pd = _trace(ops=[(50, 300), (900, 2000)], spans=[
        ("bench.window", 100, 1000), ("squash.alg1", 0, 500),
        ("squash.plane.fetch", 600, 1500), ("squash.gc", 2000, 2100)])
    red = hostspans.reduce(pd)
    assert red.window_s == pytest.approx(900e-9)
    assert red.span_seconds == pytest.approx(
        {"squash.alg1": 400e-9, "squash.plane.fetch": 400e-9})
    # The one gap, [300, 900), is half in fetch and a third in alg1.
    assert red.idle_by_span == pytest.approx({"squash.plane.fetch": 600e-9})
    assert red.idle_s == pytest.approx(600e-9)


def test_longest_requests_list_the_spans_inside():
    pd = _trace(ops=[(0, 10)], spans=[
        ("squash.request", 0, 100), ("squash.alg1", 10, 60),
        ("squash.gc", 20, 50), ("squash.request", 200, 230),
        ("squash.alg1", 205, 215)])
    longest = hostspans.longest_requests(pd, top=1)
    assert longest == [{"seconds": pytest.approx(100e-9), "spans": {
        "squash.alg1": pytest.approx(50e-9),
        "squash.gc": pytest.approx(30e-9)}}]


@pytest.fixture(scope="module")
def fixture_pd():
    return traces.load(FIXTURE)


def test_recorded_trace_keeps_every_gap_name_and_length(fixture_pd):
    red = hostspans.reduce(fixture_pd)
    old = traces.reduce(fixture_pd)
    assert red.idle_gaps == old.idle_gaps
    assert red.window_s == old.window_s
    assert red.idle_s == pytest.approx(old.window_s - old.busy_s, rel=1e-9)
    assert set(red.span_seconds) == {"bench.request"}
    # A trace from a program without the spans gives no layer reading.
    assert hostspans.layer_readings(red, {}, 26) == {}


@pytest.mark.parametrize("metric,span_seconds,counters,value", [
    ("stage1_ms.batch", {"squash.stage1": 0.5}, {}, 50.0),
    ("alg1_ms.batch", {"squash.alg1": 0.9, "squash.stage1": 0.1}, {}, 90.0),
    ("plane_setup_ms.batch",
     {"squash.plane.setup": 0.2, "squash.plane.upload": 0.1}, {}, 30.0),
    ("upload_mib.batch", {},
     {"dataplane.upload.bytes": 3 * 31 * 2**20, "serve.requests": 3}, 31.0),
])
def test_layer_reading(metric, span_seconds, counters, value):
    red = hostspans.SpanReduction(window_s=1.0, idle_s=0.5,
                                  span_seconds=span_seconds,
                                  idle_by_span={}, idle_gaps=[])
    readings = hostspans.layer_readings(red, counters, requests=10)
    assert readings[metric] == pytest.approx(value)
    assert ("upload_mib.batch" in readings) == bool(counters)


def _small_cell():
    with open(os.path.join(BENCH, "configs", "sift1m-7bit.json")) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "traffic", "batch16.json")) as f:
        traffic = json.load(f)
    config["dataset"].update(n=6000, clusters=23)
    config["index"].update(num_partitions=4, kmeans_iters=3, lloyd_iters=4)
    traffic.update(query_pool=128)
    return harness.Cell(name="sift1m-7bit.batch16", chips=1, config=config,
                        traffic=traffic, end_to_end=[], per_layer=[])


def _cpu_as_chip(load):
    """``traces.load`` for a CPU trace: the XLA CPU client's op events (on
    a host thread there) stand in for a chip's ``XLA Ops`` line."""
    def wrapped(path):
        pd = load(path)
        ops = [ev for plane in pd.planes if plane.name == "/host:CPU"
               for line in plane.lines if "CpuClient" in line.name
               for ev in line.events if not ev.name.startswith(
                   ("end: ", "ThreadpoolListener"))]
        chip = NS(name="/device:TPU:0",
                  lines=[NS(name=traces.OPS_LINE, events=ops)])
        return NS(planes=list(pd.planes) + [chip])
    return wrapped


@pytest.mark.parametrize("obs", [True, False], ids=["obs_on", "obs_off"])
def test_span_report_on_a_small_cell(obs, tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path / "trace"))
    monkeypatch.setattr(traces, "load", _cpu_as_chip(traces.load))
    r = span_report.report(_small_cell(), seed=2**31 + 29, seconds=0.4,
                           obs=obs, t_start=time.perf_counter())
    assert r["requests"] >= 1 and r["failed"] == 0
    assert r["compiles_in_window"] == 0
    assert sum(r["idle_by_span"].values()) == pytest.approx(r["idle_s"])
    if not obs:
        assert r["readings"] == {} and r["counters"] == {}
        assert not any(n.startswith("squash.") for n in r["idle_by_span"])
        return
    assert set(r["readings"]) == {"stage1_ms.batch", "alg1_ms.batch",
                                  "plane_setup_ms.batch", "upload_mib.batch"}
    assert r["readings"]["upload_mib.batch"] == pytest.approx(
        r["upload_mib_from_shapes"], rel=1e-12)
    assert r["counters"]["serve.requests"] == r["requests"]
    assert r["idle_named_share"] > 0.5
    assert any(n.startswith("squash.") for n, _ in r["idle_gaps"])
    assert r["longest_requests"][0]["spans"]["squash.alg1"] > 0
