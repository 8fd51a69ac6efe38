"""One run of one cell: set up, warm up, drive the window, check, report.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own, found by the name ``BENCHMARK.json`` gives:

* ``bench/configs/<config>.json``: dataset shape, attribute schema,
  ``SquashConfig`` overrides, stated guarantees, chips;
* ``bench/traffic/<traffic>.json``: loop (closed or open), queries per
  request, k, predicate selectivity, query pool, arrival rate;
* ``bench/metrics/<metric>.py``: ``read(run) -> float | None``.

The system under test is ``VectorSearchService(backend="jax")`` over a
``SquashIndex`` built from the generated data; ``--control`` puts the
bfloat16 brute force of ``control.py`` in its place.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import math
import os
import shutil
import sys
import time
from typing import Dict, List, Optional

import numpy as np

from squashbench import data as bdata
from squashbench import reference, traces, work

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
# Where a run keeps what it writes: fixed paths inside the checkout.
COMPILE_CACHE = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(ROOT, ".bench_trace")
# Answers held to the reference per run, drawn from the seed when more were
# served; keeps the host reference well inside the window's length.
REFERENCE_REQUESTS = {"closed": 48, "open": 160}


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def _applies(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def load_cell(workload: str, root: str = ROOT) -> Cell:
    """The cell's entry in BENCHMARK.json with its config and traffic."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next((w for w in bench["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cfg_entry = next(c for c in bench["configs"]
                     if c["name"] == entry["config"])
    with open(os.path.join(root, cfg_entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(BENCH_DIR, "traffic",
                           entry["traffic"] + ".json")) as f:
        traffic = json.load(f)
    return Cell(
        name=workload, chips=int(entry["chips"]), config=config,
        traffic=traffic,
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)])


def load_reader(metric: str):
    """The ``read`` function of ``bench/metrics/<metric>.py``."""
    path = os.path.join(BENCH_DIR, "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ------------------------------------------------------------ systems

class ProgramSystem:
    """The served path: VectorSearchService(backend="jax") → SquashIndex."""

    def __init__(self, ds: bdata.Dataset, config: dict, k: int, seed: int):
        import jax

        from repro.core.pipeline import SquashConfig, SquashIndex
        from repro.serve import ServiceConfig, VectorSearchService

        self.k = k
        self.index = SquashIndex.build(
            ds.vectors, ds.attributes.astype(np.float64),
            SquashConfig(**config.get("index", {})),
            seed=int(seed) % (1 << 32))
        self.stacked = jax.block_until_ready(self.index.device_stack())
        self.svc = VectorSearchService(self.index,
                                       ServiceConfig(backend="jax"))

    def shapes(self) -> Dict[str, int]:
        st = self.stacked
        return {"p": st.num_partitions, "n_max": st.n_max,
                "d": int(st.codes.shape[-1]),
                "m1": int(st.boundaries.shape[1])}

    def query(self, queries: np.ndarray, ranges):
        from repro.core.attributes import Predicate

        preds = [Predicate(attr=a, op="B", lo=float(lo), hi=float(hi))
                 for a, lo, hi in ranges]
        return self.svc.query(queries, preds, k=self.k)

    def close(self) -> None:
        self.svc.close()
        self.svc = self.index = self.stacked = None


class ControlSystem:
    """The bfloat16 brute force in the program's place (no stats)."""

    def __init__(self, ds: bdata.Dataset, config: dict, k: int, seed: int):
        from squashbench.control import Bf16BruteForce

        self.bf = Bf16BruteForce(ds.vectors, ds.attributes, k)

    def shapes(self) -> Dict[str, int]:
        return {}

    def query(self, queries, ranges):
        ids, dists = self.bf.query(queries, ranges)
        return ids, dists, None

    def close(self) -> None:
        self.bf = None


# ------------------------------------------------------------ the run

class CompileCounter:
    """Counts new executables (compiled or read from the persistent cache)
    and persistent-cache misses while it is entered."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        self.compiles = 0
        self.cache_misses = 0

    def _on_duration(self, event: str, duration: float, **kw) -> None:
        if event == self.COMPILE:
            self.compiles += 1

    def _on_event(self, event: str, **kw) -> None:
        if event == self.MISS:
            self.cache_misses += 1

    def __enter__(self):
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)


def _span(name: str, tracing: bool):
    if not tracing:
        return contextlib.nullcontext()
    import jax.profiler

    return jax.profiler.TraceAnnotation(name)


@dataclasses.dataclass
class Served:
    """What the window produced, request by request."""

    due: List[float] = dataclasses.field(default_factory=list)
    start: List[float] = dataclasses.field(default_factory=list)
    end: List[float] = dataclasses.field(default_factory=list)
    queries: List[int] = dataclasses.field(default_factory=list)
    stats: list = dataclasses.field(default_factory=list)
    answers: List[reference.Answer] = dataclasses.field(default_factory=list)
    failed: int = 0
    window_s: float = 0.0
    late_s: float = 0.0         # worst wake-up lateness of the generator


def _serve_one(system, ds, req, k, out: Served, tracing: bool) -> None:
    queries = ds.queries[req.query_rows]
    t0 = time.perf_counter()
    try:
        with _span("bench.request", tracing):
            ids, dists, stats = system.query(queries, req.ranges)
    except Exception as e:  # a failed request counts as missing its answer
        print(f"request {req.index} failed: {e!r}", file=sys.stderr)
        out.failed += 1
        ids = np.full((queries.shape[0], k), -1, np.int64)
        dists = np.full((queries.shape[0], k), np.inf)
        stats = None
        t1 = math.inf
    else:
        t1 = time.perf_counter()
    out.start.append(t0)
    out.end.append(t1)
    out.queries.append(queries.shape[0])
    out.stats.append(stats)
    out.answers.append(reference.Answer(queries=queries, ranges=req.ranges,
                                        ids=np.asarray(ids),
                                        dists=np.asarray(dists)))


def drive_window(system, ds, traffic: dict, seed: int, seconds: float,
                 tracing: bool) -> Served:
    k = int(traffic["k"])
    stream = bdata.RequestStream(traffic, ds, seed)
    out = Served()
    with _span(traces.WINDOW_SPAN, tracing):
        t0 = time.perf_counter()
        if traffic["loop"] == "closed":
            i = 0
            while True:
                out.due.append(time.perf_counter())
                _serve_one(system, ds, stream[i], k, out, tracing)
                i += 1
                if out.end[-1] - t0 >= seconds:
                    break
            out.window_s = out.end[-1] - t0
        else:
            offsets = bdata.arrival_offsets(float(traffic["rate_per_s"]),
                                            seconds, seed)
            for i, off in enumerate(offsets):
                due = t0 + off
                wait = due - time.perf_counter()
                if wait > 0:
                    with _span("bench.wait", tracing):
                        time.sleep(wait)
                    out.late_s = max(out.late_s, time.perf_counter() - due)
                out.due.append(due)
                _serve_one(system, ds, stream[i], k, out, tracing)
            out.window_s = max(out.end[-1] - t0, seconds)
    return out


def end_to_end(cell: Cell, served: Served, setup_s: float,
               peak_bytes: int) -> Dict[str, dict]:
    lat_ms = (np.asarray(served.end) - np.asarray(served.due)) * 1e3
    values = {
        "setup_s": setup_s,
        "qps": sum(served.queries) / served.window_s,
        "p50_ms": float(np.percentile(lat_ms, 50)),
        "p95_ms": float(np.percentile(lat_ms, 95)),
        "peak_hbm_gib": peak_bytes / 2**30,
    }
    out = {}
    for m in cell.end_to_end:
        v = values[m["name"]]
        out[m["name"]] = {"value": v if math.isfinite(v) else None,
                          "unit": m["unit"]}
    return out


@dataclasses.dataclass
class RunView:
    """What a per-layer reader may read."""

    cell: Cell
    shapes: Dict[str, int]
    served: Served
    trace: Optional[traces.Reduced]
    peaks: Optional[Dict[str, float]]


def peak_memory(devices) -> int:
    stats = [d.memory_stats() or {} for d in devices]
    return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)


def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool,
             t_start: float, control: bool = False, devices=None) -> dict:
    """Set up, warm up, drive the window, check; the result line's dict."""
    import jax

    devices = devices or jax.devices()[:cell.chips]
    traffic = cell.traffic
    k = int(traffic["k"])
    with CompileCounter() as setup_compiles:
        ds = bdata.make_dataset(**cell.config["dataset"],
                                query_pool=int(traffic["query_pool"]),
                                seed=seed)
        system = (ControlSystem if control else ProgramSystem)(
            ds, cell.config, k, seed)
        shapes = system.shapes()
        warm = bdata.RequestStream(traffic, ds, seed, stream=4)
        for i in range(int(traffic["warmup_requests"])):
            system.query(ds.queries[warm[i].query_rows], warm[i].ranges)
    setup_s = time.perf_counter() - t_start

    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0      # the bench.* spans suffice
        options.enable_hlo_proto = False
        jax.profiler.start_trace(TRACE_DIR, profiler_options=options)
    try:
        with CompileCounter() as compiles:
            served = drive_window(system, ds, traffic, seed, seconds, trace)
    finally:
        if trace:
            jax.profiler.stop_trace()
    peak_bytes = peak_memory(devices)
    system.close()
    del system

    reduced = None
    if trace:
        reduced = traces.reduce(traces.load(_xplane(TRACE_DIR)))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)

    sample = reference.sample_answers(served.answers,
                                      REFERENCE_REQUESTS[traffic["loop"]],
                                      seed)
    checks = reference.compare(ds.vectors, ds.attributes, sample, k,
                               float(cell.config["guarantees"]
                                     ["recall_floor"]))
    correct = served.failed == 0 and reference.passes(checks)

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak_bytes}
    if trace:
        metrics = {}
        view = RunView(cell=cell, shapes=shapes, served=served,
                       trace=reduced,
                       peaks=work.peaks(dev.device_kind))
        for m in cell.per_layer:
            v = load_reader(m["name"])(view)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device.update(busy_s=reduced.busy_s, window_s=reduced.window_s)
    else:
        metrics = end_to_end(cell, served, setup_s, peak_bytes)

    result = {"correct": correct, "attempted": len(served.end),
              "failed": served.failed, "metrics": metrics, "device": device}
    if trace:
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in reduced.device_ops],
            "idle_gaps": [[n, s] for n, s in reduced.idle_gaps]}
    service_ms = (np.asarray(served.end) - np.asarray(served.start)) * 1e3
    result["diagnostics"] = {
        "service_ms": {"p50": float(np.percentile(service_ms, 50)),
                       "p95": float(np.percentile(service_ms, 95)),
                       "max": float(service_ms.max())},
        "compiles_in_setup": setup_compiles.compiles,
        "cache_misses_in_setup": setup_compiles.cache_misses,
        "compiles_in_window": compiles.compiles,
        "generator_late_s": served.late_s,
        "requests_compared": len(sample),
        "shapes": shapes}
    result["checks"] = checks
    return result


def _xplane(root: str) -> str:
    found = [os.path.join(d, f) for d, _, fs in os.walk(root) for f in fs
             if f.endswith(".xplane.pb")]
    if len(found) != 1:
        raise FileNotFoundError(f"want one .xplane.pb under {root}, "
                                f"found {found}")
    return found[0]
