#!/usr/bin/env python3
"""Put a cell's device-idle time down to the program's own host spans.

    python3 bench/span_report.py --workload sift1m-7bit.batch16 \
        --seed 7 --seconds 51 [--obs 0] [--keep <file>.xplane.pb.gz]

One process, on the chip. It sets a cell up and warms it up as
``bench/run.py`` does, then enables the program's obs registry (``--obs
0`` leaves it off) and drives the cell's window under a profiler trace.
The registry's spans (``squash.*``) land in that trace on the device's
clock, and its counters count the bytes put on the device and the requests
served. The last line of stdout is one JSON object: the per-layer readings
(``stage1_ms.batch``, ``alg1_ms.batch``, ``plane_setup_ms.batch``,
``upload_mib.batch``), the upload the cell's shapes predict, the device's
idle time by span, the longest idle gaps named, the longest requests span
by span, and the service times. No answer is checked here: ``bench/run.py``
holds the cell to its reference.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))


def upload_bytes_from_shapes(index, shapes, queries: int,
                             float_bytes: int) -> int:
    """Bytes the served path puts on the device per request: Stage 1's
    r_lookup (uint8) and attribute codes (int32), the plane's queries
    (``float_bytes`` each), cand_mask (bool), keep and take (int32)."""
    m1, a = index.attr_index.boundaries.shape
    n = index.attr_index.codes.shape[0]
    q, p = queries, shapes["p"]
    return (m1 * a + 4 * n * a + float_bytes * q * shapes["d"]
            + q * p * shapes["n_max"] + 2 * 4 * q * p)


def report(cell, *, seed: int, seconds: float, obs: bool, t_start: float,
           keep=None) -> dict:
    """Set up, warm up and drive one traced window of ``cell``; the dict
    the command prints."""
    import jax
    import numpy as np

    from repro.obs.metrics import REGISTRY
    from squashbench import data as bdata
    from squashbench import harness, hostspans, traces

    traffic = cell.traffic
    ds = bdata.make_dataset(**cell.config["dataset"],
                            query_pool=int(traffic["query_pool"]),
                            seed=seed)
    system = harness.ProgramSystem(ds, cell.config, int(traffic["k"]), seed)
    shapes = system.shapes()
    predicted = upload_bytes_from_shapes(
        system.index, shapes, int(traffic["queries_per_request"]),
        system.stacked.vectors.dtype.itemsize)
    warm = bdata.RequestStream(traffic, ds, seed, stream=4)
    for i in range(int(traffic["warmup_requests"])):
        system.query(ds.queries[warm[i].query_rows], warm[i].ranges)
    setup_s = time.perf_counter() - t_start

    REGISTRY.reset()
    if obs:
        REGISTRY.enable()
    shutil.rmtree(harness.TRACE_DIR, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.enable_hlo_proto = False
    jax.profiler.start_trace(harness.TRACE_DIR, profiler_options=options)
    try:
        with harness.CompileCounter() as compiles:
            served = harness.drive_window(system, ds, traffic, seed, seconds,
                                          True)
    finally:
        jax.profiler.stop_trace()
        counters = REGISTRY.snapshot()["counters"]
        REGISTRY.disable()
        REGISTRY.reset()
    system.close()

    path = harness._xplane(harness.TRACE_DIR)
    if keep:
        import gzip

        os.makedirs(os.path.dirname(os.path.abspath(keep)), exist_ok=True)
        with open(path, "rb") as src, gzip.open(keep, "wb") as dst:
            shutil.copyfileobj(src, dst)
    pd = traces.load(path)
    reduced = traces.reduce(pd)
    spans = hostspans.reduce(pd)
    longest = hostspans.longest_requests(pd)
    shutil.rmtree(harness.TRACE_DIR, ignore_errors=True)

    requests = len(served.end)
    service_ms = (np.asarray(served.end) - np.asarray(served.start)) * 1e3
    return {
        "workload": cell.name, "seed": seed, "obs": int(obs),
        "device": jax.devices()[0].device_kind, "setup_s": setup_s,
        "requests": requests, "failed": served.failed,
        "readings": hostspans.layer_readings(spans, counters, requests),
        "upload_mib_from_shapes": predicted / 2**20,
        "window_s": spans.window_s, "idle_share": reduced.idle_share,
        "idle_s": spans.idle_s,
        "idle_named_share": spans.named_share(),
        "idle_by_span": dict(sorted(spans.idle_by_span.items(),
                                    key=lambda t: -t[1])),
        "span_ms_per_request": {n: 1e3 * s / requests for n, s in
                                sorted(spans.span_seconds.items())},
        "idle_gaps": [[n, s] for n, s in spans.idle_gaps],
        "longest_requests": longest,
        "device_ops": [[n, s] for n, s in reduced.device_ops],
        "service_ms": {"p50": float(np.percentile(service_ms, 50)),
                       "p95": float(np.percentile(service_ms, 95)),
                       "max": float(service_ms.max())},
        "compiles_in_window": compiles.compiles,
        "counters": counters, "shapes": shapes,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--obs", type=int, choices=(0, 1), default=1)
    ap.add_argument("--keep", default=None,
                    help="also write the window's trace here, gzipped")
    args = ap.parse_args(argv)

    sys.path.insert(0, HERE)
    sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))
    from squashbench import harness

    cell = harness.load_cell(args.workload)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print("span_report: no TPU; nothing was run", file=sys.stderr)
        return 2
    jax.config.update("jax_compilation_cache_dir", harness.COMPILE_CACHE)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_enable_x64", False)
    result = report(cell, seed=args.seed, seconds=args.seconds,
                    obs=bool(args.obs), t_start=T_START, keep=args.keep)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
