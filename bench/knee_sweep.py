#!/usr/bin/env python3
"""Find the knee of an open-loop cell once, on the chip.

    python3 bench/knee_sweep.py --workload sift1m-7bit.online --seed 7 \
        --seconds 15 --fractions 0.5,0.7,0.8,0.9,1.0,1.1

One process: builds the cell's index once, measures the capacity with a
closed loop of the cell's request shape, then offers Poisson load at each
fraction of it and prints one JSON line per rate (p50, p95, completed/s,
and how far latency grew from the first to the last quarter of the window,
which stays near 0 below the knee and grows with the run above it). The
cell's traffic file takes its rate from this table by hand; the benchmark's
runs never search for one.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--fractions", default="0.5,0.7,0.8,0.9,1.0,1.1")
    args = ap.parse_args(argv)

    sys.path.insert(0, HERE)
    sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))
    import numpy as np

    from squashbench import data as bdata
    from squashbench import harness

    cell = harness.load_cell(args.workload)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print("knee_sweep: no TPU; nothing was run", file=sys.stderr)
        return 2
    jax.config.update("jax_compilation_cache_dir", harness.COMPILE_CACHE)
    jax.config.update("jax_enable_x64", False)

    traffic = cell.traffic
    ds = bdata.make_dataset(**cell.config["dataset"],
                            query_pool=int(traffic["query_pool"]),
                            seed=args.seed)
    system = harness.ProgramSystem(ds, cell.config, int(traffic["k"]),
                                   args.seed)
    warm = bdata.RequestStream(traffic, ds, args.seed, stream=4)
    for i in range(2):
        system.query(ds.queries[warm[i].query_rows], warm[i].ranges)
    closed = dict(traffic, loop="closed")
    served = harness.drive_window(system, ds, closed, args.seed,
                                  args.seconds, False)
    capacity = len(served.end) / served.window_s
    print(json.dumps({"closed_loop_req_per_s": capacity,
                      "mean_service_ms": 1e3 * served.window_s
                      / len(served.end)}), flush=True)
    for frac in (float(f) for f in args.fractions.split(",")):
        rate = frac * capacity
        served = harness.drive_window(system, ds,
                                      dict(traffic, rate_per_s=rate),
                                      args.seed, args.seconds, False)
        lat = (np.asarray(served.end) - np.asarray(served.due)) * 1e3
        q = max(1, lat.size // 4)
        print(json.dumps({
            "fraction": frac, "rate_per_s": rate, "requests": int(lat.size),
            "completed_per_s": lat.size / served.window_s,
            "p50_ms": float(np.percentile(lat, 50)),
            "p95_ms": float(np.percentile(lat, 95)),
            "growth_ms": float(lat[-q:].mean() - lat[:q].mean())}),
            flush=True)
    system.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
