#!/usr/bin/env python3
"""Run one cell of the on-chip benchmark once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``; its
configuration, traffic mix and per-layer metric readers are files under
``bench/`` found by name (see ``squashbench/harness.py``). One process, no
children, float32 (x64 off). The run needs a TPU with as many chips as the
cell asks for and exits non-zero, printing no result, without one. The last
line of stdout is the result as one JSON object; the numbers that decided
``correct`` are the last lines of stderr and the ``checks`` key of the
result. ``--trace 1`` reports the per-layer metrics from a profiler trace of
the window instead of the end-to-end ones. ``--control`` serves the window
with the bfloat16 brute force in place of the index; the benchmark's own
runs never pass it.
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
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="serve with the bfloat16 brute force (a check)")
    args = ap.parse_args(argv)

    sys.path.insert(0, HERE)
    sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))
    from squashbench import harness

    cell = harness.load_cell(args.workload)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: the cell needs {cell.chips} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s). Nothing was "
              f"run.", file=sys.stderr)
        return 2
    jax.config.update("jax_compilation_cache_dir", harness.COMPILE_CACHE)
    # Every program, however quick to compile, so a second run compiles none.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_enable_x64", False)
    import repro  # noqa: F401  (fails here, before any result, without src/)

    result = harness.run_cell(cell, seed=args.seed, seconds=args.seconds,
                              trace=bool(args.trace), t_start=T_START,
                              control=args.control,
                              devices=devices[:cell.chips])
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
