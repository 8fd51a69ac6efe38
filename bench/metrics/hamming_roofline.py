"""Stage 3 Pallas Hamming kernel's share of its roofline, from the trace.

Required work per plane call comes from the cell's shapes (work.py); the
time is the device time of the kernel's events in the window.
"""

from squashbench import work

KERNEL = "packed_hamming_stacked"


def read(run):
    if run.trace is None or not run.shapes:
        return None
    seconds = run.trace.kernel_seconds(KERNEL)
    if not seconds:
        return None
    s = run.shapes
    q = run.cell.traffic["queries_per_request"]
    least, _ = work.roofline_seconds(
        work.hamming_work(q, s["p"], s["n_max"], s["d"]), run.peaks)
    return 100.0 * least * len(run.served.end) / seconds
