"""Stage 4 Pallas ADC kernel's share of its roofline, from the trace.

Required work per plane call comes from the cell's shapes and the
configuration's Hamming keep (work.py); the time is the device time of the
kernel's events in the window. Silent where Stage 4 takes the gather path.
"""

from squashbench import work

KERNEL = "adc_lb_distances_batch"


def read(run):
    if run.trace is None or not run.shapes:
        return None
    seconds = run.trace.kernel_seconds(KERNEL)
    if not seconds:
        return None
    s, idx = run.shapes, run.cell.config["index"]
    keep_s = work.keep_survivors(s["n_max"], idx["hamming_perc"],
                                 idx["min_hamming_keep"])
    q = run.cell.traffic["queries_per_request"]
    least, _ = work.roofline_seconds(
        work.adc_work(q, s["p"], keep_s, s["d"], s["m1"]), run.peaks)
    return 100.0 * least * len(run.served.end) / seconds
