"""Device milliseconds per request that one chip spends in the all-gather
merge of the sharded plane, from the trace.

Sums the device time of every instruction whose name contains
``all-gather`` (the sharded plane's two gathers of the chips' top-k ids and
distances, ``all-gather`` and ``all-gather.1`` in the compiled plane, under
``squash.merge``), over every chip, then divides by the chips that ran ops
and by the requests served. Silent where no collective ran, as in a
one-chip plane. A change that adds a second collective round shows here.
"""

from squashbench import traces


def read(run):
    if run.trace is None or not run.served.end:
        return None
    hits = [s for name, s in run.trace.op_seconds.items()
            if "all-gather" in traces.instruction(name)]
    if not hits:
        return None
    return 1e3 * sum(hits) / run.trace.chips / len(run.served.end)
