"""Device idle share of the window: 1 - busy / window, from the trace."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * run.trace.idle_share
