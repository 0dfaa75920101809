"""Device: share of the traced window of a gradient sync in which no
operation ran, averaged over the devices."""

from chipbench import trace as tr


def read(run):
    if run.trace is None or "reductions" not in run.data:
        return None
    return 100 * (1 - tr.busy_s(run.trace) / run.trace.window_s)
