"""Model step: mean device time of one decode program in the traced
window."""

from chipbench import reduce


def read(run):
    if run.trace is None or "records" not in run.data:
        return None
    steps = reduce.decode_steps(run)
    if not steps:
        return None
    return sum(e.dur for _, e in steps) / len(steps) * 1e-6
