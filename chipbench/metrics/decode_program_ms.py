"""Model step: mean device time of the decode program, found by the name
the program gives it (``jit_decode_step``, or its sampled twin), over its
runs wholly inside the traced window. No tick decides which program is
the decode step: a program that gives it no such name reads nothing."""

PROGRAM = "jit_decode_step"


def read(run):
    if run.trace is None or "records" not in run.data:
        return None
    lo, hi = run.trace.window
    steps = [e for e in run.trace.modules.get(0, [])
             if e.name.startswith(PROGRAM) and lo <= e.start
             and e.end <= hi]
    if not steps:
        return None
    return sum(e.dur for e in steps) / len(steps) * 1e-6
