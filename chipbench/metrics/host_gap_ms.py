"""Device: mean device-idle time between consecutive device programs
while a request is in the engine (from the first traced tick to the last,
leaving out stretches in which the loop slept for want of work)."""

from chipbench import reduce
from chipbench import trace as tr


def read(run):
    if run.trace is None or "records" not in run.data:
        return None
    pairs = reduce.tick_programs(run)
    if not pairs:
        return None
    tracer, trace = run.data["tracer"], run.trace
    lo = tracer.to_trace_ns(trace, pairs[0][0].start)
    hi = tracer.to_trace_ns(trace, pairs[-1][0].end)
    idle = [e for e in trace.host if e.name == tr.HOST_PREFIX + "idle"]
    mods = sorted(tr.clip(trace.modules.get(0, []), (lo, hi)),
                  key=lambda e: e.start)
    gaps = []
    for a, b in zip(mods, mods[1:]):
        if b.start <= a.end:
            continue
        mid = (a.end + b.start) / 2
        if not any(s.start <= mid <= s.end for s in idle):
            gaps.append(b.start - a.end)
    return sum(gaps) / len(gaps) * 1e-6 if gaps else None
