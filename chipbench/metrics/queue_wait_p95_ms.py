"""Scheduler: 95th percentile, over the requests due in the window, of the
time from when each was due to the start of the tick that admitted it into
a slot. In a traced run only the requests due before the profiler started
count: starting and stopping it stalls the loop."""

from chipbench import reduce


def read(run):
    if "records" not in run.data:
        return None
    waits = reduce.queue_waits(run)
    tracer = run.data.get("tracer")
    if tracer is not None:
        waits = [w for w, r in zip(waits, run.data["records"])
                 if r.due < tracer.t_on]
    p = reduce.percentile(waits, 95)
    return None if p is None else p * 1e3
