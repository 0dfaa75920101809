"""Collective: bus bandwidth of one whole gradient reduction (bytes per
device times 2(p-1)/p, over its device time, averaged over the devices)
as a share of the chip's inter-chip bandwidth, the published figure taken
as one-way so that the share cannot pass 100 whichever way it is meant."""

from chipbench import reduce, work


def read(run):
    if run.trace is None or "reductions" not in run.data:
        return None
    per = reduce.reductions(run)
    durs = [e.dur for evs in per.values() for e in evs]
    if not durs:
        return None
    t = sum(durs) / len(durs) * 1e-9
    bus = work.allreduce_bus_bytes(run.data["payload_bytes"],
                                   run.data["devices"])
    return 100 * bus / t / run.peaks.ici_bytes_per_s
