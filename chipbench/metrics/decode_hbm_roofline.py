"""Whole decode step against the HBM roofline: the bytes each traced
decode step needs (every weight once, and the K/V of each active slot's
live positions only) over the chip's HBM bandwidth, divided by the steps'
device time."""

from chipbench import reduce, work


def read(run):
    if run.trace is None or "records" not in run.data:
        return None
    steps = reduce.decode_steps(run)
    if not steps:
        return None
    need = sum(work.decode_bytes(run.config, t.decode_ctx) for t, _ in steps)
    took = sum(e.dur for _, e in steps) * 1e-9
    return 100 * need / run.peaks.hbm_bytes_per_s / took
