"""Whole decode step against the chip's bf16 peak: the model FLOPs of each
traced decode step (active slots times per-token FLOPs at their live
context) over the steps' device time times the peak."""

from chipbench import reduce, work


def read(run):
    if run.trace is None or "records" not in run.data:
        return None
    steps = reduce.decode_steps(run)
    if not steps:
        return None
    flops = sum(work.decode_flops(run.config, t.decode_ctx)
                for t, _ in steps)
    took = sum(e.dur for _, e in steps) * 1e-9
    return 100 * flops / run.peaks.bf16_flops / took
