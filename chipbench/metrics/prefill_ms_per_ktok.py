"""Model step: device time of the prefill programs per 1000 real (unpadded)
prompt tokens, over the traced ticks that admitted requests."""

from chipbench import reduce


def read(run):
    if run.trace is None or "records" not in run.data:
        return None
    secs, toks = reduce.prefill_work(run)
    return secs * 1e3 / (toks / 1000) if toks and secs else None
