"""95th percentile of every gap between consecutive output tokens, over
all requests due in the window (tokens of one tick share its end time)."""

from chipbench import reduce


def read(run):
    if "records" not in run.data:
        return None
    p = reduce.percentile(reduce.token_gaps(run), 95)
    return None if p is None else p * 1e3
