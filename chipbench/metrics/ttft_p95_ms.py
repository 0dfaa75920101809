"""95th percentile, over every request due in the window, of the time from
when it was due to its first token; a request not served by the end of the
drain counts as served then."""

from chipbench import reduce


def read(run):
    if "records" not in run.data:
        return None
    p = reduce.percentile(reduce.ttfts(run), 95)
    return None if p is None else p * 1e3
