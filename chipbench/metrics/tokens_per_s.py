"""Prompt tokens prefilled plus tokens generated inside the window, over
the window's seconds."""

from chipbench import reduce


def read(run):
    if "records" not in run.data:
        return None
    t0, t1 = run.data["window"]
    return reduce.window_tokens(run) / (t1 - t0)
