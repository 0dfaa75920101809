"""The window's seconds over the gradient reductions finished in it (from
the window's start to the end of its last reduction)."""


def read(run):
    if "reductions" not in run.data:
        return None
    t0, t1 = run.data["window"]
    return (t1 - t0) / run.data["reductions"] * 1e3
