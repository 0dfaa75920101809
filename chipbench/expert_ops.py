"""The traced steps of a held-experts model, with their counts and the
device time of their expert ops: shared by the ``moe_*`` and
``hybrid_*`` readers.

A traced tick's programs are ``reduce.tick_programs``'; its counts are
those the tick record holds (``serving_hybrid.Tick.moe``: the steps whose
counts the engine fetched in the tick). A tick takes part when it ran as
many programs of the kind as it holds counts of that step, so that each
count has its program.
"""

from __future__ import annotations

import bisect

from chipbench import reduce, work_hybrid
from chipbench import trace as tr


def steps(run, program: str, step: str) -> list:
    """``(tick, [program events], [counts])`` for every traced tick whose
    programs named ``program...`` match its ``step`` counts one to one."""
    if run.trace is None or "records" not in run.data:
        return []
    out = []
    for t, evs in reduce.tick_programs(run):
        progs = [e for e in evs if e.name.startswith(program)]
        counts = [c for p, c in getattr(t, "moe", ()) if p == step]
        if progs and len(progs) == len(counts):
            out.append((t, progs, counts))
    return out


def expert_time_ns(trace, progs) -> float:
    """Device time of the expert ops inside the programs ``progs``."""
    ops = sorted(trace.ops.get(0, []), key=lambda e: e.start)
    starts = [e.start for e in ops]
    total = 0.0
    for p in progs:
        i = bisect.bisect_left(starts, p.start)
        while i < len(ops) and ops[i].start < p.end:
            e = ops[i]
            if tr.op_family(e.name).startswith(work_hybrid.EXPERT_OPS):
                total += min(e.end, p.end) - e.start
            i += 1
    return total


def roofline_share(run, program: str, step: str):
    """% of the expert ops' device time in ``program`` that their
    roofline needs, over the traced steps; None without such ops."""
    found = steps(run, program, step)
    roof, took = 0.0, 0.0
    for _, progs, counts in found:
        t = expert_time_ns(run.trace, progs)
        if not t:
            continue
        took += t * 1e-9
        for c in counts:
            b, f = work_hybrid.moe_work(run.config, c)
            roof += max(b / run.peaks.hbm_bytes_per_s,
                        f / run.peaks.bf16_flops)
    return 100 * roof / took if took else None
