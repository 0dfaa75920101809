"""Reductions shared by the metric readers: the requests and ticks a
serving run recorded, and the device programs its trace holds.

Which device program is which is read from the run, not from names: the
decode program is the one that runs in ticks that admitted nothing, a
prefill program one that runs only in ticks that admitted a request, and
the reduction program of a gradient sync the one that runs most.
"""

from __future__ import annotations

import collections

import numpy as np

from chipbench import trace as tr


def percentile(values, q: float) -> float | None:
    return float(np.percentile(values, q)) if len(values) else None


# ------------------------------------------------------------- serving

def due(run, rec) -> float:
    return run.data["window"][0] + rec.due


def stop_time(run) -> float:
    """When the drain gave up: a request not served by then counts as
    served no earlier."""
    return run.data["window"][1] + run.workload["drain_s"]


def ttfts(run) -> list:
    return [(r.token_times[0] if r.token_times else stop_time(run))
            - due(run, r) for r in run.data["records"]]


def queue_waits(run) -> list:
    return [(r.admit if r.admit is not None else stop_time(run))
            - due(run, r) for r in run.data["records"]]


def token_gaps(run) -> list:
    out = []
    for r in run.data["records"]:
        out.extend(np.diff(r.token_times))
    return out


def window_tokens(run) -> int:
    """Prompt tokens prefilled plus tokens generated inside the window."""
    t0, t1 = run.data["window"]
    prompt = sum(t.prompt_tokens for t in run.data["ticks"] if t.end <= t1)
    gen = sum(1 for r in run.data["records"] for t in r.token_times
              if t <= t1)
    return prompt + gen


def tick_programs(run) -> list:
    """``(tick, [module events])`` for every tick wholly inside the traced
    window, on the serving chip (device 0). A tick's programs are those
    that end between its start and the next tick's: the device's clock in
    the trace runs up to a millisecond apart from the host's, so a program
    can seem to start before the host span that dispatched it, but it ends
    well after (every step takes tens of milliseconds) and before the host
    has its result."""
    trace, tracer = run.trace, run.data["tracer"]
    mods = sorted(trace.modules.get(0, []), key=lambda e: e.end)
    lo, hi = trace.window
    ticks = run.data["ticks"]
    out = []
    for i, t in enumerate(ticks):
        a = tracer.to_trace_ns(trace, t.start)
        b = tracer.to_trace_ns(trace, t.end)
        nxt = (tracer.to_trace_ns(trace, ticks[i + 1].start)
               if i + 1 < len(ticks) else b)
        if a >= lo and b <= hi:
            out.append((t, [e for e in mods if a <= e.end < max(b, nxt)]))
    return out


def classify(pairs) -> tuple:
    """(decode program key, set of prefill program keys)."""
    decode_only = collections.Counter(
        tr.program_key(e) for t, evs in pairs
        if t.admitted == 0 and t.decode_ctx for e in evs)
    if not decode_only:
        return None, set()
    decode = decode_only.most_common(1)[0][0]
    in_admits = {tr.program_key(e) for t, evs in pairs if t.admitted
                 for e in evs}
    return decode, in_admits - set(decode_only)


def decode_steps(run) -> list:
    """``(tick, decode event)`` for each traced tick that decoded."""
    pairs = tick_programs(run)
    decode, _ = classify(pairs)
    out = []
    for t, evs in pairs:
        mine = [e for e in evs if tr.program_key(e) == decode]
        if t.decode_ctx and len(mine) == 1:
            out.append((t, mine[0]))
    return out


def prefill_work(run) -> tuple:
    """(device seconds of prefill programs, real prompt tokens) over the
    traced ticks that admitted requests."""
    pairs = tick_programs(run)
    _, prefill = classify(pairs)
    secs, toks = 0.0, 0
    for t, evs in pairs:
        if t.admitted:
            secs += sum(e.dur for e in evs
                        if tr.program_key(e) in prefill) * 1e-9
            toks += t.prompt_tokens
    return secs, toks


# --------------------------------------------------------- gradient sync

def reductions(run) -> dict:
    """device -> the reduction program's events wholly inside the traced
    window."""
    trace = run.trace
    out = {}
    for dev, mods in trace.modules.items():
        inside = [e for e in mods if e.start >= trace.window[0]
                  and e.end <= trace.window[1]]
        if not inside:
            continue
        key = collections.Counter(map(tr.program_key, inside)
                                  ).most_common(1)[0][0]
        out[dev] = [e for e in inside if tr.program_key(e) == key]
    return out
