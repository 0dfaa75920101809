"""Model step: device time of the prefill programs, found by the name the
program gives them (``jit_prefill_step``, or its sampled twin), per 1000
real (unpadded) prompt tokens. The programs are those that end in the
traced ticks; the tokens are the prompts admitted in the ticks in which
such a program ended (the serving loop records a prompt in the tick that
admitted it, which prefills all of it while it fits one chunk, as in
chat). A program that gives its prefill no such name reads nothing."""

from chipbench import reduce

PROGRAM = "jit_prefill_step"


def read(run):
    if run.trace is None or "records" not in run.data:
        return None
    secs, toks = 0.0, 0
    for t, evs in reduce.tick_programs(run):
        mine = [e for e in evs if e.name.startswith(PROGRAM)]
        if mine:
            secs += sum(e.dur for e in mine) * 1e-9
            toks += t.prompt_tokens
    return secs * 1e3 / (toks / 1000) if toks and secs else None
