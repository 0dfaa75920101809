"""Serving under a closed loop: ``clients`` users, each sending its next
request as soon as its last one finished (no think time), fed to
``ServingEngine.start`` -> ``EngineSession.submit`` / ``tick``.

The engine, the warm-up and the records are ``serving``'s (``build``,
``warm``, ``Record``, ``Tick``); so is the correctness check
(``served_check``), with the reference run two rows at a time for these
lengths. What differs
is when a request is due: at the start of the window for each client's
first, and when the client's previous one finished for the rest. Clients
send while the window is open; what they sent is drained after it, up to
the workload's ``drain_s``.

The requests come from a pool of ``pool``, taken in turn and from the
start again when it runs out; their prompt and output lengths are the
lognormals' quantiles shuffled by the fixed ``order_seed``, the run's seed
turns the pool to start at another request and draws the token ids, as
``traffic.open_loop`` does for the open loop.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from chipbench import served_check, traffic, weights
from chipbench.drivers import serving
from chipbench.harness import log
from chipbench.reference import dense_decoder as ref


def pool(spec: dict, seed: int, vocab: int) -> list:
    """The pool of requests, in the order the clients take them (``due``
    is filled in as they are sent)."""
    n = spec["pool"]
    order = np.random.default_rng(spec["order_seed"])
    prompts = order.permutation(traffic.lognormal_lengths(n, spec["prompt"]))
    outs = order.permutation(traffic.lognormal_lengths(n, spec["output"]))
    turn = seed % n
    prompts, outs = np.roll(prompts, -turn), np.roll(outs, -turn)
    rng = np.random.default_rng(seed)
    return [traffic.Arrival(i, 0.0, tuple(int(t) for t in
                                          rng.integers(1, vocab, prompts[i])),
                            int(outs[i]))
            for i in range(n)]


def play(session, requests, clients: int, seconds: float, drain_s: float,
         tracer=None):
    """The closed loop. Returns (records, ticks, window (t0, t1))."""
    from repro.serving import Request
    recs, live, ticks = [], {}, []
    nxt = 0
    t0 = time.perf_counter()
    stop_by = t0 + seconds + drain_s
    free = clients                   # clients waiting to send
    while True:
        now = time.perf_counter()
        if tracer is not None:
            tracer.poll(now - t0)
        if free and now < t0 + seconds:
            with serving._annotate("submit"):
                while free:
                    a = requests[nxt % len(requests)]
                    req = Request(nxt, a.prompt, max_new_tokens=a.max_new,
                                  arrival=session.now)
                    session.submit(req)
                    rec = serving.Record(nxt, now - t0, len(a.prompt),
                                         a.max_new,
                                         submit=time.perf_counter())
                    recs.append(rec)
                    live[nxt] = (req, rec)
                    nxt += 1
                    free -= 1
            continue
        if not session.running or now >= stop_by:
            break
        before = {rid: len(r.tokens) for rid, (r, _) in live.items()}
        waiting = {rid for rid, (r, _) in live.items() if r.t_admit is None}
        ts = time.perf_counter()
        with serving._annotate("tick"):
            session.tick()
        te = time.perf_counter()
        admitted, ptoks, ctx = 0, 0, []
        for rid, (req, rec) in list(live.items()):
            if rid in waiting and req.t_admit is not None:
                rec.admit = ts
                admitted += 1
                ptoks += rec.prompt_len
            n = len(req.tokens)
            new = n - before[rid]
            if new:
                rec.token_times.extend([te] * new)
                # the decode step fed token n-1 at position prompt+n-2,
                # attending to prompt+n-1 positions
                if before[rid] > 0 or new > 1:
                    ctx.append(rec.prompt_len + n - 1)
            if req.done:
                rec.done = te
                del live[rid]
                free += 1
        ticks.append(serving.Tick(ts, te, admitted, ptoks, tuple(ctx)))
    if tracer is not None:
        tracer.close()
    return recs, ticks, (t0, t0 + seconds)


def run(run, devices, tracer=None, control: bool = False) -> None:
    """Play the cell; fill ``run`` (see ``harness.Run``)."""
    config, wl = run.config, run.workload
    spec = wl["traffic"]
    engine = serving.build(config, wl, run.seed, devices)
    requests = pool(spec, run.seed, engine.cfg.vocab_size)
    serving.warm(engine, requests)
    session = engine.start()
    compiles = serving._Compiles()
    compiles.counting = True
    run.setup_s = time.perf_counter() - run.t_start
    recs, ticks, window = play(session, requests, spec["clients"],
                               run.seconds, wl["drain_s"], tracer)
    compiles.counting = False
    finished = [r for r in recs if r.done is not None]
    run.attempted, run.failed = len(recs), len(recs) - len(finished)
    run.memory_peak_bytes = serving.peak_bytes(devices[:1])
    log(f"[serving_closed] {len(recs)} requests from {spec['clients']} "
        f"clients in {run.seconds:g} s, {len(finished)} finished, "
        f"{run.failed} not within {wl['drain_s']} s after the window; "
        f"{len(ticks)} ticks; {compiles.n} compiles inside the window")
    run.data.update(records=recs, ticks=ticks, window=window,
                    compiles_in_window=compiles.n)
    prompts = {r.rid: requests[r.rid % len(requests)].prompt for r in recs}
    served = {r.rid: list(r.tokens) for r in session.sched.finished}
    cfg = engine.cfg
    del session, engine
    gc.collect()
    check = wl["check"]
    if not finished:
        run.compared.append(("served_logit_gap", float("nan"),
                             check["limit"]))
        return
    sample = serving._sample(finished, run.seed, check["sample_requests"])
    bad = [r.rid for r in finished
           if len(served[r.rid]) != r.max_new
           or not all(0 <= t < cfg.vocab_size for t in served[r.rid])]
    # the reference two rows at a time: four 4000-token rows would hold
    # 9 GB of attention scores
    gap, n, ctl = served_check.served_gap(
        ref, config, weights.draw(ref.spec(config), run.seed), sample,
        prompts, served, control=control, rows=2)
    log(f"[serving_closed] reference over {len(sample)} requests, {n} "
        f"served tokens: widest gap {gap:.4f} std of the reference logits"
        + ("" if ctl is None else f"; float8 control {ctl:.4f}"))
    run.data["control_gap"] = ctl
    run.compared.append(("served_logit_gap", gap, check["limit"]))
    run.compared.append(("requests_with_wrong_token_count", float(len(bad)),
                         0.0))
