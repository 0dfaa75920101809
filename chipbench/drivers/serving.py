"""Serving: an open loop of requests on the wall clock, fed to
``ServingEngine.start`` -> ``EngineSession.submit`` / ``tick``.

Set-up draws the weights on the device from the seed, builds the engine,
and warms every shape the traffic uses (one request per prompt bucket, then
the session is dropped). The window then submits each request when it is
due, ticks while there is work and sleeps while there is none, and records
on the host clock when each request was admitted and when each of its
tokens came back. Requests due in the window are drained after it, up to
the workload's ``drain_s``; one not finished by then has failed.

Correctness: a sample of the finished requests, drawn from the seed and
with the longest among them, is run through the float32 reference over its
prompt and the tokens it was served; the widest gap by which a served
token's logit lies below the reference's best is compared with the
workload's limit.
"""

from __future__ import annotations

import dataclasses
import gc
import time

import numpy as np

from chipbench import traffic, weights
from chipbench.harness import log
from chipbench.reference import dense_decoder as ref


def program_config(config: dict):
    """The program's model config for these sizes, served in bfloat16."""
    from repro.launch.serve import serving_config
    base = serving_config(config["repro_arch"])
    return dataclasses.replace(
        base, n_layers=config["num_hidden_layers"],
        d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        d_ff=config["intermediate_size"], vocab_size=config["vocab_size"],
        rope_theta=float(config["rope_theta"]),
        tie_embeddings=config["tie_word_embeddings"])


def draw_params(config: dict, seed: int, cfg):
    """The weights, drawn from the seed by path, in the program's tree."""
    import jax

    from repro.models import transformer as tf
    like = jax.eval_shape(lambda: tf.init_params(jax.random.PRNGKey(0), cfg))
    want = {k: (tuple(s), d) for k, (s, d) in ref.spec(config).items()}
    got = weights.spec_of(like)
    if got != want:
        raise RuntimeError(f"the program's parameter layout {got} is not the "
                           f"one the reference reads {want}")
    return weights.as_tree(weights.draw(want, seed), like)


@dataclasses.dataclass
class Record:
    """One request as the client saw it (host perf_counter seconds)."""
    rid: int
    due: float
    prompt_len: int
    max_new: int
    submit: float | None = None
    admit: float | None = None     # start of the tick that admitted it
    token_times: list = dataclasses.field(default_factory=list)
    done: float | None = None


@dataclasses.dataclass
class Tick:
    start: float
    end: float
    admitted: int                  # requests admitted in this tick
    prompt_tokens: int             # real prompt tokens prefilled
    decode_ctx: tuple              # live context of each slot decoded


def _annotate(name: str):
    import jax
    return jax.profiler.TraceAnnotation("chipbench." + name)


class _Compiles:
    """Backend compilations seen while ``counting`` (none belong in the
    window)."""

    def __init__(self):
        import jax
        self.counting, self.n = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if self.counting and \
                event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


def warm(engine, arrivals) -> None:
    """Compile what the window will run: the prefill of every prompt
    bucket this traffic reaches (one prompt each), the decode step and the
    slot reset. Then drop the session, so that its caches free."""
    from repro.serving import Request
    per_bucket = {}
    for a in arrivals:
        per_bucket.setdefault(engine._bucket(len(a.prompt)), a.prompt)
    session = engine.start()
    for i, prompt in enumerate(per_bucket.values()):
        session.submit(Request(i, prompt, max_new_tokens=2))
    while session.running:
        session.tick()
    del session
    gc.collect()


def play(session, arrivals, seconds: float, drain_s: float, tracer=None):
    """The open loop. Returns (records, ticks, window (t0, t1), late)."""
    from repro.serving import Request
    recs = {a.rid: Record(a.rid, a.due, len(a.prompt), a.max_new)
            for a in arrivals}
    live, ticks, late = {}, [], []
    nxt = 0
    t0 = time.perf_counter()
    stop_by = t0 + seconds + drain_s
    while True:
        now = time.perf_counter()
        if tracer is not None:
            tracer.poll(now - t0)
        if nxt < len(arrivals) and arrivals[nxt].due <= now - t0:
            with _annotate("submit"):
                while nxt < len(arrivals) and arrivals[nxt].due <= now - t0:
                    a = arrivals[nxt]
                    req = Request(a.rid, a.prompt, max_new_tokens=a.max_new,
                                  arrival=session.now)
                    session.submit(req)
                    recs[a.rid].submit = time.perf_counter()
                    late.append(recs[a.rid].submit - (t0 + a.due))
                    live[a.rid] = req
                    nxt += 1
            continue
        if not session.running:
            if nxt == len(arrivals):
                break
            with _annotate("idle"):
                time.sleep(max(0.0, min(t0 + arrivals[nxt].due - now,
                                        0.002)))
            continue
        if now >= stop_by:
            break
        before = {rid: len(r.tokens) for rid, r in live.items()}
        waiting = {rid for rid, r in live.items() if r.t_admit is None}
        ts = time.perf_counter()
        with _annotate("tick"):
            session.tick()
        te = time.perf_counter()
        admitted, ptoks, ctx = 0, 0, []
        for rid, req in list(live.items()):
            rec = recs[rid]
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
        ticks.append(Tick(ts, te, admitted, ptoks, tuple(ctx)))
    if tracer is not None:
        tracer.close()
    return list(recs.values()), ticks, (t0, t0 + seconds), late


def _sample(finished, seed: int, n: int) -> list:
    """``n`` finished requests drawn from the seed, the longest among them."""
    longest = max(finished, key=lambda r: (r.prompt_len + r.max_new, r.rid))
    rest = [r for r in finished if r is not longest]
    rng = np.random.default_rng(seed + 1)
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def served_gap(config: dict, seed: int, sample, prompts, served,
               control: bool = False) -> tuple:
    """Widest gap, in reference standard deviations, by which a served
    token's logit lies below the float32 reference's best, over every
    served token of ``sample``; with ``control``, also the widest gap of
    the token the float8 control puts first at each of those positions."""
    S = max(len(prompts[r.rid]) + len(served[r.rid]) for r in sample)
    K = max(len(served[r.rid]) for r in sample)
    toks = np.zeros((len(sample), S), np.int32)
    at = np.zeros((len(sample), K), np.int32)
    out = np.zeros((len(sample), K), np.int32)
    real = np.zeros((len(sample), K), bool)
    for i, r in enumerate(sample):
        p, s = prompts[r.rid], served[r.rid]
        toks[i, :len(p) + len(s) - 1] = [*p, *s[:-1]]
        at[i, :len(s)] = len(p) - 1 + np.arange(len(s))
        out[i, :len(s)] = s
        real[i, :len(s)] = True
    w = weights.draw(ref.spec(config), seed)
    base = ref.logits(w, config, toks, at)
    gap = ref.gaps(base, out)[:len(sample)][real]
    ctl = None
    if control:
        low = ref.argmax(ref.logits(w, config, toks, at, lowp=True))
        ctl = float(ref.gaps(base, low)[:len(sample)][real].max())
    del w, base
    return float(gap.max()), int(real.sum()), ctl


def build(config: dict, workload: dict, seed: int, devices):
    """The engine on the first device, with weights drawn from ``seed``."""
    from repro.configs.base import get_parallel
    from repro.launch.mesh import make_mesh
    from repro.serving import ServingEngine
    cfg = program_config(config)
    mesh = make_mesh((1, 1), ("data", "model"), devices=devices[:1])
    eng = workload["engine"]
    return ServingEngine(cfg, get_parallel(config["repro_arch"]), mesh,
                         draw_params(config, seed, cfg),
                         n_slots=eng["n_slots"], max_len=eng["max_len"])


def run(run, devices, tracer=None, control: bool = False) -> None:
    """Play the cell; fill ``run`` (see ``harness.Run``)."""
    config, wl = run.config, run.workload
    spec = wl["traffic"]
    engine = build(config, wl, run.seed, devices)
    cfg = engine.cfg
    arrivals = traffic.open_loop(spec, run.seconds, run.seed,
                                 cfg.vocab_size)
    warm(engine, arrivals)
    session = engine.start()
    compiles = _Compiles()
    compiles.counting = True
    run.setup_s = time.perf_counter() - run.t_start
    recs, ticks, window, late = play(session, arrivals, run.seconds,
                                     wl["drain_s"], tracer)
    compiles.counting = False
    finished = [r for r in recs if r.done is not None]
    run.attempted, run.failed = len(recs), len(recs) - len(finished)
    run.memory_peak_bytes = peak_bytes(devices[:1])
    lateness = np.array(late) * 1e3
    log(f"[serving] {len(recs)} requests due in {run.seconds:g} s "
        f"({spec['rate_per_s']} req/s), {len(finished)} finished, "
        f"{run.failed} not within {wl['drain_s']} s after the window; "
        f"{len(ticks)} ticks; generator late by p50 "
        f"{np.percentile(lateness, 50):.3f} ms, max {lateness.max():.3f} ms;"
        f" {compiles.n} compiles inside the window")
    run.data.update(records=recs, ticks=ticks, window=window,
                    compiles_in_window=compiles.n)
    prompts = {a.rid: a.prompt for a in arrivals}
    served = {r.rid: list(r.tokens) for r in session.sched.finished}
    del session, engine
    gc.collect()
    check = wl["check"]
    if not finished:
        run.compared.append(("served_logit_gap", float("nan"),
                             check["limit"]))
        return
    sample = _sample(finished, run.seed, check["sample_requests"])
    bad = [r.rid for r in finished
           if len(served[r.rid]) != r.max_new
           or not all(0 <= t < cfg.vocab_size for t in served[r.rid])]
    gap, n, ctl = served_gap(config, run.seed, sample, prompts, served,
                             control=control)
    log(f"[serving] reference over {len(sample)} requests, {n} served "
        f"tokens: widest gap {gap:.4f} std of the reference logits"
        + ("" if ctl is None else f"; float8 control {ctl:.4f}"))
    run.data["control_gap"] = ctl
    run.compared.append(("served_logit_gap", gap, check["limit"]))
    run.compared.append(("requests_with_wrong_token_count", float(len(bad)),
                         0.0))


def peak_bytes(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)
