"""Serving driver: continuous-batching engine or the legacy fixed-batch loop.

Continuous batching (the default path for real traffic — see
docs/serving.md and docs/sampling_and_prefill.md): a staggered-arrival
workload through the slot scheduler, prefill interleaved with in-flight
decode, per-step stats reduced with the b=1 dual-root tree:

  PYTHONPATH=src python -m repro.launch.serve --arch minicpm_2b --reduced \
      --continuous --requests 8 --slots 4 --arrival-gap 2

Any token-prompt decoder qualifies, including the recurrent-state mixers —
e.g. RWKV6 with prompts longer than the prefill chunk (streamed in chunk
per tick) and seeded nucleus sampling:

  PYTHONPATH=src python -m repro.launch.serve --arch rwkv6_7b --reduced \
      --continuous --requests 6 --slots 3 --prompt-len 20 80 \
      --prefill-chunk 16 --temperature 0.9 --top-p 0.85

Speculative decoding (docs/speculative.md) emits up to --draft-k+1 tokens
per tick with bit-identical streams — n-gram self-drafting by default,
or a second reduced model via --draft-model:

  PYTHONPATH=src python -m repro.launch.serve --arch minicpm_2b --reduced \
      --speculate --draft-k 4 --requests 8 --slots 4

SLO mode (docs/scheduling.md) runs the priority policy — aging, deadline
shedding, and exact-resume preemption — instead of FIFO; --priority and
--deadline-ticks attach SLO metadata to every synthetic request:

  PYTHONPATH=src python -m repro.launch.serve --arch minicpm_2b --reduced \
      --policy slo --priority interactive --deadline-ticks 24 \
      --requests 8 --slots 2 --arrival-gap 1

Chaos mode (docs/robustness.md) serves the same workload across a replica
fleet under a seeded fault plan — replica kills, heartbeat flaps,
stragglers, poisoned logits — and proves the merged streams match an
undisturbed single-engine run bit-for-bit:

  PYTHONPATH=src python -m repro.launch.serve --arch minicpm_2b --reduced \
      --chaos-seed 7 --replicas 3 --heartbeat-timeout 2 --heartbeat-misses 2

Published width on one 16 GB TPU v5e chip (weights are held in the compute
dtype, bf16):

  PYTHONPATH=src python -m repro.launch.serve --arch minicpm_2b \
      --continuous --requests 6 --slots 4 --prompt-len 16 128 \
      --max-new 16 16 --cache-len 256

Legacy fixed-batch demo (every row decodes in lockstep from an empty cache):

  PYTHONPATH=src python -m repro.launch.serve --arch granite_3_8b --reduced \
      --batch 4 --steps 16
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ShapeSuite, get_config, get_parallel
from repro.launch import step_fns
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.models import transformer as tf


def synthetic_workload(n: int, vocab: int, *, gap: int = 2, seed: int = 0,
                       prompt_lens=(3, 12), max_new=(4, 24),
                       sampling=None, spec=None, repetitive=False,
                       slo=None, shared_prefix: int = 0) -> list:
    """Deterministic staggered-arrival request stream (bench + CLI).

    ``sampling`` is a base :class:`~repro.serving.sampling.SamplingParams`
    or None (greedy). Each request gets its own seed (``base seed + rid``)
    so streams differ per request but reproduce run-to-run. ``spec`` is a
    :class:`~repro.serving.speculative.SpecParams` every request carries
    (None = plain decoding). ``repetitive=True`` cycles each prompt over a
    tiny per-request token alphabet instead of sampling i.i.d. — the
    structured-text stand-in the prompt-lookup drafter can actually draft
    from (an i.i.d. prompt has no recurring n-grams by construction).
    ``slo`` is a :class:`~repro.serving.slo.SLOParams` every request
    carries (None = plain FIFO metadata); for per-class MIXES use
    :func:`repro.serving.traces.generate_trace` instead.
    ``shared_prefix`` prepends one common ``shared_prefix``-token "system
    prompt" to every request — the workload shape ``--prefix-cache``
    exists for (i.i.d. prompts share no prefix by construction).
    """
    from repro.serving import Request
    rng = np.random.default_rng(seed)
    common = tuple(int(t) for t in rng.integers(1, vocab, shared_prefix)) \
        if shared_prefix else ()

    def prompt(plen):
        if not repetitive:
            return common + tuple(int(t) for t in rng.integers(1, vocab,
                                                               plen))
        period = rng.integers(1, vocab, int(rng.integers(2, 5)))
        return common + tuple(int(period[j % len(period)])
                              for j in range(plen))

    return [
        Request(i,
                prompt(int(rng.integers(prompt_lens[0],
                                        prompt_lens[1] + 1))),
                max_new_tokens=int(rng.integers(max_new[0], max_new[1] + 1)),
                arrival=i * gap,
                sampling=(None if sampling is None else
                          dataclasses.replace(sampling,
                                              seed=sampling.seed + i)),
                spec=spec,
                slo=slo)
        for i in range(n)
    ]


def _obs_build(args):
    """Observability sinks from the CLI flags: ``(tracer, metrics_kwargs)``.
    One Tracer serves the whole run (continuous or chaos); metrics attach
    only when ``--metrics-every`` asks for live snapshots."""
    from repro.obs import StreamingMetrics, Tracer
    tracer = Tracer() if args.trace_out else None
    kwargs = {}
    if args.metrics_every > 0:
        kwargs["metrics"] = StreamingMetrics()
        kwargs["metrics_every"] = args.metrics_every
        kwargs["metrics_sink"] = (
            lambda tick, s: print(
                f"[metrics t={tick}] "
                f"ttft p50/p99 {s['ttft_ticks_p50']:.0f}/"
                f"{s['ttft_ticks_p99']:.0f} ticks "
                f"({s['ttft_n']:.0f} obs), "
                f"latency p50/p99 {s['latency_ticks_p50']:.0f}/"
                f"{s['latency_ticks_p99']:.0f} ticks "
                f"({s['latency_n']:.0f} obs)"))
    return tracer, kwargs


def _obs_finish(args, tracer, probe=None):
    """Write the trace file(s) and print the probe's fit/residual summary."""
    if probe is not None and len(probe):
        from repro.obs import fit_alpha_beta, residual_report
        timed = probe.timed()
        print(f"[probe] {probe.n_seen} samples "
              f"({len(timed)} timed, {len(probe.traced())} trace-time)")
        rows = residual_report(timed, probe.model)
        if rows:
            worst = max(rows, key=lambda r: r["rel_err"])
            print(f"[probe] vs {probe.model.name}: worst residual "
                  f"{worst['rel_err']:.1%} at p={worst['p']} "
                  f"{worst['nbytes']}B ({worst['method']})")
        try:
            fr = fit_alpha_beta(timed)
            print(f"[probe] fitted alpha={fr.alpha:.3e}s "
                  f"beta={fr.beta:.3e}s/B over {fr.n_samples} samples "
                  f"(max rel err {fr.max_rel_err:.1%})")
        except ValueError as e:
            print(f"[probe] no fit: {e}")
        if tracer is not None:
            from repro.obs import export_residuals
            export_residuals(tracer, timed, model=probe.model)
    if tracer is not None:
        path = args.trace_out
        if args.trace_format in ("chrome", "both"):
            tracer.to_chrome(path)
            print(f"[trace] {len(tracer)} events -> {path} "
                  f"(chrome://tracing / Perfetto"
                  f"{', %d dropped' % tracer.dropped if tracer.dropped else ''})")
        if args.trace_format in ("jsonl", "both"):
            jl = path if args.trace_format == "jsonl" else path + ".jsonl"
            n = tracer.to_jsonl(jl)
            print(f"[trace] {n} events -> {jl} (jsonl)")


def serving_config(arch: str, reduced: bool = False):
    """The model config a serving run builds: weights held in the compute
    dtype. Every matmul casts its weight to that dtype, so f32 weights buy
    no precision, and XLA hoists those casts out of the layer loop: f32
    weights plus their bf16 copies do not fit a published-width
    ``minicpm_2b`` on one 16 GB v5e chip."""
    cfg = get_config(arch, reduced=reduced)
    return dataclasses.replace(cfg, param_dtype=cfg.compute_dtype)


def serve_continuous(args):
    """Drive the continuous-batching engine on a synthetic workload."""
    from repro.serving import (DraftModelDrafter, PriorityClass,
                               SamplingParams, ServingEngine, SLOParams,
                               SpecParams, make_policy, make_stats_reducer)
    mesh_shape = tuple(int(x) for x in args.mesh.split("x"))
    axes = ("data", "model")[-len(mesh_shape):]
    mesh = make_mesh(mesh_shape, axes)
    cfg = serving_config(args.arch, args.reduced)
    pcfg = get_parallel(args.arch)
    params = tf.init_params(jax.random.PRNGKey(args.seed), cfg)
    drafter = None
    if args.draft_model:
        dcfg = get_config(args.draft_model, reduced=True)
        if dcfg.vocab_size != cfg.vocab_size:
            raise SystemExit(
                f"--draft-model {args.draft_model}: vocab "
                f"{dcfg.vocab_size} != target vocab {cfg.vocab_size}")
        dparams = tf.init_params(jax.random.PRNGKey(args.seed + 7), dcfg)
        drafter = DraftModelDrafter(dcfg, dparams, mesh,
                                    n_slots=args.slots,
                                    max_len=args.cache_len)
    # per-tick stats cross the replica axis on the b=1 dual-root tree
    # (host-side sum on a 1-wide axis)
    tracer, obs_kwargs = _obs_build(args)
    engine = ServingEngine(cfg, pcfg, mesh, params, n_slots=args.slots,
                           max_len=args.cache_len,
                           prefill_chunk=args.prefill_chunk,
                           stats_reducer=make_stats_reducer(mesh),
                           drafter=drafter,
                           prefix_cache=args.prefix_cache,
                           prefix_cache_nodes=(args.prefix_cache_nodes
                                               or 256),
                           tracer=tracer, **obs_kwargs)
    sampling = None
    if args.temperature > 0:
        sampling = SamplingParams(temperature=args.temperature,
                                  top_k=args.top_k, top_p=args.top_p,
                                  seed=args.sample_seed)
    spec = None
    if args.speculate or args.draft_model:
        spec = SpecParams(draft_k=args.draft_k)
    slo = None
    if args.priority is not None or args.deadline_ticks is not None:
        slo = SLOParams(
            priority=PriorityClass[(args.priority or "batch").upper()],
            deadline_ticks=args.deadline_ticks)
    reqs = synthetic_workload(args.requests, cfg.vocab_size,
                              gap=args.arrival_gap, seed=args.seed + 1,
                              prompt_lens=tuple(args.prompt_len),
                              max_new=tuple(args.max_new),
                              sampling=sampling, spec=spec,
                              repetitive=spec is not None
                              and not args.draft_model,
                              slo=slo, shared_prefix=args.shared_prefix)
    policy = make_policy(args.policy) if args.policy != "fifo" else None
    probe = None
    if args.probe:
        from repro.obs import CollectiveProbe, install
        probe = install(CollectiveProbe())
    try:
        report = engine.run(reqs, static=args.static, policy=policy)
    finally:
        if probe is not None:
            from repro.obs import uninstall
            uninstall()
    _obs_finish(args, tracer, probe)
    spec_note = (f", {report['accepted_tokens']}/"
                 f"{report['drafted_tokens']} drafts accepted"
                 if report["drafted_tokens"] else "")
    slo_note = (f", {report['preemptions']} preemptions, "
                f"{report['shed_requests']} shed, "
                f"{report['deadline_misses']} deadline misses"
                if report["policy"] != "fifo" else "")
    prefix_note = ""
    if "prefix_cache" in report:
        pc = report["prefix_cache"]
        prefix_note = (f", prefix cache: {report['prefix_hits']} hits / "
                       f"{report['prefix_tokens_reused']} tokens reused, "
                       f"{pc['nodes']} nodes ({pc['evictions']} evicted)")
    print(f"[{report['mode']}/{report['policy']}] "
          f"{report['requests']} requests, "
          f"{report['total_tokens']} tokens "
          f"({report['sampled_tokens']} sampled, "
          f"{report['prefill_chunks']} prefill chunks{spec_note}) "
          f"in {report['wall_s']:.2f}s "
          f"({report['tok_s']:.1f} tok/s, {report['ticks']} ticks, "
          f"ttft p50 {report['ttft_ticks_p50']:.1f} ticks, "
          f"latency p95 {report['latency_ticks_p95']:.1f} ticks"
          f"{slo_note}{prefix_note})")
    return report


def serve_chaos(args):
    """Serve across a replica fleet under a seeded fault plan and verify
    zero token divergence against the undisturbed single-engine run."""
    from repro.runtime.chaos import FaultPlan
    from repro.serving import (FleetRunner, SamplingParams, ServingEngine,
                               make_stats_reducer)
    mesh_shape = tuple(int(x) for x in args.mesh.split("x"))
    axes = ("data", "model")[-len(mesh_shape):]
    mesh = make_mesh(mesh_shape, axes)
    cfg = serving_config(args.arch, args.reduced)
    pcfg = get_parallel(args.arch)
    params = tf.init_params(jax.random.PRNGKey(args.seed), cfg)
    engine = ServingEngine(cfg, pcfg, mesh, params, n_slots=args.slots,
                           max_len=args.cache_len,
                           prefill_chunk=args.prefill_chunk,
                           stats_reducer=make_stats_reducer(mesh))
    sampling = None
    if args.temperature > 0:
        sampling = SamplingParams(temperature=args.temperature,
                                  top_k=args.top_k, top_p=args.top_p,
                                  seed=args.sample_seed)

    def workload():
        return synthetic_workload(args.requests, cfg.vocab_size,
                                  gap=args.arrival_gap, seed=args.seed + 1,
                                  prompt_lens=tuple(args.prompt_len),
                                  max_new=tuple(args.max_new),
                                  sampling=sampling)

    base = engine.run(workload())
    # observability attaches AFTER the baseline: the divergence check
    # compares the fleet against the undisturbed run, and the trace should
    # cover the chaos run (failovers, quarantines), not the reference.
    # Late attach is supported — the engine reads these attrs every tick.
    tracer, obs_kwargs = _obs_build(args)
    engine.tracer = tracer
    engine.metrics = obs_kwargs.get("metrics")
    engine.metrics_every = obs_kwargs.get("metrics_every", 0)
    engine.metrics_sink = obs_kwargs.get("metrics_sink")
    plan = FaultPlan.seeded(args.chaos_seed, n_replicas=args.replicas,
                            horizon=max(2, base["ticks"]))
    runner = FleetRunner(engine, args.replicas, plan=plan,
                         timeout_s=args.heartbeat_timeout,
                         misses=args.heartbeat_misses,
                         rejoin_backoff_s=args.rejoin_backoff)
    probe = None
    if args.probe:
        from repro.obs import CollectiveProbe, install
        probe = install(CollectiveProbe())
    try:
        report = runner.run(workload())
    finally:
        if probe is not None:
            from repro.obs import uninstall
            uninstall()
    _obs_finish(args, tracer, probe)
    diverged = sum(report["tokens"][rid] != base["tokens"][rid]
                   for rid in base["tokens"])
    faults = ", ".join(f"t{f.tick}:{f.kind}@r{f.replica}" for f in plan) \
        or "none"
    print(f"[chaos seed={args.chaos_seed}] faults: {faults}")
    print(f"[chaos] {report['requests']} requests over "
          f"{report['n_replicas']} replicas: {report['failovers']} "
          f"failovers, {report['quarantines']} quarantines, "
          f"{report['rejoins']} rejoins, {report['resumed_tokens']} "
          f"resumed tokens, recovery {report['recovery_ticks']} ticks, "
          f"{diverged} diverged streams (want 0)")
    if diverged:
        raise SystemExit(f"chaos run diverged on {diverged} streams")
    return report


def serve_loop(args):
    mesh_shape = tuple(int(x) for x in args.mesh.split("x"))
    axes = ("data", "model")[-len(mesh_shape):]
    mesh = make_mesh(mesh_shape, axes)
    cfg = serving_config(args.arch, args.reduced)
    pcfg = get_parallel(args.arch)
    suite = ShapeSuite("serve", args.cache_len, args.batch, "decode")
    step, sh = step_fns.make_serve_step(cfg, pcfg, mesh, suite)

    params = tf.init_params(jax.random.PRNGKey(args.seed), cfg)
    params = jax.device_put(params, step_fns._named(mesh, sh["params"]))
    caches = tf.init_cache(cfg, args.batch, args.cache_len)
    caches = jax.device_put(caches, step_fns._named(mesh, sh["cache"]))

    key = jax.random.PRNGKey(args.seed + 1)
    if cfg.input_mode == "embeds":
        inputs = {"embeds": jax.random.normal(
            key, (args.batch, 1, cfg.d_model), jnp.bfloat16)}
        if cfg.mrope_sections:
            inputs["positions"] = jnp.zeros((args.batch, 1, 3), jnp.int32)
    else:
        inputs = {"tokens": jnp.zeros((args.batch, 1), jnp.int32)}
    if cfg.n_enc_layers:
        inputs["memory"] = jax.random.normal(
            key, (args.batch, 64, cfg.d_model), jnp.bfloat16)

    tokens_out = []
    t0 = time.time()
    for i in range(args.steps):
        logits, caches = step(params, inputs, caches)
        nxt = jnp.argmax(logits, -1).astype(jnp.int32)
        tokens_out.append(np.asarray(nxt))
        if cfg.input_mode != "embeds":
            inputs = {**inputs, "tokens": nxt[:, None]}
    dt = time.time() - t0
    toks = args.batch * args.steps
    print(f"decoded {toks} tokens in {dt:.2f}s "
          f"({toks/dt:.1f} tok/s on {mesh_shape} "
          f"{jax.devices()[0].platform} mesh)")
    out = np.stack(tokens_out, 1)
    # argmax over (B, V) logits must yield in-vocabulary token ids
    # (np.isfinite on an int array is vacuously true)
    assert ((out >= 0) & (out < cfg.vocab_size)).all()
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite_3_8b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--steps", type=int, default=16)
    ap.add_argument("--cache-len", type=int, default=128)
    ap.add_argument("--mesh", default="1x1")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--continuous", action="store_true",
                    help="run the continuous-batching engine on a "
                         "staggered-arrival synthetic workload")
    ap.add_argument("--static", action="store_true",
                    help="run the engine's batch-synchronous reference "
                         "policy on the synthetic workload (same jitted "
                         "steps; implies --continuous)")
    ap.add_argument("--requests", type=int, default=8,
                    help="continuous mode: number of synthetic requests")
    ap.add_argument("--slots", type=int, default=4,
                    help="continuous mode: KV-cache slots (concurrency)")
    ap.add_argument("--arrival-gap", type=int, default=2,
                    help="continuous mode: ticks between request arrivals")
    ap.add_argument("--prompt-len", type=int, nargs=2, default=(3, 12),
                    metavar=("MIN", "MAX"),
                    help="continuous mode: synthetic prompt length range "
                         "(prompts longer than --prefill-chunk stream in "
                         "chunk per tick)")
    ap.add_argument("--max-new", type=int, nargs=2, default=(4, 24),
                    metavar=("MIN", "MAX"),
                    help="continuous mode: synthetic range of tokens each "
                         "request generates")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="continuous mode: max prompt tokens per prefill "
                         "call (default: the largest single call the cache "
                         "geometry allows)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="continuous mode: sampling temperature "
                         "(0 = greedy, the bit-exact default)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="continuous mode: top-k filter (0 = off)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="continuous mode: nucleus (top-p) filter (1 = off)")
    ap.add_argument("--sample-seed", type=int, default=0,
                    help="continuous mode: base sampler seed (request i "
                         "uses seed+i; streams reproduce run-to-run)")
    ap.add_argument("--speculate", action="store_true",
                    help="continuous mode: speculative decoding with the "
                         "prompt-lookup (n-gram) self-drafter — several "
                         "tokens per tick, streams bit-identical")
    ap.add_argument("--draft-k", type=int, default=4,
                    help="continuous mode: max draft tokens per verify "
                         "tick (1..MAX_DRAFT_K)")
    ap.add_argument("--draft-model", default=None,
                    help="continuous mode: draft with this REDUCED arch as "
                         "the draft model instead of prompt lookup "
                         "(implies --speculate; vocab must match)")
    ap.add_argument("--policy", choices=("fifo", "slo"), default="fifo",
                    help="continuous mode: scheduling policy — 'fifo' (the "
                         "reference) or 'slo' (priority classes, aging, "
                         "deadline shedding, exact-resume preemption; see "
                         "docs/scheduling.md; implies --continuous)")
    ap.add_argument("--priority", default=None,
                    choices=("interactive", "batch", "best_effort"),
                    help="continuous mode: priority class every synthetic "
                         "request carries (default: no SLO metadata; for "
                         "per-class mixes use serving.traces)")
    ap.add_argument("--deadline-ticks", type=int, default=None,
                    help="continuous mode: TTFT deadline in ticks relative "
                         "to each request's arrival (>= 1; misses are "
                         "counted in telemetry)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="continuous mode: cross-request prefix caching — "
                         "admissions sharing a cached prompt prefix adopt "
                         "its slot-cache row and prefill only from the "
                         "first divergent chunk; streams stay bit-identical "
                         "(docs/prefix_caching.md; implies --continuous)")
    ap.add_argument("--prefix-cache-nodes", type=int, default=None,
                    help="prefix cache: max cached boundary rows before "
                         "LRU eviction (>= 1; default 256; requires "
                         "--prefix-cache)")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="prepend one common N-token system prompt to "
                         "every synthetic request (>= 0; the workload "
                         "shape --prefix-cache accelerates — i.i.d. "
                         "prompts share nothing)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a structured trace of the run (admissions, "
                         "prefill chunks, commits, preemptions, failovers "
                         "...) to PATH; tracing is pure observation — token "
                         "streams are bit-identical with it on or off "
                         "(docs/observability.md; implies --continuous)")
    ap.add_argument("--trace-format", choices=("chrome", "jsonl", "both"),
                    default="chrome",
                    help="--trace-out format: 'chrome' (chrome://tracing / "
                         "Perfetto JSON, the default), 'jsonl' (one event "
                         "per line), or 'both' (chrome at PATH, jsonl at "
                         "PATH.jsonl)")
    ap.add_argument("--metrics-every", type=int, default=0,
                    help="print live fleet-wide TTFT/latency percentiles "
                         "every N ticks — fixed-bucket histograms riding "
                         "the SAME b=1 stats reduction as the counters "
                         "(0 = off; implies --continuous)")
    ap.add_argument("--probe", action="store_true",
                    help="record (p, nbytes, method, blocks) -> wall-time "
                         "samples from every collective in the run and "
                         "print the alpha-beta fit + predicted-vs-measured "
                         "residuals (docs/observability.md; implies "
                         "--continuous)")
    ap.add_argument("--autotune-cache", default=None, metavar="PATH",
                    help="per-deployment autotune cache file; overrides "
                         "REPRO_AUTOTUNE_CACHE and the XDG default (what "
                         "the b=1 stats reduction's method='auto' consults)")
    ap.add_argument("--chaos-seed", type=int, default=None,
                    help="serve the workload across --replicas engines "
                         "under the seeded fault plan (kills, flaps, "
                         "stragglers, poisoned logits) and verify zero "
                         "token divergence vs the undisturbed run")
    ap.add_argument("--replicas", type=int, default=3,
                    help="chaos mode: fleet size (>= 2)")
    ap.add_argument("--heartbeat-timeout", type=float, default=2.0,
                    help="chaos mode: heartbeat deadline in ticks (the "
                         "fleet simulation's virtual clock)")
    ap.add_argument("--heartbeat-misses", type=int, default=1,
                    help="chaos mode: missed deadlines before a SUSPECT "
                         "replica is declared dead (flap tolerance)")
    ap.add_argument("--rejoin-backoff", type=float, default=1.0,
                    help="chaos mode: base rejoin probation in ticks "
                         "(doubles per drop)")
    args = ap.parse_args(argv)
    _validate_args(ap, args)
    if args.autotune_cache:
        from repro.core import autotune
        autotune.set_cache_path(args.autotune_cache)
    if args.chaos_seed is not None:
        return serve_chaos(args)
    if args.continuous or args.static or args.speculate or args.draft_model \
            or args.policy != "fifo" or args.priority is not None \
            or args.deadline_ticks is not None or args.prefix_cache \
            or args.trace_out is not None or args.metrics_every > 0 \
            or args.probe:
        return serve_continuous(args)
    return serve_loop(args)


def _validate_args(ap, args) -> None:
    """Reject bad flag values BEFORE any engine/jit work: a broken value
    that only explodes once a step is traced costs minutes of compile on a
    real mesh and produces an opaque XLA error instead of a usage line."""
    from repro.serving.speculative import MAX_DRAFT_K
    if args.prefill_chunk is not None and args.prefill_chunk < 1:
        ap.error(f"--prefill-chunk must be >= 1, got {args.prefill_chunk}")
    if args.arrival_gap < 0:
        ap.error(f"--arrival-gap must be >= 0, got {args.arrival_gap}")
    if args.requests < 1:
        ap.error(f"--requests must be >= 1, got {args.requests}")
    if args.slots < 1:
        ap.error(f"--slots must be >= 1, got {args.slots}")
    lo, hi = args.prompt_len
    if lo < 1 or hi < lo:
        ap.error(f"--prompt-len needs 1 <= MIN <= MAX, got {lo} {hi}")
    lo, hi = args.max_new
    if lo < 1 or hi < lo:
        ap.error(f"--max-new needs 1 <= MIN <= MAX, got {lo} {hi}")
    if not 1 <= args.draft_k <= MAX_DRAFT_K:
        ap.error(f"--draft-k must be in [1, {MAX_DRAFT_K}], "
                 f"got {args.draft_k}")
    if args.batch < 1:
        ap.error(f"--batch must be >= 1, got {args.batch}")
    if args.cache_len < 1:
        ap.error(f"--cache-len must be >= 1, got {args.cache_len}")
    if args.heartbeat_timeout <= 0:
        ap.error(f"--heartbeat-timeout must be > 0, "
                 f"got {args.heartbeat_timeout}")
    if args.heartbeat_misses < 1:
        ap.error(f"--heartbeat-misses must be >= 1, "
                 f"got {args.heartbeat_misses}")
    if args.rejoin_backoff < 0:
        ap.error(f"--rejoin-backoff must be >= 0, got {args.rejoin_backoff}")
    if args.deadline_ticks is not None and args.deadline_ticks < 1:
        ap.error(f"--deadline-ticks must be >= 1, got {args.deadline_ticks}")
    if args.prefix_cache_nodes is not None:
        if not args.prefix_cache:
            ap.error("--prefix-cache-nodes requires --prefix-cache "
                     "(the node bound configures the prefix trie)")
        if args.prefix_cache_nodes < 1:
            ap.error(f"--prefix-cache-nodes must be >= 1, "
                     f"got {args.prefix_cache_nodes}")
    if args.shared_prefix < 0:
        ap.error(f"--shared-prefix must be >= 0, got {args.shared_prefix}")
    if args.metrics_every < 0:
        ap.error(f"--metrics-every must be >= 0, got {args.metrics_every}")
    if args.trace_out is None and args.trace_format != "chrome":
        ap.error("--trace-format requires --trace-out (there is no trace "
                 "file to format without it)")
    if args.prefix_cache and args.chaos_seed is not None:
        ap.error("--prefix-cache is incompatible with --chaos-seed: the "
                 "trie is per-session state and the chaos baseline/fleet "
                 "comparison assumes identical tick accounting")
    if args.policy != "fifo":
        if args.static:
            ap.error("--policy slo is incompatible with --static: static "
                     "batching IS the batch-synchronous FIFO reference")
        if args.chaos_seed is not None:
            ap.error("--policy slo is incompatible with --chaos-seed: the "
                     "fleet's exact-resume accounting assumes FIFO "
                     "(shedding would strand the run-to-completion loop)")
    if args.chaos_seed is not None:
        if args.replicas < 2:
            ap.error(f"--chaos-seed needs --replicas >= 2, "
                     f"got {args.replicas}")
        if args.speculate or args.draft_model:
            ap.error("--chaos-seed is incompatible with --speculate/"
                     "--draft-model: the drafter slot table is engine-"
                     "global, and the fleet runs one session per replica")
        if args.static:
            ap.error("--chaos-seed is incompatible with --static "
                     "(the fleet is continuous-batching only)")


if __name__ == "__main__":
    # the command line, not main(): tests drive main() in-process and keep
    # the compile cache off
    enable_compile_cache()
    main()
