"""SLO scheduling: exact-resume preemption bit-identity across
architectures and decode modes, telemetry drift guard, trace-generator
determinism, engine-level shedding and deadline accounting, CLI
fail-fast validation.

The acceptance bar (ISSUE 7): preempted-and-resumed streams equal
undisturbed streams for attention / recurrent / hybrid stacks, greedy and
sampled, with and without speculative decoding active on the preempted
slot — scheduling policy moves WHEN tokens land, never WHAT.
"""

import dataclasses

import jax
import pytest

from repro.configs.base import ParallelConfig, get_config
from repro.launch.mesh import make_mesh
from repro.models import transformer as tf
from repro.models.transformer import ModelConfig
from repro.serving import (STATS_FIELDS, Request, RequestState,
                           SamplingParams, ServingEngine, SLOParams,
                           SLOPolicy, SpecParams, StepStats, PriorityClass,
                           TraceSpec, generate_trace, make_policy,
                           stats_vector, trace_summary)


def tiny_cfg(**kw):
    base = dict(name="slo-tiny", n_layers=2, d_model=32, n_heads=2,
                n_kv_heads=2, d_ff=64, vocab_size=101, remat=False)
    base.update(kw)
    return ModelConfig(**base)


_ENGINE_CACHE = {}


def get_engine(arch):
    """One compiled single-slot engine per arch, shared by the matrix —
    n_slots=1 forces every admission conflict through preemption."""
    if arch not in _ENGINE_CACHE:
        cfg = (tiny_cfg() if arch == "attn-tiny"
               else get_config(arch, reduced=True))
        mesh = make_mesh((1, 1), ("data", "model"))
        params = tf.init_params(jax.random.PRNGKey(0), cfg)
        _ENGINE_CACHE[arch] = (cfg, ServingEngine(
            cfg, ParallelConfig(), mesh, params, n_slots=1, max_len=48,
            min_prefill_bucket=8))
    return _ENGINE_CACHE[arch]


# repetitive prompt: gives the n-gram drafter material, so the spec cases
# actually accept drafts on the preempted slot
VICTIM_PROMPT = (5, 9, 2, 5, 9, 2, 5, 9)


def _matrix_reqs(cfg, *, sampled, spec):
    sp = SamplingParams(temperature=0.9, top_p=0.85, seed=11) \
        if sampled else None
    victim = Request(0, VICTIM_PROMPT, max_new_tokens=16, arrival=0,
                     sampling=sp, spec=spec,
                     slo=SLOParams(priority=PriorityClass.BATCH))
    interloper = Request(
        1, (7, 3), max_new_tokens=3, arrival=2,
        sampling=None if sp is None else
        dataclasses.replace(sp, seed=12),
        slo=SLOParams(priority=PriorityClass.INTERACTIVE,
                      deadline_ticks=8))
    return [victim, interloper]


# ==========================================================================
# the bit-identity matrix (the tentpole's acceptance bar)
# ==========================================================================

@pytest.mark.parametrize("arch", ["attn-tiny", "rwkv6_7b", "jamba_v0_1_52b"])
@pytest.mark.parametrize("mode", ["greedy", "sampled",
                                  "greedy+spec", "sampled+spec"])
def test_preempt_resume_streams_bit_identical(arch, mode):
    """FIFO (undisturbed) vs SLO (preempted mid-decode) on one slot: the
    interloper evicts the victim, the victim later resumes from its
    journal, and both streams must match the undisturbed run exactly —
    attention, recurrent, and hybrid caches; greedy and seeded-sampled;
    with and without speculative decoding on the preempted slot."""
    cfg, eng = get_engine(arch)
    sampled = mode.startswith("sampled")
    spec = SpecParams(draft_k=4) if mode.endswith("+spec") else None

    base = eng.run(_matrix_reqs(cfg, sampled=sampled, spec=spec))
    slo = eng.run(_matrix_reqs(cfg, sampled=sampled, spec=spec),
                  policy=SLOPolicy(age_ticks=100))

    assert slo["preemptions"] >= 1, \
        f"{arch}/{mode}: the interloper must actually preempt"
    assert slo["tokens"] == base["tokens"], \
        f"{arch}/{mode}: preempt+resume changed a stream"
    if eng._exact_resume:
        # full-capacity rings resume through the journal (bounded rings and
        # capacity-dispatch MoE fall back to the lossy restart — same
        # stream, zero replay count)
        assert slo["resumed_tokens"] > 0, \
            f"{arch}/{mode}: resume must replay the journal"


def test_held_experts_moe_resumes_exactly():
    """The drop-free held-experts layer (Jamba) resumes a preempted stream
    through its journal, bit-identical to the undisturbed run; capacity
    dispatch, whose drops depend on the chunk plan, keeps the restart."""
    cfg, eng = get_engine("jamba_v0_1_52b")
    assert cfg.moe.impl == "grouped" and eng._exact_resume
    base = eng.run(_matrix_reqs(cfg, sampled=False, spec=None))
    slo = eng.run(_matrix_reqs(cfg, sampled=False, spec=None),
                  policy=SLOPolicy(age_ticks=100))
    assert slo["preemptions"] >= 1 and slo["resumed_tokens"] > 0
    assert slo["tokens"] == base["tokens"]
    capped = tiny_cfg(pattern=(("attn", "moe"),),
                      moe=tf.MoESettings(n_experts=4, top_k=2))
    eng = ServingEngine(capped, ParallelConfig(),
                        make_mesh((1, 1), ("data", "model")),
                        tf.init_params(jax.random.PRNGKey(0), capped),
                        n_slots=1, max_len=48)
    assert not eng._exact_resume


def test_preempted_request_metadata():
    """The victim's Request object records the eviction and resumes to
    completion; the interloper's deadline is met."""
    cfg, eng = get_engine("attn-tiny")
    reqs = _matrix_reqs(cfg, sampled=False, spec=None)
    session = eng.start(reqs, policy=SLOPolicy(age_ticks=100))
    while session.running:
        session.tick()
    victim, interloper = reqs
    assert victim.preemptions >= 1
    assert victim.state is RequestState.DONE
    assert len(victim.tokens) == victim.max_new_tokens
    assert interloper.t_first is not None
    assert interloper.t_first <= interloper.deadline
    rep = session.report()
    assert rep["slo"]["interactive"]["deadline_hit_rate"] == 1.0


# ==========================================================================
# telemetry drift guard (satellite 3)
# ==========================================================================

def test_stats_fields_match_stepstats_exactly():
    """STATS_FIELDS and the StepStats dataclass must agree field-for-field
    (tick aside): PRs 3-6 grew both by hand; pin them together so the b=1
    reduction payload cannot silently skew."""
    names = tuple(f.name for f in dataclasses.fields(StepStats))
    assert names[0] == "tick"
    assert names[1:] == STATS_FIELDS


def test_stats_vector_refuses_drift():
    good = {f: 0.0 for f in STATS_FIELDS}
    assert stats_vector(good) == [0.0] * len(STATS_FIELDS)
    with pytest.raises(ValueError, match="drifted"):
        stats_vector({k: v for k, v in good.items()
                      if k != "preemptions"})
    with pytest.raises(ValueError, match="drifted"):
        stats_vector({**good, "surprise_counter": 1.0})


def test_engine_tick_emits_exactly_stats_fields():
    """The live guard: every tick's row comes out of stats_vector, so its
    length and order are pinned to STATS_FIELDS — including the new
    preemption/shed/deadline-miss counters."""
    cfg, eng = get_engine("attn-tiny")
    session = eng.start([Request(0, (3, 4, 5), max_new_tokens=2)])
    vec = session.tick()
    assert len(vec) == len(STATS_FIELDS)
    idx = {f: i for i, f in enumerate(STATS_FIELDS)}
    assert vec[idx["prefills"]] == 1
    assert vec[idx["preemptions"]] == 0
    assert vec[idx["shed_requests"]] == 0


# ==========================================================================
# trace generator determinism (satellite 4)
# ==========================================================================

def test_trace_same_seed_identical():
    spec = TraceSpec(n_requests=24)
    a = generate_trace(spec, vocab=97, seed=5)
    b = generate_trace(spec, vocab=97, seed=5)
    assert len(a) == len(b) == 24
    for ra, rb in zip(a, b):
        assert (ra.rid, ra.prompt, ra.max_new_tokens, ra.arrival, ra.slo) \
            == (rb.rid, rb.prompt, rb.max_new_tokens, rb.arrival, rb.slo)


def test_trace_different_seed_differs():
    spec = TraceSpec(n_requests=24)
    a = generate_trace(spec, vocab=97, seed=5)
    b = generate_trace(spec, vocab=97, seed=6)
    assert any(ra.prompt != rb.prompt or ra.arrival != rb.arrival
               for ra, rb in zip(a, b))


def test_trace_is_bursty_and_heavy_tailed():
    reqs = generate_trace(TraceSpec(n_requests=64), vocab=97, seed=7)
    s = trace_summary(reqs)
    assert s["peak_burst"] >= 2, "arrivals must actually burst"
    assert s["span_ticks"] > 1, "arrivals must spread over time"
    assert len(s["classes"]) >= 2, "the mix must span classes"
    plens = sorted(len(r.prompt) for r in reqs)
    assert plens[-1] >= 2 * plens[len(plens) // 2], \
        "the prompt-length tail must be heavy (max >= 2x median)"


def test_trace_respects_bounds():
    spec = TraceSpec(n_requests=32, max_prompt=10, max_out=6)
    for r in generate_trace(spec, vocab=50, seed=3):
        assert 1 <= len(r.prompt) <= 10
        assert 1 <= r.max_new_tokens <= 6
        assert all(0 <= t < 50 for t in r.prompt)


def test_slo_tick_gates_are_wall_clock_independent():
    """The smoke for bench_serving --slo: every deterministic quantity the
    bench gates on (ticks, preemptions, sheds, misses, per-class TTFT
    percentiles) must reproduce exactly across runs — tick counts never
    depend on wall time (the PR-4 lesson about shared-CPU noise)."""
    cfg, eng = get_engine("attn-tiny")
    spec = TraceSpec(n_requests=8, max_prompt=8, max_out=8)

    def run():
        return eng.run(generate_trace(spec, cfg.vocab_size, seed=17),
                       policy=SLOPolicy(age_ticks=16))

    a, b = run(), run()
    for k in ("ticks", "preemptions", "shed_requests", "deadline_misses",
              "total_tokens"):
        assert a[k] == b[k], k
    assert repr(a["slo"]) == repr(b["slo"])
    assert a["tokens"] == b["tokens"]


# ==========================================================================
# engine-level shedding + deadline accounting
# ==========================================================================

def test_engine_sheds_hopeless_best_effort():
    """A best-effort request whose TTFT deadline expires while it queues
    behind a long batch request is shed, counted once, and reported."""
    cfg, eng = get_engine("attn-tiny")
    hog = Request(0, (3, 4, 5), max_new_tokens=10, arrival=0,
                  slo=SLOParams(priority=PriorityClass.BATCH))
    doomed = Request(1, (6, 7), max_new_tokens=4, arrival=1,
                     slo=SLOParams(priority=PriorityClass.BEST_EFFORT,
                                   deadline_ticks=1))
    rep = eng.run([hog, doomed], policy=SLOPolicy(age_ticks=0))
    assert rep["shed_requests"] == 1
    assert rep["deadline_misses"] == 1
    assert doomed.state is RequestState.SHED
    assert doomed.tokens == [] and doomed.slot is None
    assert rep["slo"]["best_effort"]["shed"] == 1
    assert rep["slo"]["best_effort"]["deadline_hits"] == 0
    # the hog was untouched: best-effort never preempts batch
    assert hog.preemptions == 0 and len(hog.tokens) == 10


def test_deadline_miss_counted_once_under_fifo():
    """Deadline accounting is engine-side and policy-independent: a late
    first token under plain FIFO still counts exactly one miss."""
    cfg, eng = get_engine("attn-tiny")
    hog = Request(0, (3, 4, 5), max_new_tokens=8, arrival=0)
    late = Request(1, (6, 7), max_new_tokens=2, arrival=0,
                   slo=SLOParams(priority=PriorityClass.INTERACTIVE,
                                 deadline_ticks=2))
    rep = eng.run([hog, late])
    assert rep["policy"] == "fifo"
    assert rep["deadline_misses"] == 1
    assert late.t_first is not None and late.t_first > late.deadline
    assert rep["slo"]["interactive"]["deadline_hit_rate"] == 0.0


def test_static_mode_rejects_slo_policy():
    cfg, eng = get_engine("attn-tiny")
    with pytest.raises(ValueError, match="static"):
        eng.start([], static=True, policy=SLOPolicy())


def test_make_policy_factory():
    assert make_policy("fifo").name == "fifo"
    pol = make_policy("slo", age_ticks=8, max_queue=4)
    assert pol.name == "slo" and pol.age_ticks == 8 and pol.max_queue == 4
    with pytest.raises(ValueError, match="unknown"):
        make_policy("priority")
    with pytest.raises(ValueError):
        make_policy("slo", age_ticks=-1)
    with pytest.raises(ValueError):
        SLOParams(deadline_ticks=0)


# ==========================================================================
# CLI fail-fast validation (serve.py flags)
# ==========================================================================

@pytest.mark.parametrize("argv", [
    ["--policy", "slo", "--static"],
    ["--policy", "slo", "--chaos-seed", "3"],
    ["--deadline-ticks", "0"],
    ["--priority", "urgent"],
])
def test_serve_cli_rejects_bad_slo_flags(argv):
    from repro.launch.serve import main
    with pytest.raises(SystemExit) as ei:
        main(argv)
    assert ei.value.code == 2
