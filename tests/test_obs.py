"""End-to-end observability plane (ISSUE 10): structured tick tracing,
collective timing probes, and fleet-mergeable histograms.

The acceptance bars:

* tracing is PURE OBSERVATION — the same workload with tracing/metrics on
  and off produces bit-identical token streams (attention and SSM caches,
  greedy and sampled, with speculation and preemption in play);
* a traced run covering chunked prefill + speculation + a preemption + a
  failover exports a Perfetto-loadable Chrome trace with per-request
  lifetime spans;
* an installed probe records >= 1 sample per instrumented all_reduce
  (trace-time notes from the collective layer, timed samples from the b=1
  stats reducer's host boundary);
* the least-squares fitter recovers (alpha, beta) within 10% from noisy
  simulator-generated samples, with residuals reported.
"""

import dataclasses
import json
import math
import os
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

from repro.core import cost_model as cm
from repro.obs import (DEFAULT_EDGES, PROFILER_PREFIX, SPAN_NAMES,
                       CollectiveProbe, ProbeSample, StreamingMetrics,
                       TickHistogram, TraceEvent, Tracer, export_residuals,
                       fit_alpha_beta, fit_hier, flat_coeffs, predict_time,
                       probing, residual_report, span)
from repro.obs import probe as probe_mod
from repro.runtime.chaos import Fault, FaultPlan
from repro.serving import (STATS_FIELDS, FleetRunner, PriorityClass, Request,
                           SamplingParams, SLOParams, SLOPolicy, SpecParams,
                           StepStats, TelemetryLog, stats_vector)

from test_serving import make_engine, make_requests

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ==========================================================================
# tracer: recording, bounds, exporters (host-only, no model)
# ==========================================================================

def test_tracer_records_ordered_events():
    tr = Tracer()
    tr.event("admit", 0, rid=3, replica=1, prompt_len=7)
    tr.event("decode", 1, n_active=2)
    tr.event("commit", 1, rid=3, n_tokens=1)
    assert len(tr) == 3
    assert tr.names() == {"admit", "decode", "commit"}
    assert [e.seq for e in tr.events] == [1, 2, 3]      # stable intra-tick
    admit = tr.by_name("admit")[0]
    assert (admit.tick, admit.rid, admit.replica) == (0, 3, 1)
    assert admit.attrs["prompt_len"] == 7
    assert tr.by_name("decode")[0].rid is None          # engine-lane event


def test_tracer_max_events_counts_drops():
    tr = Tracer(max_events=3)
    for t in range(5):
        tr.event("decode", t)
    assert len(tr) == 3 and tr.dropped == 2
    assert tr.to_chrome()["otherData"]["dropped_events"] == 2
    with pytest.raises(ValueError):
        Tracer(max_events=0)


def test_tracer_jsonl_roundtrip(tmp_path):
    tr = Tracer()
    tr.event("commit", 4, rid=0, n_tokens=np.int64(1),
             ttft_ticks=np.float32(2.0))
    path = tmp_path / "trace.jsonl"
    assert tr.to_jsonl(str(path)) == 1
    rows = [json.loads(line) for line in path.read_text().splitlines()]
    t_ns = tr.events[0].t_ns
    assert t_ns > 0
    assert rows == [{"name": "commit", "tick": 4, "seq": 1, "replica": 0,
                     "t_ns": t_ns, "dur_ns": 0, "parent": None,
                     "rid": 0,
                     "attrs": {"n_tokens": 1, "ttft_ticks": 2.0}}]


def test_chrome_trace_layout(tmp_path):
    """pid = replica, tid = rid + 1 (0 = engine lane), one metadata pair
    per lane, one lifetime span per request, one slice per event — all in
    microseconds of real time from the first event, ticks in ``args``."""
    tr = Tracer()
    tr.event("admit", 0, rid=0, replica=0)
    with span(tr, "decode", 1, replica=2, n_active=1):
        tr.event("admit", 1, rid=1, replica=2)
    tr.event("commit", 3, rid=0, replica=0)
    path = tmp_path / "trace.json"
    doc = tr.to_chrome(str(path))
    assert json.loads(path.read_text()) == doc          # file == return
    evs = doc["traceEvents"]
    meta = [e for e in evs if e["ph"] == "M"]
    assert {(m["name"], m["pid"], m.get("args", {}).get("name"))
            for m in meta} >= {
        ("process_name", 0, "replica 0"), ("process_name", 2, "replica 2"),
        ("thread_name", 0, "req 0"), ("thread_name", 2, "req 1"),
        ("thread_name", 2, "engine")}
    spans = [e for e in evs if e.get("cat") == "request"]
    by_req = {(s["pid"], s["args"]["rid"]): s for s in spans}
    assert set(by_req) == {(0, 0), (2, 1)}
    t0 = tr.events[0].t_ns                              # the first event
    first, last = tr.by_name("admit")[0], tr.by_name("commit")[0]
    assert by_req[(0, 0)]["ts"] == 0
    assert by_req[(0, 0)]["dur"] == (last.t_ns - first.t_ns) / 1e3
    assert doc["otherData"]["t0_ns"] == t0
    slices = [e for e in evs if e.get("cat") == "serving"]
    assert len(slices) == 4
    by_seq = {e.seq: e for e in tr.events}
    for s, e in zip(slices, tr.events):
        assert s["ph"] == "X" and s["name"] == e.name
        assert s["ts"] == (e.t_ns - t0) / 1e3 and s["dur"] == e.dur_ns / 1e3
        assert s["args"]["tick"] == e.tick
    eng = [s for s in slices if s["name"] == "decode"][0]
    assert (eng["pid"], eng["tid"]) == (2, 0)           # engine lane
    assert eng["dur"] > 0 and eng["args"]["n_active"] == 1
    inner = by_seq[tr.by_name("decode")[0].seq + 1]     # the nested admit
    assert inner.rid == 1 and inner.parent == tr.by_name("decode")[0].seq


def test_span_taxonomy_is_pinned():
    """docs/observability.md documents exactly these producer names."""
    assert set(SPAN_NAMES) == {"tick", "schedule", "submit", "admit",
                               "prefill_chunk", "decode", "draft", "verify",
                               "fetch", "commit", "reset", "stats",
                               "preempt", "resume", "failover",
                               "prefix_adopt", "shed"}


# ==========================================================================
# histograms: buckets, conservative percentiles, mergeability
# ==========================================================================

def test_histogram_edge_validation():
    with pytest.raises(ValueError):
        TickHistogram(())
    with pytest.raises(ValueError):
        TickHistogram((1.0, 1.0))
    with pytest.raises(ValueError):
        TickHistogram((4.0, 2.0))


def test_histogram_buckets_and_conservative_percentile():
    h = TickHistogram((1.0, 2.0, 4.0))
    assert h.n_buckets == 4
    assert math.isnan(h.percentile(50))                 # empty -> NaN
    h.add_many([0, 1, 1, 3, 100])                       # edge-inclusive
    assert list(h.counts) == [3, 0, 1, 1]
    assert h.total() == 5
    # conservative: always the UPPER edge of the containing bucket
    assert h.percentile(50) == 1.0
    assert h.percentile(80) == 4.0
    assert h.percentile(99) == 4.0                      # overflow clamps


def test_histogram_percentile_never_underestimates():
    rng = np.random.default_rng(0)
    vals = rng.integers(0, 100, 500)
    h = TickHistogram(DEFAULT_EDGES)
    h.add_many(vals)
    for q in (50, 90, 95, 99):
        assert h.percentile(q) >= np.percentile(vals, q) or \
            h.percentile(q) == DEFAULT_EDGES[-1]


def test_histogram_merge_is_addition():
    a, b = TickHistogram((1.0, 2.0)), TickHistogram((1.0, 2.0))
    a.add_many([0, 1])
    b.add_many([2, 5, 5])
    a.merge_counts(b.counts)
    assert list(a.counts) == [2, 1, 2]
    with pytest.raises(ValueError, match="merge shape"):
        a.merge_counts([1.0, 2.0])


def test_streaming_metrics_row_is_pure_increment():
    m = StreamingMetrics((1.0, 2.0))
    assert m.width == 6
    row = m.row([0, 3], [1])
    assert row == [1.0, 0.0, 1.0, 1.0, 0.0, 0.0]
    assert m.ttft.total() == 0                          # row did not mutate
    m.absorb(row)
    m.absorb(row)                                       # 2-replica tile
    assert m.ttft.total() == 4 and m.latency.total() == 2
    snap = m.snapshot()
    assert snap["ttft_n"] == 4 and snap["latency_ticks_p50"] == 1.0
    with pytest.raises(ValueError, match="metrics tail has 2 floats"):
        m.absorb([0.0, 0.0])


# ==========================================================================
# telemetry satellites: drift guard, backfill, report keys
# ==========================================================================

def test_stats_vector_rejects_extra_and_missing():
    good = {f: 0.0 for f in STATS_FIELDS}
    assert stats_vector(good) == [0.0] * len(STATS_FIELDS)
    bad = dict(good)
    del bad["prefills"]
    bad["bogus_counter"] = 1.0
    with pytest.raises(ValueError) as err:
        stats_vector(bad)
    msg = str(err.value)
    assert "missing=['prefills']" in msg
    assert "unexpected=['bogus_counter']" in msg


def test_stepstats_backfills_appended_fields():
    """Rows recorded before a counter existed still parse: every field
    appended after the original four defaults to 0.0."""
    s = StepStats(0, 1.0, 2.0, 3.0, 4.0)
    for field in STATS_FIELDS[4:]:
        assert getattr(s, field) == 0.0
    assert dataclasses.asdict(StepStats(0, *range(len(STATS_FIELDS)))) \
        == {"tick": 0, **{f: float(i)
                          for i, f in enumerate(STATS_FIELDS)}}


def test_report_percentiles_and_tok_s_note():
    log = TelemetryLog()
    rep = log.report([], wall_s=0.0, ticks=5)
    assert math.isnan(rep["tok_s"])
    assert rep["tok_s_note"] == "wall_s <= 0: tok_s undefined"
    for k in ("ttft_ticks_p95", "ttft_ticks_p99", "latency_ticks_p99"):
        assert k in rep and math.isnan(rep[k])
    rep = log.report([], wall_s=1.5, ticks=5)
    assert rep["tok_s_note"] is None


def test_telemetry_log_keeps_full_reduced_row():
    """``last_reduced`` keeps payload appended past STATS_FIELDS (the
    histogram tail) that StepStats deliberately drops."""
    log = TelemetryLog()
    vec = list(range(len(STATS_FIELDS))) + [7.0, 9.0]
    s = log.step(0, vec)
    assert s.queue_depth == 0.0 and s.prefix_tokens_reused == 15.0
    assert list(log.last_reduced[len(STATS_FIELDS):]) == [7.0, 9.0]


# ==========================================================================
# probe: ring buffer, ambient install, cost-model predictions
# ==========================================================================

def test_probe_ring_buffer_and_filters():
    pr = CollectiveProbe(capacity=2)
    pr.note("dptree", 8, 64, 1, kind="trace")
    pr.note("dptree", 8, 64, 1, kind="timed", wall_s=1e-4)
    pr.note("ring", 8, 1 << 20, 1, kind="timed", wall_s=2e-3)
    assert len(pr) == 2 and pr.n_seen == 3              # ring evicted one
    assert [s.method for s in pr.timed()] == ["dptree", "ring"]
    assert pr.traced() == []
    with pytest.raises(ValueError):
        CollectiveProbe(capacity=0)


def test_probing_context_installs_and_restores():
    assert probe_mod.active() is None
    outer = probe_mod.install(CollectiveProbe())
    with probing() as pr:
        assert probe_mod.active() is pr
    assert probe_mod.active() is outer
    probe_mod.uninstall()
    assert probe_mod.active() is None


def test_predict_time_matches_cost_model():
    p, m, b = 16, 4096.0, 4
    assert predict_time("dptree", p, int(m), b) == \
        cm.dptree_time(p, m, b, cm.TPU_V5E)
    assert predict_time("ring", p, int(m), 1) == \
        cm.ring_time(p, m, cm.TPU_V5E)
    assert predict_time("hier", p, int(m), b, levels=(4,)) == \
        cm.hier_time(p, m, b, cm.TPU_V5E, group_size=(4,))
    assert predict_time("psum", p, int(m), 1) is None   # no closed form


def test_probe_note_fills_prediction():
    pr = CollectiveProbe()
    s = pr.note("sptree", 8, 2048, 2, kind="timed", wall_s=3e-4)
    assert s.predicted_s == cm.sptree_time(8, 2048.0, 2, cm.TPU_V5E)
    assert s.to_dict()["method"] == "sptree"


@pytest.mark.parametrize("p", [2, 4, 5, 8])
def test_probe_sample_counts_the_tree_rounds(p):
    """A trace-time dptree/sptree sample carries the transfer rounds its
    schedule runs. Under a commutative operator those are the concurrent
    rounds: one a block plus the trees' skew, ``b + 4H`` with two roots
    (``H`` the deepest node's depth; ``b`` alone at p = 2, no node below the
    roots) and ``b + 4H - 2`` with one. That is fewer than the one-ported
    schedule's step per active edge class per macro-round wherever more
    than one class is active; a merely associative operator still runs
    those."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import AbstractMesh, PartitionSpec as P
    from repro.core import topology as tp
    from repro.core.collectives import CollectiveConfig, all_reduce
    from repro.core.dptree import tree_rounds
    mesh = AbstractMesh((p,), ("data",))
    m = 600
    for method, build, extra in (("dptree", tp.build_dual_tree, 0),
                                 ("sptree", tp.build_single_tree, -2)):
        topo = build(p)
        H = topo.max_depth
        for b in (1, 7, 231, m + 5):
            cfg = CollectiveConfig(method=method, num_blocks=b)
            with probing() as pr:
                f = jax.shard_map(lambda x: all_reduce(x, "data", p, cfg),
                                  mesh=mesh, in_specs=P("data"),
                                  out_specs=P("data"))
                jax.make_jaxpr(f)(jax.ShapeDtypeStruct((p * m,),
                                                       jnp.float32))
            (s,) = pr.traced()
            nb = min(b, m)
            want = nb if H == 0 else nb + 4 * H + extra
            assert s.rounds == want, (method, p, b, s.rounds, want)
            seed = topo.num_macro_rounds(nb) * len(topo.active_classes())
            assert tree_rounds(topo, nb, lambda a, c: a + c) == seed
            if len(topo.active_classes()) > 1:
                assert s.rounds < seed, (method, p, b, s.rounds, seed)
    # the probe's samples of other methods carry no rounds
    assert pr.note("ring", p, 64, 1).rounds is None


# ==========================================================================
# fit: alpha-beta recovery, hier per-level recovery, diagnostics
# ==========================================================================

def _flat_samples(model, *, seed, noise, n=40):
    """Simulator-generated timed samples: latency-dominated shapes (small
    payloads, varied p) so alpha is well-constrained, plus larger payloads
    so beta is too."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        method = ["dptree", "sptree", "ring"][int(rng.integers(3))]
        p = int(2 ** rng.integers(2, 7))                # 4..64
        nbytes = int(2 ** rng.integers(6, 21))          # 64B..1MB
        b = 1 if method == "ring" else int(rng.integers(1, 5))
        t = predict_time(method, p, nbytes, b, model)
        t *= 1.0 + noise * float(rng.standard_normal())
        out.append(ProbeSample(p=p, nbytes=nbytes, dtype="float32",
                               method=method, num_blocks=b, wall_s=t,
                               kind="timed"))
    return out


@pytest.mark.parametrize("seed", range(5))
def test_fit_recovers_alpha_beta_within_10pct(seed):
    true = cm.CommModel(alpha=2.4e-6, beta=9.0e-12, gamma=0.0, name="true")
    samples = _flat_samples(true, seed=seed, noise=0.005)
    fit = fit_alpha_beta(samples)
    assert fit.n_samples == 40
    assert abs(fit.alpha - true.alpha) / true.alpha < 0.10
    assert abs(fit.beta - true.beta) / true.beta < 0.10
    assert len(fit.residuals) == 40
    # the honesty number: residuals of the refit stay in the noise band
    rows = residual_report(samples, model=fit.model())
    assert rows and max(r["rel_err"] for r in rows) < 0.05
    assert fit.model("refit").name == "refit"


def test_fit_noise_free_is_exact():
    true = cm.CommModel(alpha=1.0e-6, beta=2.0e-11, gamma=0.0, name="true")
    fit = fit_alpha_beta(_flat_samples(true, seed=0, noise=0.0))
    assert np.isclose(fit.alpha, true.alpha, rtol=1e-9)
    assert np.isclose(fit.beta, true.beta, rtol=1e-9)
    assert fit.max_rel_err < 1e-9


def test_fit_rejects_degenerate_designs():
    one = ProbeSample(p=8, nbytes=64, dtype="float32", method="dptree",
                      num_blocks=1, wall_s=1e-4, kind="timed")
    with pytest.raises(ValueError, match="at least 2 samples"):
        fit_alpha_beta([one])
    with pytest.raises(ValueError, match="do not span"):
        fit_alpha_beta([one] * 10)                      # rank-1 design
    # trace-time notes never enter the system (no wall clock)
    trace = dataclasses.replace(one, kind="trace")
    with pytest.raises(ValueError):
        fit_alpha_beta([trace] * 10)


def test_fit_hier_recovers_intra_and_inter_constants():
    """Samples varying (p, m, b) under one spec identify the shared intra
    pair and the inter pair exactly (the four-column design is full rank
    only once p varies — the inter stage is the only lever against the
    constant/m-proportional intra columns)."""
    levels = (4, 2)
    intra = cm.CommModel(2e-7, 3e-12, 0.0, name="intra")
    inter = cm.CommModel(5e-6, 1e-10, 0.0, name="inter")
    rng = np.random.default_rng(3)
    samples = []
    for _ in range(30):
        p = int(8 * 2 ** rng.integers(1, 5))            # 16..128
        nbytes = int(2 ** rng.integers(8, 24))
        b = int(rng.integers(1, 9))
        t = cm.hier_time(p, float(nbytes), b, inter, group_size=levels,
                         intra_model=intra)
        samples.append(ProbeSample(p=p, nbytes=nbytes, dtype="float32",
                                   method="hier", num_blocks=b, wall_s=t,
                                   kind="timed", levels=levels))
    out = fit_hier(samples)
    assert out["spec"] == levels
    assert np.isclose(out["intra"].alpha, intra.alpha, rtol=1e-6)
    assert np.isclose(out["intra"].beta, intra.beta, rtol=1e-6)
    assert np.isclose(out["inter"].alpha, inter.alpha, rtol=1e-6)
    assert np.isclose(out["inter"].beta, inter.beta, rtol=1e-6)
    assert out["inter"].max_rel_err < 1e-6
    # fixed-p sampling cannot separate intra from inter: refuse, don't
    # hand back garbage constants
    fixed = [dataclasses.replace(s, p=16) for s in samples]
    with pytest.raises(ValueError, match="do not span"):
        fit_hier(fixed)


def test_fit_hier_rejects_mixed_or_missing_specs():
    mk = lambda lv: ProbeSample(p=8, nbytes=1024, dtype="float32",
                                method="hier", num_blocks=1, wall_s=1e-4,
                                kind="timed", levels=lv)
    with pytest.raises(ValueError, match="no timed hier samples"):
        fit_hier([])
    with pytest.raises(ValueError, match="share one explicit level spec"):
        fit_hier([mk((4,)), mk((2, 2))])
    with pytest.raises(ValueError, match="share one explicit level spec"):
        fit_hier([mk(None)])


def test_flat_coeffs_reconstruct_time():
    """T = c_alpha*alpha + c_beta*beta holds exactly for gamma = 0 models
    (the fit folds any compute term into beta — gamma is not separable
    from wire time by collective measurements alone)."""
    g0 = cm.CommModel(alpha=cm.TPU_V5E.alpha, beta=cm.TPU_V5E.beta,
                      gamma=0.0, name="g0")
    for method in ("dptree", "sptree", "redbcast", "ring"):
        ca, cb = flat_coeffs(method, 16, 8192.0, 2)
        want = predict_time(method, 16, 8192, 2, g0)
        got = ca * g0.alpha + cb * g0.beta
        assert np.isclose(got, want, rtol=1e-12), method


def test_export_residuals_lands_in_trace():
    tr = Tracer()
    samples = _flat_samples(cm.TPU_V5E, seed=1, noise=0.0, n=5)
    n = export_residuals(tr, samples, tick=7)
    assert n == 5 and len(tr.by_name("probe_residual")) == 5
    e = tr.by_name("probe_residual")[0]
    assert e.tick == 7
    assert set(e.attrs) >= {"p", "nbytes", "method", "measured_s",
                            "predicted_s", "residual_s", "rel_err"}


# ==========================================================================
# engine integration: purity (bit-identity on/off) + event coverage
# ==========================================================================

# repetitive prompts give the n-gram drafter real material, so the spec
# requests actually draft AND verify on the traced runs
_DRAFTY = (5, 9, 2, 5, 9, 2, 5, 9, 2, 5, 9, 2, 5, 9, 2, 5)


def _obs_matrix_reqs(sampled):
    sp = SamplingParams(temperature=0.9, top_p=0.85, seed=11) \
        if sampled else None
    victim = Request(0, _DRAFTY, max_new_tokens=12, arrival=0, sampling=sp,
                     spec=SpecParams(draft_k=4),
                     slo=SLOParams(priority=PriorityClass.BATCH))
    interloper = Request(
        1, (7, 3), max_new_tokens=3, arrival=2,
        sampling=None if sp is None else dataclasses.replace(sp, seed=12),
        slo=SLOParams(priority=PriorityClass.INTERACTIVE, deadline_ticks=8))
    return [victim, interloper]


_OBS_ENGINES = {}


def _obs_engine(arch):
    """One compiled single-slot chunked-prefill engine per arch: n_slots=1
    forces the interloper through preemption, prefill_chunk=8 makes the
    16-token victim prompt feed two chunks."""
    if arch not in _OBS_ENGINES:
        from repro.configs.base import get_config
        cfg = None if arch == "attn-tiny" else get_config(arch, reduced=True)
        _OBS_ENGINES[arch] = make_engine(cfg=cfg, n_slots=1, max_len=48,
                                         prefill_chunk=8)
    return _OBS_ENGINES[arch]


@pytest.mark.parametrize("arch", ["attn-tiny", "rwkv6_7b"])
@pytest.mark.parametrize("sampled", [False, True])
def test_traced_streams_bit_identical(arch, sampled):
    """The purity bar: tracing + live metrics attached mid-life change
    NOTHING about the streams — chunked prefill, speculation, and a
    preemption all in play, attention and SSM caches, greedy and seeded
    sampling."""
    cfg, eng = _obs_engine(arch)
    policy = SLOPolicy(age_ticks=100)
    base = eng.run(_obs_matrix_reqs(sampled), policy=policy)
    tr = Tracer()
    eng.tracer = tr
    eng.metrics = StreamingMetrics()
    eng.metrics_every = 2
    try:
        traced = eng.run(_obs_matrix_reqs(sampled), policy=policy)
    finally:
        eng.tracer = None
        eng.metrics = None
        eng.metrics_every = 0
    assert traced["tokens"] == base["tokens"], f"{arch}: tracing fed back"
    assert traced["preemptions"] >= 1
    # detached again: still identical (the hooks really are gone)
    again = eng.run(_obs_matrix_reqs(sampled), policy=policy)
    assert again["tokens"] == base["tokens"]
    # the run covered the core taxonomy, speculation included
    assert tr.names() >= {"admit", "prefill_chunk", "decode", "draft",
                          "commit", "preempt", "resume", "metrics"}
    # speculation genuinely ran; when the drafter lands proposals the
    # verify step traces too (a high-temperature stream can diverge from
    # the n-gram corpus entirely — then every proposal comes back empty
    # and the draft events record that instead)
    if traced["drafted_tokens"] > 0:
        assert "verify" in tr.names()
    else:
        assert any(e.attrs["proposed"] == 0 for e in tr.by_name("draft"))
    assert "live_metrics" in traced
    assert traced["live_metrics"]["ttft_n"] == traced["requests"]


def test_trace_event_payloads_are_faithful():
    """Spot-check attrs against the run's own telemetry: chunk counts,
    first-token TTFT stamps, verify accounting, preempt journals."""
    cfg, eng = _obs_engine("attn-tiny")
    tr = Tracer()
    eng.tracer = tr
    try:
        reqs = _obs_matrix_reqs(False)
        rep = eng.run(reqs, policy=SLOPolicy(age_ticks=100))
    finally:
        eng.tracer = None
    victim, interloper = reqs
    # admit: chunk plan for the 16-token prompt on the 8-token grid
    # (first admit per rid — re-admission after the preemption emits a
    # second one flagged resumed=True)
    admits: dict = {}
    for e in tr.by_name("admit"):
        admits.setdefault(e.rid, e)
    assert admits[0].attrs["prompt_len"] == 16
    assert admits[0].attrs["chunks"] == 2
    assert not admits[0].attrs["resumed"]
    assert any(e.attrs["resumed"] for e in tr.by_name("admit")
               if e.rid == 0)
    # one prefill_chunk event per chunk the telemetry counted
    assert len(tr.by_name("prefill_chunk")) == rep["prefill_chunks"]
    # first-token commits carry the TTFT the request object records
    firsts = {e.rid: e for e in tr.by_name("commit")
              if e.attrs.get("first_token")}
    assert firsts[1].attrs["ttft_ticks"] == interloper.ttft
    # preempt events journal the victim at eviction time
    pre = tr.by_name("preempt")
    assert pre and all(e.rid == 0 for e in pre)
    assert pre[0].attrs["journal_tokens"] >= 1
    resumes = tr.by_name("resume")
    assert resumes and resumes[0].attrs["preemptions"] >= 1
    # verify accounting, on each request's commit after a verify step,
    # sums to the telemetry counters
    vs = [e for e in tr.by_name("commit") if "n_draft" in e.attrs]
    assert vs and all(tr.events[e.parent - 1].name == "commit" for e in vs)
    assert sum(e.attrs["n_draft"] for e in vs) == rep["drafted_tokens"]
    assert sum(e.attrs["accepted"] for e in vs) == rep["accepted_tokens"]
    # final commits carry the stream length
    done = [e for e in tr.by_name("commit") if e.attrs.get("done")]
    assert {e.rid: e.attrs["n_tokens"] for e in done} == \
        {r.rid: len(r.tokens) for r in reqs}


def test_fleet_failover_traced_and_chrome_loadable(tmp_path):
    """The full acceptance composition in one trace: chunked prefill +
    speculation + preemption (session run) and a kill-driven failover
    (fleet run) — exported as a Chrome trace Perfetto can load, with a
    lifetime span per request and the replica topology in metadata."""
    cfg, eng = make_engine(n_slots=2, max_len=64, prefill_chunk=8)

    def reqs():
        out = make_requests(6, cfg, gap=1, seed=3, max_new=(8, 16))
        out[0] = Request(0, _DRAFTY, max_new_tokens=8, arrival=0,
                         spec=SpecParams(draft_k=4))
        return out

    want = eng.run(reqs())["tokens"]
    tr = Tracer()
    _, slot1 = _obs_engine("attn-tiny")
    slot1.tracer = tr
    eng.tracer = tr
    try:
        # a preemption first (single-slot engine, same tracer)
        pre = slot1.run(_obs_matrix_reqs(False),
                        policy=SLOPolicy(age_ticks=100))
        assert pre["preemptions"] >= 1
        # then chaos: kill replica 1 mid-run, work fails over
        runner = FleetRunner(eng, 2, plan=FaultPlan(
            (Fault(5, "kill", replica=1),)), timeout_s=2.0)
        rep = runner.run(reqs())
    finally:
        slot1.tracer = None
        eng.tracer = None
    assert rep["tokens"] == want                        # tracing is pure
    assert rep["failovers"] > 0
    fails = tr.by_name("failover")
    assert any(e.rid is None and e.replica == 1 for e in fails)
    moved = [e for e in fails if e.rid is not None]
    assert moved and all(e.attrs["new_p"] == 1 for e in moved)
    assert tr.names() >= {"admit", "prefill_chunk", "draft", "verify",
                          "preempt", "failover", "commit"}
    # both replicas emitted; the chrome export keeps them apart
    assert {e.replica for e in tr.events} >= {0, 1}
    path = tmp_path / "acceptance.json"
    doc = tr.to_chrome(str(path))
    loaded = json.loads(path.read_text())
    assert loaded["traceEvents"] and loaded["otherData"]["clock"] == \
        "perf_counter_ns"
    pids = {e["pid"] for e in loaded["traceEvents"]}
    assert pids >= {0, 1}
    spans = [e for e in loaded["traceEvents"] if e.get("cat") == "request"]
    assert {s["args"]["rid"] for s in spans} >= {r.rid for r in reqs()}
    t0 = min(e.t_ns for e in tr.events)
    for s in spans:                                     # Perfetto invariants
        assert s["ph"] == "X" and s["dur"] >= 0 and s["ts"] >= 0
        mine = [e for e in tr.events if e.rid == s["args"]["rid"]
                and e.replica == s["pid"]]
        assert s["ts"] == (min(e.t_ns for e in mine) - t0) / 1e3
        assert s["dur"] == (max(e.t_ns + e.dur_ns for e in mine)
                            - min(e.t_ns for e in mine)) / 1e3


def test_prefix_trie_events_ride_the_trace():
    """prefix_adopt on the request lane + trie detail events, with the
    warm streams still bit-identical to cold under tracing."""
    from test_prefix_caching import _shared_reqs
    _, cold = make_engine(n_slots=3, max_len=64, prefill_chunk=8)
    cfg, warm = make_engine(n_slots=3, max_len=64, prefill_chunk=8,
                            prefix_cache=True)
    want = cold.run(_shared_reqs(cfg.vocab_size))["tokens"]
    tr = Tracer()
    warm.tracer = tr
    try:
        rep = warm.run(_shared_reqs(cfg.vocab_size))
    finally:
        warm.tracer = None
    assert rep["tokens"] == want
    adopts = tr.by_name("prefix_adopt")
    assert len(adopts) == 2 and all(e.attrs["tokens_reused"] == 16
                                    for e in adopts)
    assert len(tr.by_name("prefix_hit")) == 2
    assert tr.by_name("prefix_insert")                  # boundary snapshots


def test_shed_events_from_overload():
    cfg, eng = _obs_engine("attn-tiny")
    tr = Tracer()
    eng.tracer = tr
    hog = Request(0, (3, 1), max_new_tokens=10, arrival=0,
                  slo=SLOParams(priority=PriorityClass.BATCH))
    doomed = Request(1, (2, 2), max_new_tokens=2, arrival=1,
                     slo=SLOParams(priority=PriorityClass.BEST_EFFORT,
                                   deadline_ticks=1))
    try:
        rep = eng.run([hog, doomed], policy=SLOPolicy(age_ticks=0))
    finally:
        eng.tracer = None
    assert rep["shed_requests"] == 1
    shed = tr.by_name("shed")
    assert len(shed) == 1 and shed[0].rid == 1
    assert shed[0].attrs["deadline"] is not None


def test_spans_nest_and_run_on_the_wall_clock():
    """A span records its start, duration and parent; events inside it
    name it as their parent; the stack unwinds through an exception."""
    tr = Tracer()
    with span(tr, "tick", 5, tick=5):
        with span(tr, "fetch", 5, of="decode"):
            time.sleep(0.002)
        tr.event("commit", 5, rid=1)
    tick, fetch, commit = tr.events
    assert [e.seq for e in tr.events] == [1, 2, 3]     # in order of start
    assert (tick.parent, fetch.parent, commit.parent) == (None, 1, 1)
    assert tick.attrs == {"tick": 5} and fetch.attrs == {"of": "decode"}
    assert fetch.dur_ns >= 2_000_000 and tick.dur_ns >= fetch.dur_ns
    assert tick.t_ns <= fetch.t_ns <= commit.t_ns \
        <= tick.t_ns + tick.dur_ns
    assert commit.dur_ns == 0                           # a point event
    with pytest.raises(RuntimeError):
        with span(tr, "stats", 6):
            raise RuntimeError("in the body")
    tr.event("admit", 7)
    assert tr.by_name("stats")[0].parent is None
    assert tr.events[-1].parent is None                 # stack unwound
    with span(None, "tick", 8, tick=8):                 # no tracer: no-op
        pass
    assert len(tr) == 5
    capped = Tracer(max_events=1)
    with span(capped, "tick", 0):
        with span(capped, "fetch", 0):
            capped.event("commit", 0)
    assert len(capped) == 1 and capped.dropped == 2
    assert capped.events[0].dur_ns > 0


def _host_spans(trace_dir) -> list:
    """The ``repro.serve.*`` events of a profile on the host, as
    ``(name, start_ns, end_ns, stats)``."""
    import glob
    import warnings

    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(str(trace_dir), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    out = []
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in ProfileData.from_file(path).planes:
            for line in plane.lines:
                out += [(e.name[len(PROFILER_PREFIX):], e.start_ns,
                         e.start_ns + e.duration_ns, dict(e.stats))
                        for e in line.events
                        if e.name.startswith(PROFILER_PREFIX)]
    return sorted(out, key=lambda e: e[1])


def test_tick_phases_reach_the_profiler(tmp_path):
    """With a JAX profiler running and no tracer attached, every tick
    leaves a ``repro.serve.tick`` span with its phases nested inside, and
    each prefill a span carrying the prompt's bucket and real tokens."""
    import jax
    cfg, eng = make_engine(n_slots=2, max_len=32)
    reqs = make_requests(3, cfg, seed=5, plen=(3, 12))
    jax.profiler.start_trace(str(tmp_path))
    try:
        rep = eng.run(reqs)
    finally:
        jax.profiler.stop_trace()
    spans = _host_spans(tmp_path)
    ticks = [s for s in spans if s[0] == "tick"]
    assert [s[3]["tick"] for s in ticks] == list(range(rep["ticks"]))

    def inside(name, tick):
        return [s for s in spans if s[0] == name
                and tick[1] <= s[1] and s[2] <= tick[2]]
    decoded = [t for t in ticks if inside("decode", t)]
    assert decoded
    for t in decoded:
        assert len(inside("decode", t)) == 1
        assert [s[3]["of"] for s in inside("fetch", t)][-1] == "decode"
        assert len(inside("stats", t)) == 1 and inside("schedule", t)
    stats = [s[3] for s in spans if s[0] == "stats"]
    assert sum(s["new_tokens"] for s in stats) == \
        sum(r.max_new_tokens for r in reqs)
    assert sum(s["prompt_tokens"] for s in stats) == \
        sum(len(r.prompt) for r in reqs)
    chunks = {s[3]["rid"]: s[3] for s in spans if s[0] == "prefill_chunk"}
    assert sorted(chunks) == [r.rid for r in reqs]
    for r in reqs:
        assert chunks[r.rid]["tokens"] == len(r.prompt)
        assert chunks[r.rid]["bucket"] == eng._bucket(len(r.prompt))
        assert chunks[r.rid]["final"]
    assert sorted(s[3]["rid"] for s in spans if s[0] == "submit") == \
        [r.rid for r in reqs]


def _programs(step) -> dict:
    """The jitted programs behind one of the engine's step functions, by
    the name they lower under."""
    out = {}
    for cell in step.__closure__:
        v = cell.cell_contents
        for f in (v.values() if isinstance(v, dict) else [v]):
            if hasattr(f, "lower"):
                out[f.__name__] = f
    return out


def test_step_programs_carry_their_role_names():
    """The device trace finds each program by name: the decode and prefill
    steps lower to ``jit_decode_step`` and ``jit_prefill_step`` (with
    their sampled twins), the slot reset and row copy to theirs."""
    import jax.numpy as jnp
    cfg, eng = make_engine(n_slots=2, max_len=32, prefix_cache=True)
    session = eng.start()
    caches, slot = session.caches, jnp.asarray(0, jnp.int32)
    dec = _programs(eng._decode)
    assert set(dec) == {"decode_step", "decode_step_sampled"}
    text = dec["decode_step"].lower(
        eng.params, {"tokens": jnp.zeros((2, 1), jnp.int32)}, caches,
        jnp.ones((2,), bool)).as_text()
    assert text.startswith("module @jit_decode_step ")
    pre = _programs(eng._prefill)
    assert set(pre) == {"prefill_step", "prefill_step_sampled"}
    text = pre["prefill_step"].lower(
        eng.params, jnp.zeros((1, 8), jnp.int32), caches, slot,
        jnp.asarray(3, jnp.int32)).as_text()
    assert text.startswith("module @jit_prefill_step ")
    assert eng._reset.lower(caches, jnp.zeros((2,), bool)).as_text() \
        .startswith("module @jit_reset_slots ")
    assert eng._extract.lower(caches, slot).as_text() \
        .startswith("module @jit_extract_row ")
    assert set(_programs(eng._get_verify(2))) == {"verify_step",
                                                  "verify_step_sampled"}


def test_admit_carries_the_wait_since_submit():
    """``submit`` stamps the request on the wall clock; its ``admit``
    event carries the milliseconds since, re-admissions included."""
    cfg, eng = _obs_engine("attn-tiny")
    tr = Tracer()
    eng.tracer = tr
    try:
        reqs = _obs_matrix_reqs(False)
        eng.run(reqs, policy=SLOPolicy(age_ticks=100))
    finally:
        eng.tracer = None
    submitted = {e.rid: e for e in tr.by_name("submit")}
    assert set(submitted) == {r.rid for r in reqs}
    assert all(r.t_submit_ns is not None for r in reqs)
    admits = tr.by_name("admit")
    assert len(admits) > len(reqs)                      # a re-admission
    for e in admits:
        s = submitted[e.rid]
        assert s.t_ns <= reqs[e.rid].t_submit_ns <= s.t_ns + s.dur_ns
        assert 0 < e.attrs["wait_ms"] * 1e6 <= e.t_ns - s.t_ns


# ==========================================================================
# probes on a real mesh: >=1 sample per reduction (8-device subprocess)
# ==========================================================================

@pytest.mark.slow
def test_stats_reducer_probe_samples_and_row_guard():
    """On an 8-way 'data' mesh: the reducer under an active probe lands
    one timed sample per reduction call (plus the collective layer's
    trace-time note, once per compilation), wrong row counts raise, and
    probed results stay bit-identical to unprobed ones."""
    script = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys
        sys.path.insert(0, {ROOT + '/src'!r})
        import numpy as np
        from repro.launch.mesh import make_mesh
        from repro.obs import probing
        from repro.serving import STATS_FIELDS, make_stats_reducer

        mesh = make_mesh((8,), ("data",))
        reduce = make_stats_reducer(mesh)
        k = len(STATS_FIELDS) + 4        # stats row + a histogram tail
        rows = np.arange(8 * k, dtype=np.float32).reshape(8, k)
        want = reduce(rows)              # compile once, unprobed
        with probing() as pr:
            got = reduce(rows)
            got2 = reduce(rows[:1])      # broadcast single-row path
            try:
                reduce(rows[:3])
            except ValueError as e:
                print("GUARD:", e)
            # a FRESH reducer compiles under the probe: the collective
            # layer's trace-time note fires once per compilation
            got3 = make_stats_reducer(mesh)(rows)
        assert np.array_equal(np.asarray(want), np.asarray(got))
        assert np.array_equal(np.asarray(got2), 8 * rows[0])
        assert np.array_equal(np.asarray(got), np.asarray(got3))
        timed = pr.timed()
        assert len(timed) == 3, timed    # one per executed reduction
        s = timed[0]
        assert s.p == 8 and s.nbytes == k * 4 and s.num_blocks == 1
        assert s.wall_s > 0 and s.axis == "data"
        assert all(t.predicted_s is not None or t.method == "psum"
                   for t in timed)
        traced = pr.traced()
        assert len(traced) >= 1, traced
        assert traced[0].p == 8 and traced[0].wall_s == 0.0
        print("METHODS:", sorted({{t.method for t in timed}}),
              "TRACED:", len(traced))
    """)
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=560)
    assert r.returncode == 0, f"\nOUT:{r.stdout[-2000:]}\nERR:{r.stderr[-3000:]}"
    assert "do not match the 8-way 'data' replica axis" in r.stdout
    assert "METHODS:" in r.stdout


# ==========================================================================
# bench artifact provenance: schema stamp + mixed-provenance merge refusal
# ==========================================================================

def _bench_mods():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmarks import bench_serving
    from benchmarks import run as bench_run
    return bench_serving, bench_run


def test_bench_row_merge_enforces_provenance():
    bs, _ = _bench_mods()

    def row(name, sv=bs.ROW_SCHEMA_VERSION, obs=False):
        return {"suite": "serving", "name": name, "value": "1",
                "derived": "", "schema_version": sv, "obs": obs}

    fresh = [row("a"), row("b")]
    prior = [
        row("a", sv=1),       # name collision: fresh wins regardless
        row("c"),             # same provenance: survives
        row("d", sv=1),       # stale schema: dropped
        {"suite": "serving", "name": "e", "value": "1", "derived": ""},
        row("f", obs=True),   # probe-instrumented wall clock: dropped
    ]
    merged, rejected = bs.merge_rows(prior, fresh, obs_on=False)
    assert [r["name"] for r in merged] == ["c", "a", "b"]
    assert rejected == 3      # d, unstamped e, and obs-tainted f
    # symmetric: an obs run refuses clean prior rows
    merged2, rejected2 = bs.merge_rows([row("c")], [row("g", obs=True)],
                                       obs_on=True)
    assert [r["name"] for r in merged2] == ["g"] and rejected2 == 1


def test_bench_runner_stamps_serving_rows(tmp_path, monkeypatch):
    bs, bench_run = _bench_mods()

    def fake_suite(csv_out):
        csv_out("serving_fake_metric", "1.0", "stub")

    monkeypatch.setitem(bench_run.SUITES, "serving", fake_suite)
    art = tmp_path / "b.json"
    assert bench_run.main(["--only", "serving",
                           "--artifact", str(art)]) == 0
    [r] = json.loads(art.read_text())["rows"]
    # the harness path stamps the same provenance as bench_serving's own
    # entry point, so single-scenario refreshes can merge into its artifact
    assert r["schema_version"] == bs.ROW_SCHEMA_VERSION
    assert r["obs"] is False
