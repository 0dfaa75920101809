"""Chaos-hardened serving: deterministic fault injection, journaled
exact-resume failover, flap-tolerant heartbeats, replica rejoin, poisoned
-logits quarantine, and corrupted-autotune-cache degradation.

Control-plane tests are host-only and fast; engine-level tests run the
tiny inline config through the real jitted slot steps (same fixtures as
tests/test_serving.py); the remesh-telemetry test spawns an 8-virtual-
device subprocess (slow).
"""

import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

from repro.core import cost_model as cm
from repro.runtime.chaos import (Fault, FaultInjector, FaultPlan,
                                 corrupt_autotune_cache, poison_slot)
from repro.runtime.fault_tolerance import (HeartbeatMonitor, HostFailure,
                                           StragglerTuner, run_with_restarts)
from repro.serving import (FleetRunner, PoisonedLogits, ReplicaFleet,
                           Request, SamplingParams, SlotScheduler)

from test_serving import make_engine, make_requests, tiny_cfg

ROOT = str(Path(__file__).resolve().parent.parent)


# ==========================================================================
# fault plans and injection (host-only)
# ==========================================================================

def test_fault_plan_seeded_is_deterministic_and_sorted():
    a = FaultPlan.seeded(42, n_replicas=4, horizon=50, n_faults=6)
    b = FaultPlan.seeded(42, n_replicas=4, horizon=50, n_faults=6)
    assert a.faults == b.faults and len(a) == 6
    assert list(a) == sorted(a)
    assert a.faults != FaultPlan.seeded(43, n_replicas=4, horizon=50,
                                        n_faults=6).faults


def test_fault_plan_seeded_never_kills_replica_zero():
    for seed in range(30):
        plan = FaultPlan.seeded(seed, n_replicas=3, horizon=40, n_faults=8)
        assert not any(f.replica == 0 and f.kind in ("kill", "flap")
                       for f in plan), plan.faults


def test_fault_validation():
    with pytest.raises(ValueError):
        Fault(0, "meteor")
    with pytest.raises(ValueError):
        Fault(-1)
    with pytest.raises(ValueError):
        Fault(0, "flap", duration=0)
    with pytest.raises(ValueError):
        Fault(0, "straggle", duration=4, factor=0.5)
    with pytest.raises(TypeError):
        FaultPlan(("not a fault",))


def test_injector_is_pure_function_of_tick():
    plan = FaultPlan((Fault(5, "kill", replica=2),
                      Fault(3, "flap", replica=1, duration=4),
                      Fault(2, "straggle", replica=0, duration=6,
                            factor=2.0),
                      Fault(7, "poison", replica=1)))
    inj = FaultInjector(plan)
    # kill: silent from its tick, forever
    assert not inj.silenced(4, 2) and inj.silenced(5, 2)
    assert inj.silenced(1000, 2)
    # flap: silent only inside the window
    assert not inj.silenced(2, 1) and inj.silenced(3, 1)
    assert inj.silenced(6, 1) and not inj.silenced(7, 1)
    # straggle: every round(factor)-th tick runs, the rest skip; beats
    # continue throughout (silenced stays False)
    skips = [inj.skips_tick(t, 0) for t in range(2, 8)]
    assert skips == [False, True, False, True, False, True]
    assert not any(inj.silenced(t, 0) for t in range(2, 8))
    assert inj.straggle_factor(4, 0) == 2.0
    assert inj.straggle_factor(9, 0) == 1.0
    # poison: exactly its tick
    assert inj.poisons(7, 1) and not inj.poisons(8, 1)
    # queries are order-independent: ask again, same answers
    assert inj.silenced(5, 2) and inj.poisons(7, 1)


# ==========================================================================
# heartbeat state machine: SUSPECT -> DEAD -> rejoin probation
# ==========================================================================

def test_monitor_suspect_window_tolerates_short_flaps():
    t = [0.0]
    mon = HeartbeatMonitor(2, 2.0, clock=lambda: t[0], misses=3)
    t[0] = 3.0                      # one deadline missed
    assert mon.suspect_hosts() == [0, 1] and mon.dead_hosts() == []
    mon.beat(0)
    mon.beat(1)                     # flap over: back to alive
    t[0] = 4.0
    assert mon.suspect_hosts() == [] and mon.dead_hosts() == []
    t[0] = 11.0                     # > misses * timeout since last beat
    assert mon.dead_hosts() == [0, 1]


def test_host_failure_reports_full_dead_set():
    t = [0.0]
    mon = HeartbeatMonitor(3, 1.0, clock=lambda: t[0])
    t[0] = 5.0
    mon.beat(1)
    with pytest.raises(HostFailure) as ei:
        mon.check()
    assert ei.value.host == 0                 # legacy single-host field
    assert ei.value.hosts == (0, 2)           # the full set, same poll
    assert "0, 2" in str(ei.value)


def test_monitor_rejoin_probation_and_backoff_doubling():
    t = [0.0]
    mon = HeartbeatMonitor(2, 1.0, clock=lambda: t[0],
                           rejoin_backoff_s=4.0, rejoin_cap_s=100.0)
    mon.drop(1)
    assert mon.rejoin_backoff(1) == 4.0
    assert mon.rejoinable() == []             # not beating yet
    t[0] = 10.0
    mon.beat(0)
    mon.beat(1)                               # probation starts
    t[0] = 12.0
    mon.beat(0)
    mon.beat(1)
    assert mon.rejoinable() == []             # 2s < 4s backoff
    t[0] = 14.0
    mon.beat(0)
    mon.beat(1)
    assert mon.rejoinable() == [1]
    mon.readmit(1)
    assert mon.dead_hosts() == []
    # second drop doubles the probation
    mon.drop(1)
    assert mon.rejoin_backoff(1) == 8.0
    with pytest.raises(ValueError):
        mon.readmit(0)                        # never dropped


def test_monitor_flapping_during_probation_restarts_it():
    t = [0.0]
    mon = HeartbeatMonitor(2, 1.0, clock=lambda: t[0], rejoin_backoff_s=3.0)
    mon.drop(1)
    t[0] = 5.0
    mon.beat(1)                               # probation starts at 5
    t[0] = 7.0                                # beats went stale (> timeout)
    assert mon.rejoinable() == []             # probation reset
    mon.beat(1)                               # probation restarts at 7
    t[0] = 9.0
    mon.beat(1)
    assert mon.rejoinable() == []             # only 2s of steady beats
    t[0] = 10.0
    mon.beat(1)
    assert mon.rejoinable() == [1]


# ==========================================================================
# straggler tuner recovery + restart backoff
# ==========================================================================

def test_straggler_tuner_recovers_after_straggler_clears():
    tuner = StragglerTuner(16, 1e8, cm.TPU_V5E, threshold=1.5, window=4)
    opt = tuner.num_blocks
    pred = cm.dptree_time(16, 1e8, opt, cm.TPU_V5E)
    for _ in range(4):                        # 5x slowdown: ratchet down
        tuner.observe(5.0 * pred)
    assert tuner.num_blocks < opt
    shrunk = tuner.num_blocks
    pred2 = cm.dptree_time(16, 1e8, shrunk, cm.TPU_V5E)
    for _ in range(4):                        # healthy again: re-solve back
        tuner.observe(1.0 * pred2)
    assert tuner.num_blocks == opt, \
        f"ratchet must undo on recovery ({shrunk} -> {tuner.num_blocks})"


def test_straggler_tuner_stays_shrunk_while_straggling():
    tuner = StragglerTuner(16, 1e8, cm.TPU_V5E, threshold=1.5, window=4)
    opt = tuner.num_blocks
    pred = cm.dptree_time(16, 1e8, opt, cm.TPU_V5E)
    for _ in range(4):
        tuner.observe(5.0 * pred)
    shrunk = tuner.num_blocks
    pred2 = cm.dptree_time(16, 1e8, shrunk, cm.TPU_V5E)
    for _ in range(4):                        # still ~2x over prediction:
        tuner.observe(2.0 * pred2)            # re-solve for 2x alpha, but
    assert shrunk <= tuner.num_blocks < opt   # do NOT snap back to opt


def test_run_with_restarts_backoff_is_capped_and_deterministic():
    def flaky(max_fail):
        state = {"n": 0}

        def loop(attempt):
            if state["n"] < max_fail:
                state["n"] += 1
                raise HostFailure(0)
            return {"ok": True}
        return loop

    slept_a, slept_b = [], []
    out = run_with_restarts(flaky(3), max_restarts=3, backoff_s=1.0,
                            backoff_cap_s=3.0, jitter=0.25, seed=5,
                            sleep=slept_a.append)
    assert out["ok"] and out["restarts"] == 3
    run_with_restarts(flaky(3), max_restarts=3, backoff_s=1.0,
                      backoff_cap_s=3.0, jitter=0.25, seed=5,
                      sleep=slept_b.append)
    assert slept_a == slept_b                  # seeded jitter replays
    bases = [1.0, 2.0, 3.0]                    # 1, 2, 4 capped at 3
    for got, base in zip(slept_a, bases):
        assert base <= got < base * 1.25
    # zero backoff (the default) never sleeps
    sleeps = []
    run_with_restarts(flaky(2), max_restarts=2, sleep=sleeps.append)
    assert sleeps == []


# ==========================================================================
# fleet control plane (host-only)
# ==========================================================================

def test_fleet_complete_is_tolerant_of_stale_notifications():
    fleet = ReplicaFleet(2, timeout_s=10.0, clock=lambda: 0.0)
    req = Request(0, (1, 2), 4)
    r = fleet.assign(req)
    assert fleet.complete(r, req) is True
    assert fleet.complete(r, req) is False        # already completed
    assert fleet.complete(1 - r, req) is False    # never placed there
    assert fleet.complete(99, req) is False       # no such replica


def test_fleet_rejoin_grows_alive_set_and_replans():
    t = [0.0]
    fleet = ReplicaFleet(3, timeout_s=1.0, clock=lambda: t[0],
                         rejoin_backoff_s=2.0)
    reqs = [Request(i, (1, 2), 4, arrival=i) for i in range(4)]
    for r in reqs:
        fleet.assign(r)
    sched = SlotScheduler(2)
    t[0] = 1.5
    fleet.beat(0)
    fleet.beat(1)
    t[0] = 2.0                                   # replica 2 dies
    plan = fleet.poll(sched)
    assert plan.dead == (2,) and plan.survivors == (0, 1)
    assert plan.elastic.new_p == 2
    # 2 resumes beating; after steady probation it rejoins and the
    # collective re-plans to GROW over the full set again
    for tick in (3.0, 4.0, 5.0, 6.0):
        t[0] = tick
        for h in (0, 1, 2):
            fleet.beat(h)
    grow = fleet.poll(sched)
    assert grow is not None and grow.dead == ()
    assert grow.rejoined == (2,) and grow.survivors == (0, 1, 2)
    assert grow.elastic.new_p == 3
    assert fleet.poll(sched) is None              # membership stable now


def test_fleet_quarantine_is_permanent():
    t = [0.0]
    fleet = ReplicaFleet(2, timeout_s=1.0, clock=lambda: t[0])
    req = Request(0, (1, 2), 4)
    req.tokens = [5, 6]
    fleet._placement[1].append(req)
    sched = SlotScheduler(2)
    plan = fleet.quarantine(1, sched)
    assert plan.quarantined == (1,) and plan.survivors == (0,)
    assert plan.requeued == (0,)
    assert req.tokens == [5, 6]                   # journal intact
    for tick in (1.0, 2.0, 3.0):                  # beats resume...
        t[0] = tick
        fleet.beat(0)
        fleet.beat(1)
    assert fleet.poll(sched) is None              # ...but never rejoins
    assert fleet.quarantined == (1,)


def test_requeue_front_exact_keeps_journals_lossy_drops_them():
    sched = SlotScheduler(2)
    a = Request(0, (1, 2), 8, arrival=0)
    a.tokens, a.t_first = [7, 9], 3
    b = Request(1, (3,), 8, arrival=1)
    sched.requeue_front([b, a])                   # exact (default)
    assert [r.rid for r in sched._queue] == [0, 1]
    assert a.tokens == [7, 9] and a.t_first == 3
    sched2 = SlotScheduler(2)
    a.state = type(a.state).QUEUED
    sched2.requeue_front([a], exact=False)        # legacy lossy restart
    assert a.tokens == [] and a.t_first is None


def test_steal_queued_preserves_fifo():
    sched = SlotScheduler(1)
    for i in range(5):
        sched.submit(Request(i, (1,), 2, arrival=i))
    stolen = sched.steal_queued(2)
    assert [r.rid for r in stolen] == [3, 4]      # from the back, in order
    assert [r.rid for r in sched._queue] == [0, 1, 2]
    assert sched.steal_queued(99) and not sched.pending


# ==========================================================================
# corrupted autotune cache: degrade to the cost model, never raise
# ==========================================================================

def test_corrupt_autotune_entry_degrades_to_miss(tmp_path):
    from repro.core import autotune as at
    from repro.core.collectives import CollectiveConfig, plan
    path = str(tmp_path / "autotune.json")
    try:
        at.set_cache_path(path)
        cfg = CollectiveConfig(method="auto")
        model = plan(cfg, 8, 4096, "float32")         # empty cache
        at.get_cache().put(8, 4096, "float32", cfg.comm_model.name,
                           at.TuneResult("sptree", 4, 1e-6))
        at.get_cache().save()
        pl = plan(cfg, 8, 4096, "float32")
        assert (pl.algorithm, pl.num_blocks) == ("sptree", 4)  # measured
        victim = corrupt_autotune_cache(path, seed=0)
        assert victim.startswith("p=8/nbytes=4096/dtype=float32/")
        at.reset_cache()                              # drop the stale handle
        at.set_cache_path(path)
        assert at.lookup(8, 4096, "float32", cfg.comm_model.name) is None
        # the corrupted entry degrades to the analytic cost-model switch
        assert plan(cfg, 8, 4096, "float32") == model  # model, not cache
    finally:
        at.set_cache_path(None)


def test_corrupt_autotune_on_missing_file_creates_malformed(tmp_path):
    from repro.core import autotune as at
    path = str(tmp_path / "none.json")
    corrupt_autotune_cache(path)
    cache = at.AutotuneCache(path)
    cache.load()                                  # malformed entry present
    assert len(cache) >= 1
    # the malformed key can never collide with a real lookup key, and a
    # direct probe of any shape degrades to a miss rather than raising
    assert cache.get(0, 0, "?", "?") is None


# ==========================================================================
# engine-level: exact resume, poison guard (tiny cfg, real jitted steps)
# ==========================================================================

def _resume_requests(cfg, base_tokens, j, sampled):
    reqs = make_requests(5, cfg, gap=1, seed=3, max_new=(4, 9))
    for i, r in enumerate(reqs):
        if sampled and i % 2:
            r.sampling = SamplingParams(seed=11 + i, temperature=0.9,
                                        top_k=20)
        r.tokens = list(base_tokens.get(r.rid, ())[:j])
    return reqs


@pytest.mark.parametrize("sampled", [False, True])
def test_exact_resume_is_bit_identical(sampled):
    """A request re-admitted with j committed tokens finishes with the
    exact stream of the undisturbed run — greedy and sampled — because
    re-prefill rebuilds the cache over prompt+journal and the sampler
    cursor is the request's own token index (fold_in contract)."""
    cfg, eng = make_engine()
    base = _resume_requests(cfg, {}, 0, sampled)
    for r in base:
        r.tokens = []
    want = eng.run(base)["tokens"]
    for j in (1, 2, 3):
        reqs = _resume_requests(cfg, want, j, sampled)
        got = eng.run(reqs)["tokens"]
        assert got == want, (j, sampled)
        assert sum(r.resumed_tokens for r in reqs) > 0


def test_exact_resume_ssm_arch():
    """Recurrent-state (RWKV) slots resume exactly too: the prefill carry
    checkpoint at the true history length is position-exact."""
    from repro.configs.base import get_config
    cfg = get_config("rwkv6_7b", reduced=True)
    cfg, eng = make_engine(cfg=cfg, n_slots=2, max_len=48)
    reqs = make_requests(3, cfg, gap=1, seed=5, max_new=(6, 10))
    want = eng.run(reqs)["tokens"]
    redo = make_requests(3, cfg, gap=1, seed=5, max_new=(6, 10))
    for r in redo:
        r.tokens = list(want[r.rid][:2])
    assert eng.run(redo)["tokens"] == want


def test_resume_discards_prefill_token_in_favor_of_journal():
    """The journal is authoritative: for greedy requests the re-derived
    prefill token EQUALS the journal tail (the invariant that makes the
    discard safe), and the resumed stream never double-commits it."""
    cfg, eng = make_engine(n_slots=1)
    req = make_requests(1, cfg, max_new=(6, 7))[0]
    want = eng.run([req])["tokens"][0]
    redo = make_requests(1, cfg, max_new=(6, 7))[0]
    redo.tokens = list(want[:3])
    got = eng.run([redo])["tokens"][0]
    assert got == want and len(got) == len(want)   # no dup, no gap


def test_poisoned_logits_guard_refuses_to_commit():
    """NaN in a slot's cache must surface as PoisonedLogits BEFORE any of
    the tick's tokens commit — argmax over NaN logits would otherwise
    silently emit a plausible token id."""
    cfg, eng = make_engine(n_slots=2)
    reqs = make_requests(2, cfg, gap=0, seed=9, max_new=(6, 7))
    session = eng.start(reqs)
    session.tick()                                 # admit + first tokens
    lens = {r.rid: len(r.tokens) for r in reqs}
    assert any(lens.values())
    session.caches = poison_slot(session.caches, 0)
    with pytest.raises(PoisonedLogits) as ei:
        for _ in range(4):
            session.tick()
    assert 0 in ei.value.slots
    victim = next(r for r in reqs if r.rid in ei.value.rids)
    assert len(victim.tokens) == lens[victim.rid], \
        "the poisoned tick must not have committed anything"


def test_fleet_runner_chaos_streams_never_diverge():
    """Kill + flap/rejoin + straggle + poison across a 2-replica fleet:
    merged streams stay bit-identical to the undisturbed run."""
    cfg, eng = make_engine(n_slots=2, max_len=64)

    def reqs():
        out = make_requests(8, cfg, gap=1, seed=3, max_new=(8, 16))
        for i, r in enumerate(out):
            if i % 2:
                r.sampling = SamplingParams(seed=11 + i, temperature=0.9,
                                            top_k=20)
        return out

    want = eng.run(reqs())["tokens"]
    scenarios = {
        "kill": FaultPlan((Fault(5, "kill", replica=1),)),
        "flap_rejoin": FaultPlan((Fault(4, "flap", replica=1, duration=8),
                                  Fault(3, "straggle", replica=0,
                                        duration=6, factor=2.0))),
        "poison": FaultPlan((Fault(5, "poison", replica=1),)),
    }
    for name, plan in scenarios.items():
        runner = FleetRunner(eng, 2, plan=plan, timeout_s=2.0,
                             rejoin_backoff_s=1.0)
        rep = runner.run(reqs())
        assert rep["tokens"] == want, name
        assert rep["failovers"] > 0, name
        if name == "flap_rejoin":
            assert rep["rejoins"] == 1 and rep["alive"] == [0, 1]
            assert rep["resumed_tokens"] > 0
            assert rep["recovery_ticks"]
        if name == "poison":
            assert rep["quarantines"] == 1 and rep["quarantined"] == [1]
    # the same seeds replay the same chaos run end-to-end
    again = FleetRunner(eng, 2, plan=scenarios["flap_rejoin"],
                        timeout_s=2.0, rejoin_backoff_s=1.0).run(reqs())
    assert again["tokens"] == want


def test_chaos_run_traced_is_bit_identical():
    """One traced chaos configuration: a tracer attached mid-life (after
    the untraced baseline) observes the kill scenario without perturbing
    a single token, and the failover lands in the trace with both the
    dead replica's engine-lane event and the per-request moves."""
    from repro.obs import Tracer
    cfg, eng = make_engine(n_slots=2, max_len=64)

    def reqs():
        return make_requests(8, cfg, gap=1, seed=3, max_new=(8, 16))

    want = eng.run(reqs())["tokens"]
    tr = Tracer()
    eng.tracer = tr
    try:
        rep = FleetRunner(eng, 2, plan=FaultPlan(
            (Fault(5, "kill", replica=1),)), timeout_s=2.0).run(reqs())
    finally:
        eng.tracer = None
    assert rep["tokens"] == want
    fails = tr.by_name("failover")
    assert any(e.rid is None and e.replica == 1 for e in fails)
    assert sum(1 for e in fails if e.rid is not None) == rep["failovers"]
    assert {e.replica for e in tr.events} == {0, 1}


def test_fleet_runner_counts_ride_the_stats_vector():
    from repro.serving import STATS_FIELDS
    assert STATS_FIELDS[8:11] == ("failovers", "resumed_tokens",
                                  "quarantines")
    cfg, eng = make_engine(n_slots=2, max_len=64)
    reqs = make_requests(6, cfg, gap=1, seed=3, max_new=(6, 12))
    plan = FaultPlan((Fault(4, "kill", replica=1),))
    rep = FleetRunner(eng, 2, plan=plan, timeout_s=2.0).run(reqs)
    assert rep["failovers"] == sum(s.failovers for s in rep["steps"]) > 0
    assert rep["resumed_tokens"] == \
        sum(s.resumed_tokens for s in rep["steps"])
    assert rep["events"] and rep["events"][0]["dead"] == [1]


# ==========================================================================
# telemetry after remesh: shrink 8 -> 5, then grow back (subprocess)
# ==========================================================================

@pytest.mark.slow          # 8-virtual-device subprocess (see pytest.ini)
def test_stats_reduction_exact_across_shrink_and_grow(tmp_path):
    """The b=1 stats reduction re-forms over ANY member count: kill three
    of eight replicas, re-plan via plan_remesh, re-run the reduction over
    the 5-survivor topology — sums exact — then rejoin two and re-run over
    7. Shrink and grow are the same code path (the tree is parametric in
    p), which is exactly what lets serving telemetry keep flowing through
    failover and rejoin."""
    script = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        os.environ["REPRO_AUTOTUNE_CACHE"] = {str(tmp_path / 'at.json')!r}
        import sys
        sys.path.insert(0, {ROOT + '/src'!r})
        import jax
        import numpy as np
        from jax.sharding import Mesh
        from repro.runtime.fault_tolerance import plan_remesh
        from repro.serving import STATS_FIELDS, make_stats_reducer

        k = len(STATS_FIELDS)
        rows = np.arange(1, 8 * k + 1, dtype=np.float32).reshape(8, k)

        def reduce_over(members):
            devs = np.array(jax.devices()[:len(members)]).reshape(-1, 1)
            mesh = Mesh(devs, ("data", "model"))
            red = make_stats_reducer(mesh)
            return red(rows[list(members)])

        full = reduce_over(range(8))
        assert (full == rows.sum(0)).all(), full        # integers: exact

        # three replicas die: re-plan over the survivors, reduce again
        survivors = (0, 2, 3, 5, 6)
        plan = plan_remesh(survivors, float(k * 4))
        assert plan.new_p == 5 and plan.new_num_blocks >= 1
        shrunk = reduce_over(survivors)
        assert (shrunk == rows[list(survivors)].sum(0)).all(), shrunk

        # two rejoin: the SAME call re-plans to grow, reduction exact again
        grown_members = (0, 1, 2, 3, 5, 6, 7)
        grow = plan_remesh(grown_members, float(k * 4))
        assert grow.new_p == 7
        grown = reduce_over(grown_members)
        assert (grown == rows[list(grown_members)].sum(0)).all(), grown
        print("REMESH_OK", plan.new_p, grow.new_p)
    """)
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=560)
    assert r.returncode == 0, \
        f"\nOUT:{r.stdout[-2500:]}\nERR:{r.stderr[-2500:]}"
    assert "REMESH_OK 5 7" in r.stdout
