"""The reduction from a trace to device numbers: on small hand-made event
lists, and on a trace recorded on a TPU v5e (``tests/data``)."""

import os

import pytest

from chipbench.tests import small  # noqa: F401
from chipbench import trace as tr

E = tr.Event
FIXTURE = os.path.join(os.path.dirname(__file__), "data", "small.xplane.pb")


def _trace():
    ops = {0: [E("fusion.1", 10, 20), E("fusion.2", 15, 30),
               E("copy.3", 40, 50), E("while.4", 60, 95),
               E("fusion.5", 70, 80)],
           1: [E("fusion.1", 0, 100)]}
    mods = {0: [E("jit_a(1)", 10, 30), E("jit_b(2)", 40, 50),
                E("jit_a(1)", 60, 95)]}
    host = [E("chipbench.window", 5, 105), E("chipbench.tick", 5, 52),
            E("chipbench.idle", 52, 58)]
    return tr.Trace(mods, ops, host, (5, 105))


def test_busy_and_idle():
    t = _trace()
    assert tr.merge(t.ops[0]) == [(10, 30), (40, 50), (60, 95)]
    assert tr.busy_ns(t.ops[0], t.window) == 65
    # device 1 runs the whole window: busy is averaged over the devices
    assert tr.busy_s(t) == pytest.approx((65 + 95) / 2 * 1e-9)
    assert tr.idle_gaps(t.ops[0], t.window) == [(5, 10), (30, 40),
                                                 (50, 60), (95, 105)]


def test_gaps_are_named_by_the_innermost_host_span():
    t = _trace()
    assert tr.label_gap((50, 60), t.host) == "idle"
    assert tr.label_gap((30, 40), t.host) == "tick"
    assert tr.label_gap((95, 105), t.host) == "window"
    assert tr.label_gap((200, 210), t.host) == "no span"
    gaps = tr.longest_gaps(t, 0, n=2)
    assert [g[0] for g in gaps] == ["tick", "idle"]
    assert gaps[0][1] == pytest.approx(10e-9)


def test_op_time_by_family():
    t = _trace()
    top = dict(tr.top_ops(t, 0))
    assert top["while"] == pytest.approx(35e-9)
    assert top["fusion"] == pytest.approx(35e-9)
    assert top["copy"] == pytest.approx(10e-9)
    assert tr.op_family("%fusion.12 = bf16[4,8]{1,0} fusion(%a.1)") == \
        "fusion bf16[4,8]{1,0}"
    assert tr.op_family("fusion.12") == "fusion"


def test_clip_and_program_key():
    t = _trace()
    clipped = tr.clip(t.ops[1], (20, 30))
    assert [(e.start, e.end) for e in clipped] == [(20, 30)]
    assert tr.program_key(E("jit_a(1)", 0, 1)) == "jit_a(1)"
    assert tr.program_key(E("x", 0, 1, (("program_id", 7),))) == 7


def test_a_trace_recorded_on_the_chip():
    """Three ticks, each a bf16 matmul and the ``combine3`` kernel, with
    the benchmark's host spans around them (TPU v5e, jax 0.9)."""
    t = tr.load(FIXTURE)
    assert sorted(t.ops) == [0]
    assert 0 < tr.busy_s(t) < t.window_s
    ticks = [e for e in t.host if e.name == "chipbench.tick"]
    assert len(ticks) == 3
    mods = t.modules[0]
    names = {tr.program_key(e) for e in mods}
    assert len(names) == 2 and len(mods) == 6
    kernel = [e for e in t.ops[0] if "tpu_custom_call" in e.name]
    assert len(kernel) == 3
    # three 4 MiB reads and one write: near the 819 GB/s of HBM
    share = 4 * 4 * 2 ** 20 / 819e9 / (kernel[0].dur * 1e-9)
    assert 0.5 < share < 1.0
    # a matmul program then the kernel's, three times over
    keys = [tr.program_key(e) for e in sorted(mods, key=lambda e: e.start)]
    assert keys[0::2] == [keys[0]] * 3 and keys[1::2] == [keys[1]] * 3
    assert all(g[0] in ("tick", "idle", "window")
               for g in tr.longest_gaps(t, 0))


class _Tracer:
    """Host seconds map to trace nanoseconds one to one from 0."""

    def to_trace_ns(self, trace, t):
        return t * 1e9


def test_programs_go_to_the_tick_they_end_in():
    """The device's clock may run a millisecond behind the host's: a
    program dispatched at a tick's start can seem to start before it."""
    from chipbench import harness, reduce
    from chipbench.drivers.serving import Tick
    ms = 1e6
    ticks = [Tick(0.000, 0.030, 0, 0, (5,)), Tick(0.031, 0.080, 1, 40, (6,)),
             Tick(0.081, 0.110, 0, 0, (7,))]
    mods = {0: [E("decode", -1 * ms, 28 * ms),
                E("prefill", 30 * ms, 50 * ms),
                E("decode", 50.5 * ms, 78 * ms),
                E("decode", 80 * ms, 108 * ms)]}
    run = harness.Run(cell={}, config={}, workload={}, seed=0, seconds=1,
                      traced=True, t_start=0.0)
    run.trace = tr.Trace(mods, {0: []}, [], (-2 * ms, 200 * ms))
    run.data.update(ticks=ticks, tracer=_Tracer())
    pairs = reduce.tick_programs(run)
    assert [[e.name for e in evs] for _, evs in pairs] == \
        [["decode"], ["prefill", "decode"], ["decode"]]
    assert reduce.classify(pairs) == ("decode", {"prefill"})
    assert [e.start for _, e in reduce.decode_steps(run)] == \
        [-1 * ms, 50.5 * ms, 80 * ms]
    secs, toks = reduce.prefill_work(run)
    assert toks == 40 and secs == pytest.approx(0.020)
