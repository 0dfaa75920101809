"""Readers that find the serving programs by the names the program gives
them (``jit_decode_step``, ``jit_prefill_step``), on hand-made traces; and
the recorded chip trace read as before."""

import os

import pytest

from chipbench.tests import small
from chipbench import harness
from chipbench import trace as tr
from chipbench.drivers.serving import Tick

E = tr.Event
MS = 1e6
FIXTURE = os.path.join(os.path.dirname(__file__), "data", "small.xplane.pb")


def _reader(name):
    return harness.load_module(os.path.join(
        small.ROOT, "chipbench", "metrics", name + ".py")).read


class _Tracer:
    """Host seconds map to trace nanoseconds one to one from 0."""

    def to_trace_ns(self, trace, t):
        return t * 1e9


def _run(mods, window=(-2 * MS, 200 * MS)):
    """Three ticks: decode; admit 40 prompt tokens and decode; decode."""
    ticks = [Tick(0.000, 0.030, 0, 0, (5,)),
             Tick(0.031, 0.080, 1, 40, (6,)),
             Tick(0.081, 0.110, 0, 0, (7,))]
    run = harness.Run(cell={}, config={}, workload={}, seed=0, seconds=1,
                      traced=True, t_start=0.0)
    run.trace = tr.Trace({0: mods}, {0: []}, [], window)
    run.data.update(records=[], ticks=ticks, tracer=_Tracer())
    return run


def _named():
    return [E("jit_decode_step(7)", -1 * MS, 28 * MS),
            E("jit_prefill_step(9)", 30 * MS, 42 * MS),
            E("jit_reset_slots(3)", 42 * MS, 43 * MS),
            E("jit_prefill_step_sampled(4)", 43 * MS, 51 * MS),
            E("jit_decode_step(7)", 51 * MS, 78 * MS),
            E("jit_decode_step_sampled(8)", 80 * MS, 110 * MS)]


def test_decode_program_by_name():
    read = _reader("decode_program_ms")
    # every decode program wholly inside the window, sampled twin included
    assert read(_run(_named())) == pytest.approx((29 + 27 + 30) / 3)
    # one that starts before the window is left out
    assert read(_run(_named(), window=(0, 200 * MS))) == \
        pytest.approx((27 + 30) / 2)


def test_prefill_program_by_name():
    read = _reader("prefill_program_ms_per_ktok")
    # 12 + 8 ms of prefill programs for the 40 tokens their tick admitted
    assert read(_run(_named())) == pytest.approx(20 / 0.040)


def test_unnamed_programs_read_nothing():
    """A program that does not name its steps (the parent of these
    readers): the readers find nothing and return None, not a guess."""
    unnamed = [E("jit_greedy_body(7)", -1 * MS, 28 * MS),
               E("jit__unknown(9)", 30 * MS, 50 * MS),
               E("jit_greedy_body(7)", 51 * MS, 78 * MS)]
    run = _run(unnamed)
    assert _reader("decode_program_ms")(run) is None
    assert _reader("prefill_program_ms_per_ktok")(run) is None
    run.trace = None
    assert _reader("decode_program_ms")(run) is None


def test_the_recorded_chip_trace_reads_as_before():
    """The v5e trace holds no program spans: the window, busy time, top
    ops and idle gaps come out exactly as they always have."""
    t = tr.load(FIXTURE)
    assert t.window == (41919895.0, 55346913.0)
    assert tr.busy_s(t) == pytest.approx(0.000280285, rel=1e-12)
    top = tr.top_ops(t, 0, n=2)
    assert [k for k, _ in top] == ["convolution_reduce_fusion bf16[]{:T(256)}",
                                   "_lambda_ f32[8192,128]{1,0:T(8,128)}"]
    assert [v for _, v in top] == pytest.approx([180.176e-6, 76.921e-6],
                                                rel=1e-9)
    gaps = tr.longest_gaps(t, 0, n=3)
    assert [g[0] for g in gaps] == ["idle"] * 3
    assert [g[1] for g in gaps] == pytest.approx(
        [4.364506e-3, 3.63042e-3, 3.583595e-3], rel=1e-9)
