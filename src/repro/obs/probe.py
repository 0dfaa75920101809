"""Collective timing probes: ``(p, nbytes, dtype, method, num_blocks) -> t``.

The cost model (:mod:`repro.core.cost_model`) predicts collective times
from ``alpha + beta * n`` constants; the ROADMAP's real-hardware pass is
blocked on fitting those constants FROM MEASUREMENT. This module is the
measurement substrate: a process-wide :class:`CollectiveProbe` that the
collective layer reports into whenever one is installed.

Two sample kinds, because jax runs Python twice:

* ``kind="trace"`` — recorded from inside :func:`repro.core.collectives
  .all_reduce` at TRACE time, once per compilation: which algorithm the
  auto switch picked, with how many pipeline blocks, for which
  ``(p, nbytes, dtype)``. No wall time (the Python body never sees
  execution), but it is the ground truth for WHAT ran.
* ``kind="timed"`` — recorded at the HOST boundary, once per execution:
  the stats reducer (:func:`repro.serving.telemetry.make_stats_reducer`)
  wraps its jitted reduction in ``perf_counter`` + ``block_until_ready``
  when a probe is active, and resolves the method/blocks host-side
  through the same ``plan`` the traced code used. Every b=1 stats
  reduction in an instrumented run lands one timed sample.

Samples go into a bounded ring buffer (``collections.deque(maxlen=...)``)
so a probe can stay installed across a long run. ``predicted_s`` carries
the cost model's prediction for the same shape, so
:mod:`repro.obs.fit` can report predicted-vs-measured residuals and fit
fresh ``(alpha, beta)`` estimates from the timed samples.

Zero overhead when off: the collective layer checks one module-level
``None`` before doing anything, and the check happens at trace time (per
compilation), not per executed collective.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses

from repro.core import cost_model as cm


@dataclasses.dataclass(frozen=True)
class ProbeSample:
    """One observed (or trace-time noted) collective.

    ``wall_s`` is 0.0 for ``kind="trace"`` samples (no execution clock at
    trace time). ``levels`` is the hierarchy spec for ``method="hier"``;
    ``axis`` the mesh axis name when known. ``predicted_s`` is the
    alpha-beta model's time for the same ``(p, nbytes, blocks)`` under
    ``model`` (None when the method has no closed form, e.g. psum).
    ``rounds`` is the number of transfer rounds the tree engine's schedule
    runs for a trace-time ``dptree``/``sptree`` sample (None otherwise):
    it says which schedule ran.
    """

    p: int
    nbytes: int
    dtype: str
    method: str
    num_blocks: int
    wall_s: float = 0.0
    predicted_s: float | None = None
    kind: str = "timed"
    levels: tuple | None = None
    axis: str | None = None
    rounds: int | None = None

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        if d["levels"] is not None:
            d["levels"] = list(d["levels"])
        return d


def predict_time(method: str, p: int, nbytes: int, num_blocks: int,
                 model: cm.CommModel = cm.TPU_V5E,
                 levels=None,
                 intra_model: cm.CommModel | None = None) -> float | None:
    """The cost model's prediction for one collective shape, or None for
    methods it has no closed form for (psum — XLA's own schedule)."""
    entry = cm.ALGORITHMS.get(method)
    if entry is None:
        return None
    return entry.time(p, float(max(nbytes, 1)), max(1, int(num_blocks)),
                      model, group_size=levels, intra_model=intra_model)


class CollectiveProbe:
    """Bounded ring buffer of :class:`ProbeSample` records."""

    def __init__(self, capacity: int = 4096,
                 model: cm.CommModel = cm.TPU_V5E):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = int(capacity)
        self.model = model
        self.samples: collections.deque = collections.deque(maxlen=capacity)
        self.n_seen = 0            # total records, including ring-evicted

    def __len__(self) -> int:
        return len(self.samples)

    def record(self, sample: ProbeSample) -> None:
        self.samples.append(sample)
        self.n_seen += 1

    def note(self, method: str, p: int, nbytes: int, num_blocks: int, *,
             dtype: str = "float32", kind: str = "trace",
             wall_s: float = 0.0, levels=None, axis=None,
             rounds: int | None = None) -> ProbeSample:
        """Build + record one sample, filling ``predicted_s`` from the
        probe's cost model. Returns the recorded sample."""
        s = ProbeSample(
            p=int(p), nbytes=int(nbytes), dtype=str(dtype),
            method=str(method), num_blocks=max(1, int(num_blocks)),
            wall_s=float(wall_s),
            predicted_s=predict_time(method, int(p), int(nbytes),
                                     int(num_blocks), self.model,
                                     levels=levels),
            kind=kind,
            levels=tuple(levels) if levels is not None
            and not isinstance(levels, int) else levels,
            axis=axis, rounds=rounds)
        self.record(s)
        return s

    def timed(self) -> list:
        return [s for s in self.samples if s.kind == "timed"]

    def traced(self) -> list:
        return [s for s in self.samples if s.kind == "trace"]


# ---------------------------------------------------------------- install
# Process-wide active probe: the collective layer cannot thread a probe
# argument through jitted call sites, so installation is ambient (like a
# profiler). None (the default) short-circuits every hook.
_ACTIVE: CollectiveProbe | None = None


def install(probe: CollectiveProbe) -> CollectiveProbe:
    """Make ``probe`` the process-wide active probe; returns it."""
    global _ACTIVE
    _ACTIVE = probe
    return probe


def uninstall() -> None:
    global _ACTIVE
    _ACTIVE = None


def active() -> CollectiveProbe | None:
    """The installed probe, or None (the zero-overhead default)."""
    return _ACTIVE


@contextlib.contextmanager
def probing(capacity: int = 4096, model: cm.CommModel = cm.TPU_V5E):
    """``with probing() as probe:`` — install a fresh probe for the block,
    restoring whatever was installed before on exit."""
    global _ACTIVE
    prev = _ACTIVE
    probe = CollectiveProbe(capacity=capacity, model=model)
    _ACTIVE = probe
    try:
        yield probe
    finally:
        _ACTIVE = prev
