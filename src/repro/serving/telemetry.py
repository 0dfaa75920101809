"""Per-step serving telemetry, reduced across replicas with the b=1 tree.

Each engine tick produces a small stats vector (queue depth, busy slots,
tokens emitted, prefills). In a data-parallel serving fleet every replica
needs the *global* view of these to make admission and autoscaling
decisions, and the payload is a handful of floats — exactly the b=1
(single-block) latency-bound regime where the paper's dual-root tree beats
a ring by ``O(p / log p)`` (see docs/serving.md for the cost-model numbers).

``make_stats_reducer`` therefore pins ``num_blocks=1`` and leaves the
algorithm choice to ``method="auto"``: a single-pod replica mesh resolves to
the flat dual-root tree from the α-β switch, while a multi-node mesh whose
autotune cache (PR 1/2's warm-up loop) recorded a ``hier`` winner replays
the hierarchical composition automatically — the serving path never hand
picks a collective.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from repro.core.collectives import CollectiveConfig, all_reduce, plan
from repro.obs import probe as _obs_probe

# field order of the per-tick stats vector (summed across replicas):
#   queue_depth    — arrived-but-unadmitted requests
#   active_slots   — slots holding an in-flight request (incl. prefilling)
#   new_tokens     — tokens emitted this tick (prefill first-tokens + decode)
#   prefills       — requests whose admission started this tick
#   prefill_chunks — prompt chunks written this tick (chunked admission; a
#                    short prompt counts one chunk, a long one >= 2 spread
#                    over consecutive ticks)
#   sampled_tokens — of new_tokens, how many came from a seeded
#                    temperature/top-k/top-p sampler rather than greedy
#   drafted_tokens — draft tokens proposed to the speculative verify step
#                    this tick (0 on plain decode ticks)
#   accepted_tokens — of drafted_tokens, how many the verify step accepted;
#                    acceptance rate = accepted/drafted is what the adaptive
#                    draft-length controller steers on, and a fleet-level
#                    view of it costs the SAME b=1 reduction the other
#                    counters already ride (the vector grows by 8 bytes,
#                    the alpha*log p latency term is unchanged)
#   failovers      — requests re-queued off a dead/quarantined replica this
#                    tick (control-plane events: counted by the fleet, 0 on
#                    a standalone engine's own row)
#   resumed_tokens — committed tokens replayed through the exact-resume
#                    re-prefill at (re-)admissions this tick — the journal
#                    restore cost, and the number that proves failover lost
#                    nothing (docs/robustness.md)
#   quarantines    — replicas quarantined this tick by the non-finite
#                    decode-logits guard (poisoned work failed over, never
#                    committed)
#   preemptions    — in-flight requests evicted from their slot this tick
#                    for higher-priority work (journal kept; the stream
#                    later resumes bit-identically — docs/scheduling.md)
#   shed_requests  — queued requests dropped unserved this tick by the
#                    overload policy (hopeless deadlines / queue bound)
#   deadline_misses — requests whose TTFT deadline was missed this tick:
#                    counted once per request, either when its first token
#                    lands past the deadline or when it is shed
#   prefix_hits    — admissions this tick that adopted a cached shared
#                    prefix from the cross-request prefix trie
#                    (serving/prefix.py) instead of prefilling from token 0
#   prefix_tokens_reused — prompt tokens those adoptions skipped (the
#                    re-prefill work the trie saved; docs/prefix_caching.md)
# NOTE: new counters are APPENDED — regression tests pin positional slices
# of this tuple, and StepStats gives appended fields 0.0 defaults so rows
# recorded before a field existed still parse.
STATS_FIELDS = ("queue_depth", "active_slots", "new_tokens", "prefills",
                "prefill_chunks", "sampled_tokens", "drafted_tokens",
                "accepted_tokens", "failovers", "resumed_tokens",
                "quarantines", "preemptions", "shed_requests",
                "deadline_misses", "prefix_hits", "prefix_tokens_reused")

# b=1: latency-bound single-block pipeline; "auto": measured autotuner hit
# if one exists for this (p, nbytes, dtype, fabric), else the cost-model
# switch — multi-node meshes with a tuned 'hier' entry pick it up here.
STATS_COLLECTIVE = CollectiveConfig(method="auto", num_blocks=1)


def stats_vector(stats: dict) -> list:
    """Order a per-tick ``{field: value}`` dict into the STATS_FIELDS row.

    This is the anti-drift chokepoint: PRs 3–6 each grew the stats row by
    hand as a positional list, which let the emitter and STATS_FIELDS skew
    silently — and a skewed b=1 reduction payload sums the WRONG counters
    fleet-wide without any shape error. The engine now builds its row by
    name through this function, which refuses any mismatch, so a field
    added to one side but not the other fails on the first tick rather
    than in a dashboard weeks later.
    """
    extra = set(stats) - set(STATS_FIELDS)
    missing = set(STATS_FIELDS) - set(stats)
    if extra or missing:
        raise ValueError(
            "per-tick stats drifted from telemetry.STATS_FIELDS: "
            f"missing={sorted(missing)} unexpected={sorted(extra)}")
    return [float(stats[f]) for f in STATS_FIELDS]


def make_stats_reducer(mesh, axis: str = "data",
                       collective: CollectiveConfig = STATS_COLLECTIVE):
    """Build ``reduce(rows) -> summed (k,)`` over the ``axis`` replicas.

    ``rows`` is either a stacked ``(p, k)`` matrix — one stats row per
    replica, the fleet simulation where the single controller holds every
    replica's counters — or a single ``(k,)``/``(1, k)`` row, the shape one
    :class:`~repro.serving.engine.ServingEngine` produces per tick. A
    single row is broadcast to all ``p`` ranks before the collective (in a
    single-controller run one engine stands in for every replica; a real
    multi-process deployment feeds its own local row per process). Either
    way the rows are summed with the configured collective inside a
    shard_map manual over ``axis``. A 1-sized (or absent) axis returns a
    plain host-side sum — the CPU 1x1 engine pays zero overhead.
    """
    p = dict(getattr(mesh, "shape", {})).get(axis, 1) if mesh is not None \
        else 1
    if p <= 1:
        return lambda rows: np.asarray(rows, np.float32).reshape(
            -1, np.shape(rows)[-1]).sum(0)

    import jax
    from jax.sharding import PartitionSpec as P

    fn = jax.jit(jax.shard_map(
        lambda v: all_reduce(v.reshape(-1), axis, p, collective),
        mesh=mesh, in_specs=P(axis), out_specs=P(),
        axis_names={axis}, check_vma=False))

    def reduce(rows):
        arr = np.atleast_2d(np.asarray(rows, np.float32))
        if arr.shape[0] == 1:
            arr = np.tile(arr, (p, 1))
        if arr.shape[0] != p:
            raise ValueError(
                f"stats rows {arr.shape} do not match the {p}-way "
                f"'{axis}' replica axis (want 1 or {p} rows)")
        probe = _obs_probe.active()
        if probe is None:
            return np.asarray(fn(arr))
        # Timed sample at the host boundary: the jitted body only runs
        # Python at trace time, so wall clocks must bracket the whole
        # dispatch+execute here (block_until_ready pins completion). The
        # method/blocks are re-resolved host-side through the same plan the
        # traced code used, so the sample labels what actually ran.
        import time

        import jax
        nbytes = arr.shape[1] * 4
        pl = plan(collective, p, nbytes, np.dtype(np.float32),
                  axis_name=axis)
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(arr))
        wall = time.perf_counter() - t0
        probe.note(pl.algorithm, p, nbytes, pl.num_blocks, kind="timed",
                   wall_s=wall, levels=pl.group_size, axis=axis)
        return np.asarray(out)

    return reduce


@dataclasses.dataclass(frozen=True)
class StepStats:
    """One engine tick's (cross-replica-summed) counters (see STATS_FIELDS)."""
    tick: int
    queue_depth: float
    active_slots: float
    new_tokens: float
    prefills: float
    prefill_chunks: float = 0.0
    sampled_tokens: float = 0.0
    drafted_tokens: float = 0.0
    accepted_tokens: float = 0.0
    failovers: float = 0.0
    resumed_tokens: float = 0.0
    quarantines: float = 0.0
    preemptions: float = 0.0
    shed_requests: float = 0.0
    deadline_misses: float = 0.0
    prefix_hits: float = 0.0
    prefix_tokens_reused: float = 0.0


class TelemetryLog:
    """Collects per-tick stats and summarizes a finished run."""

    def __init__(self, reducer=None):
        self._reduce = reducer or (
            lambda stacked: np.asarray(stacked, np.float32).sum(0))
        self.steps: list = []
        # Full reduced vector of the latest tick, INCLUDING any payload
        # appended past STATS_FIELDS (e.g. the obs histogram tail, which
        # StepStats deliberately ignores). None before the first tick.
        self.last_reduced = None

    def step(self, tick: int, local_vec) -> StepStats:
        """Record one tick. ``local_vec`` is this replica's row (k,) or a
        stacked (p, k) matrix of every replica's row (fleet simulation)."""
        vec = np.atleast_2d(np.asarray(local_vec, np.float32))
        red = self._reduce(vec)
        self.last_reduced = np.asarray(red)
        s = StepStats(tick, *(float(x) for x in red[:len(STATS_FIELDS)]))
        self.steps.append(s)
        return s

    def report(self, finished, wall_s: float, ticks: int) -> dict:
        """Aggregate a run. ``finished``: completed Request objects."""
        toks = [len(r.tokens) for r in finished]
        ttfts = [r.ttft for r in finished if r.ttft is not None]
        lats = [r.latency for r in finished if r.latency is not None]

        def pct(xs, q):
            return float(np.percentile(xs, q)) if xs else float("nan")

        total = int(sum(toks))
        return {
            "requests": len(finished),
            "total_tokens": total,
            "wall_s": float(wall_s),
            "tok_s": total / wall_s if wall_s > 0 else float("nan"),
            # tok_s is NaN exactly when no wall clock was provided (tick-
            # driven runs); the note makes that path explicit for report
            # consumers instead of a bare NaN.
            "tok_s_note": (None if wall_s > 0
                           else "wall_s <= 0: tok_s undefined"),
            "ticks": int(ticks),
            "ttft_ticks_mean": float(np.mean(ttfts)) if ttfts else float("nan"),
            "ttft_ticks_p50": pct(ttfts, 50),
            "ttft_ticks_p95": pct(ttfts, 95),
            "ttft_ticks_p99": pct(ttfts, 99),
            "latency_ticks_p50": pct(lats, 50),
            "latency_ticks_p95": pct(lats, 95),
            "latency_ticks_p99": pct(lats, 99),
            "steps": list(self.steps),
        }
