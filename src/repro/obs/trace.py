"""Structured serving traces: every serving-plane event as data, on the
wall clock, with spans around the phases of a tick.

:class:`Tracer` collects :class:`TraceEvent` records from the engine,
scheduler, drafters, prefix trie and fleet control plane. Each carries the
deterministic tick it happened in (the scheduling clock of
docs/serving.md) and its wall-clock start ``t_ns`` on
``time.perf_counter_ns``; a span also carries its duration ``dur_ns`` and
every event the ``seq`` of the span it happened inside (``parent``).

:func:`span` is the one way to time a phase. It always enters a
``jax.profiler.TraceAnnotation`` named ``repro.serve.<name>``, so a
running JAX profiler records the phase on its own clock, the clock of the
device's events, and the host's phases line up with the device's
programs. With a tracer it also records the span as a :class:`TraceEvent`.
With neither a tracer nor a profiler running, nothing is kept.

Two exporters:

* **JSONL** (:meth:`Tracer.to_jsonl`): one event per line, trivially
  greppable / loadable into pandas;
* **Chrome trace** (:meth:`Tracer.to_chrome`): the ``chrome://tracing`` /
  Perfetto JSON format in real time — one process row per replica, one
  thread row per request, a lifetime span per request from its first to
  its last event, the tick's phases on the engine lane.

Tracing is PURE OBSERVATION. The tracer is handed into the engine as an
optional sink; every hook records only values the tick loop already
computed and never feeds anything back — the bit-identity suites
(tests/test_obs.py) run the same workload with tracing on and off and
require identical token streams.
"""

from __future__ import annotations

import dataclasses
import json
import time

from jax.profiler import TraceAnnotation

# What the JAX profiler calls a span: PROFILER_PREFIX + its name.
PROFILER_PREFIX = "repro.serve."

# The names every producer emits from (extra detail events such as
# "prefix_insert" are allowed; these names are the documented minimum —
# docs/observability.md has the per-name attribute tables). The phases of
# a tick are spans; the rest are point events.
SPAN_NAMES = ("tick", "schedule", "submit", "admit", "prefill_chunk",
              "decode", "draft", "verify", "fetch", "commit", "reset",
              "stats", "preempt", "resume", "failover", "prefix_adopt",
              "shed")


def _json_safe(v):
    """Clamp attribute values to JSON scalars (arrays/objects -> str)."""
    if isinstance(v, bool) or v is None or isinstance(v, str):
        return v
    if isinstance(v, int):
        return int(v)
    if isinstance(v, float):
        return float(v)
    try:
        import numpy as np
        if isinstance(v, np.integer):
            return int(v)
        if isinstance(v, np.floating):
            return float(v)
    except ImportError:          # pragma: no cover
        pass
    return str(v)


@dataclasses.dataclass(frozen=True)
class TraceEvent:
    """One observed serving-plane event.

    ``tick`` is the session's deterministic tick stamp; ``seq`` a
    monotonically increasing intra-tracer sequence number, in order of
    start; ``rid`` the request id (None for engine-level events such as a
    decode step); ``replica`` the emitting replica (0 for a standalone
    engine). ``t_ns`` is ``time.perf_counter_ns()`` at the start,
    ``dur_ns`` the span's duration (0 for a point event), and ``parent``
    the ``seq`` of the innermost span open when it started, or None.
    """

    name: str
    tick: int
    seq: int
    rid: int | None = None
    replica: int = 0
    attrs: dict = dataclasses.field(default_factory=dict)
    t_ns: int = 0
    dur_ns: int = 0
    parent: int | None = None

    def to_dict(self) -> dict:
        d = {"name": self.name, "tick": self.tick, "seq": self.seq,
             "replica": self.replica, "t_ns": self.t_ns,
             "dur_ns": self.dur_ns, "parent": self.parent}
        if self.rid is not None:
            d["rid"] = self.rid
        if self.attrs:
            d["attrs"] = {k: _json_safe(v) for k, v in self.attrs.items()}
        return d


class Tracer:
    """Bounded in-memory event sink with JSONL and Chrome-trace exporters.

    ``max_events`` bounds memory on long runs: past the cap new events are
    counted in ``dropped`` instead of stored (the cap is generous — a
    trace that big should stream to disk, which ``to_jsonl`` after shorter
    segments covers). The tracer is deliberately dumb: no filtering, no
    sampling, no derived state — post-processing can reconstruct anything
    from the raw events.
    """

    def __init__(self, max_events: int = 200_000):
        if max_events < 1:
            raise ValueError(f"max_events must be >= 1, got {max_events}")
        self.max_events = int(max_events)
        self.events: list = []
        self.dropped = 0
        self._seq = 0
        self._open: list = []          # seqs of the spans entered, not left

    def __len__(self) -> int:
        return len(self.events)

    def _add(self, name, tick, rid, replica, attrs, t_ns) -> tuple:
        """Store one event; returns ``(seq, index)``, index None when the
        cap dropped it."""
        self._seq += 1
        if len(self.events) >= self.max_events:
            self.dropped += 1
            return self._seq, None
        self.events.append(TraceEvent(
            name=str(name), tick=int(tick), seq=self._seq, rid=rid,
            replica=int(replica), attrs=attrs, t_ns=t_ns,
            parent=self._open[-1] if self._open else None))
        return self._seq, len(self.events) - 1

    def event(self, name: str, tick: int, *, rid: int | None = None,
              replica: int = 0, **attrs) -> None:
        """Record one point event. ``attrs`` are free-form scalars (clamped
        to JSON-safe values at export, not at record time — the hot path
        stores references only)."""
        self._add(name, tick, rid, replica, attrs, time.perf_counter_ns())

    def by_name(self, name: str) -> list:
        return [e for e in self.events if e.name == name]

    def names(self) -> set:
        return {e.name for e in self.events}

    # ---------------------------------------------------------- exporters
    def to_jsonl(self, path: str) -> int:
        """One JSON object per line; returns the number of lines written."""
        with open(path, "w") as f:
            for e in self.events:
                f.write(json.dumps(e.to_dict()) + "\n")
        return len(self.events)

    def to_chrome(self, path: str | None = None) -> dict:
        """Export the Chrome-trace / Perfetto JSON object (and write it to
        ``path`` when given). ``ts`` and ``dur`` are microseconds of real
        time from the first event's start. Layout:

        * ``pid`` = replica (one process row per replica, named);
        * ``tid`` = request id + 1 (one thread row per request, named;
          ``tid`` 0 is the engine lane for events with no request);
        * per request: one ``ph="X"`` lifetime span from the start of its
          first event to the end of its last, plus each event as an
          ``"X"`` slice nested inside (Perfetto nests by ts/dur
          containment); a point event is a slice of duration 0;
        * the tick stamp of every event rides in its ``args``.
        """
        t0 = min((e.t_ns for e in self.events), default=0)
        us = lambda ns: (ns - t0) / 1e3                      # noqa: E731
        evs = []
        lanes: dict = {}      # (pid, tid) -> thread label
        spans: dict = {}      # (replica, rid) -> [first start, last end]
        for e in self.events:
            tid = 0 if e.rid is None else int(e.rid) + 1
            lanes[(e.replica, tid)] = ("engine" if e.rid is None
                                       else f"req {e.rid}")
            if e.rid is not None:
                end = e.t_ns + e.dur_ns
                lo, hi = spans.setdefault((e.replica, e.rid), [e.t_ns, end])
                spans[(e.replica, e.rid)] = [min(lo, e.t_ns), max(hi, end)]
        for (pid, tid), label in sorted(lanes.items()):
            evs.append({"ph": "M", "name": "process_name", "pid": pid,
                        "tid": 0, "args": {"name": f"replica {pid}"}})
            evs.append({"ph": "M", "name": "thread_name", "pid": pid,
                        "tid": tid, "args": {"name": label}})
        for (replica, rid), (lo, hi) in sorted(spans.items()):
            evs.append({"ph": "X", "name": f"req {rid}",
                        "cat": "request", "pid": replica, "tid": rid + 1,
                        "ts": us(lo), "dur": (hi - lo) / 1e3,
                        "args": {"rid": rid}})
        for e in self.events:
            args = {k: _json_safe(v) for k, v in e.attrs.items()}
            args["tick"] = e.tick
            if e.rid is not None:
                args["rid"] = e.rid
            evs.append({"ph": "X", "name": e.name, "cat": "serving",
                        "pid": e.replica,
                        "tid": 0 if e.rid is None else int(e.rid) + 1,
                        "ts": us(e.t_ns), "dur": e.dur_ns / 1e3,
                        "args": args})
        doc = {"traceEvents": evs, "displayTimeUnit": "ms",
               "otherData": {"clock": "perf_counter_ns", "t0_ns": t0,
                             "dropped_events": self.dropped}}
        if path is not None:
            with open(path, "w") as f:
                json.dump(doc, f)
        return doc


def span(tracer, name: str, tick: int, /, *, rid: int | None = None,
         replica: int = 0, **attrs):
    """Time one phase: ``with span(tracer, "fetch", tick, of="decode"):``.

    Always enters a ``jax.profiler.TraceAnnotation`` named
    ``repro.serve.<name>`` carrying ``attrs`` (and ``rid``), which a
    running profiler records on the device's clock and which costs about a
    microsecond when none runs. When ``tracer`` is not None the span is
    also recorded as a :class:`TraceEvent` with its start, duration and
    parent; events recorded inside it name it as their ``parent``.
    ``tick`` is positional-only, so a span may carry a ``tick`` attribute.
    """
    if rid is None:
        ann = TraceAnnotation(PROFILER_PREFIX + name, **attrs)
    else:
        ann = TraceAnnotation(PROFILER_PREFIX + name, rid=rid, **attrs)
    if tracer is None:
        return ann
    return _Recorded(ann, tracer, name, tick, rid, replica, attrs)


class _Recorded:
    """A span that a :class:`Tracer` records as well."""

    __slots__ = ("_ann", "_tracer", "_args", "_seq", "_at", "_t")

    def __init__(self, ann, tracer, name, tick, rid, replica, attrs):
        self._ann, self._tracer = ann, tracer
        self._args = (name, tick, rid, replica, attrs)

    def __enter__(self):
        self._ann.__enter__()
        tr = self._tracer
        self._t = time.perf_counter_ns()
        self._seq, self._at = tr._add(*self._args, self._t)
        tr._open.append(self._seq)
        return self

    def __exit__(self, *exc) -> None:
        dur = time.perf_counter_ns() - self._t
        tr = self._tracer
        tr._open.pop()
        if self._at is not None:
            e = tr.events[self._at]
            tr.events[self._at] = TraceEvent(
                e.name, e.tick, e.seq, e.rid, e.replica, e.attrs, e.t_ns,
                dur, e.parent)
        self._ann.__exit__(*exc)
