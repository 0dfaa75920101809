"""The reduction from a profiler trace to device numbers.

Two halves. :func:`load` reads the ``.xplane.pb`` the JAX profiler writes
into plain :class:`Event` lists: per TPU device, its XLA modules (one
event per program run) and its XLA ops, and from the host the
benchmark's own spans (names starting with ``chipbench.``). The rest
works on those lists alone, so the arithmetic is tested on small
hand-made traces as well as on a recorded one.

Times are nanoseconds on the profiler's clock; host spans and device
events share it.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
import warnings

HOST_PREFIX = "chipbench."
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float          # ns
    end: float            # ns
    stats: tuple = ()     # ((key, value), ...)

    @property
    def dur(self) -> float:
        return self.end - self.start

    def stat(self, key, default=None):
        return dict(self.stats).get(key, default)


@dataclasses.dataclass
class Trace:
    modules: dict          # device index -> [Event] (XLA Modules line)
    ops: dict              # device index -> [Event] (XLA Ops line)
    host: list             # the benchmark's own host spans
    window: tuple          # (start, end) ns of the traced window

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _events(line) -> list:
    with warnings.catch_warnings():
        # jaxlib's stats type warns on inspection; nothing to act on
        warnings.simplefilter("ignore", DeprecationWarning)
        return [Event(e.name, e.start_ns, e.start_ns + e.duration_ns,
                      tuple(dict(e.stats).items()))
                for e in line.events]


def load(path: str, window_span: str = HOST_PREFIX + "window") -> Trace:
    """Read an ``.xplane.pb``. The traced window is the host span named
    ``window_span``; without one it is the extent of all device events."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    modules, ops, host = {}, {}, []
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules[dev] = _events(line)
                elif line.name == "XLA Ops":
                    ops[dev] = _events(line)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(e for e in _events(line)
                            if e.name.startswith(HOST_PREFIX))
    host.sort(key=lambda e: e.start)
    spans = [e for e in host if e.name == window_span]
    if spans:
        window = (spans[0].start, spans[0].end)
    else:
        every = [e for evs in ops.values() for e in evs]
        if not every:
            raise ValueError(f"{path}: no device events and no "
                             f"{window_span!r} span")
        window = (min(e.start for e in every), max(e.end for e in every))
    return Trace(modules, ops, host, window)


# ------------------------------------------------------------ arithmetic

def clip(events, window) -> list:
    """Events cut to ``window``; those wholly outside are dropped."""
    lo, hi = window
    return [dataclasses.replace(e, start=max(e.start, lo), end=min(e.end, hi))
            for e in events if e.end > lo and e.start < hi]


def merge(events) -> list:
    """The union of the events' intervals as sorted ``(start, end)``."""
    out = []
    for e in sorted(events, key=lambda e: e.start):
        if out and e.start <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e.end))
        else:
            out.append((e.start, e.end))
    return out


def busy_ns(events, window) -> float:
    return sum(b - a for a, b in merge(clip(events, window)))


def busy_s(trace: Trace) -> float:
    """Seconds in the window in which an operation ran, averaged over the
    devices that ran any."""
    per = [busy_ns(evs, trace.window) for evs in trace.ops.values() if evs]
    return sum(per) / len(per) * 1e-9 if per else 0.0


def idle_gaps(events, window) -> list:
    """``(start, end)`` of every stretch of the window in which none of
    ``events`` ran, leading and trailing stretches included."""
    lo, hi = window
    gaps, at = [], lo
    for a, b in merge(clip(events, window)):
        if a > at:
            gaps.append((at, a))
        at = max(at, b)
    if hi > at:
        gaps.append((at, hi))
    return gaps


def label_gap(gap, host) -> str:
    """What the host was doing in a gap: the innermost benchmark span
    that covers the gap's midpoint, or ``"no span"``."""
    mid = (gap[0] + gap[1]) / 2
    covering = [e for e in host if e.start <= mid <= e.end]
    if not covering:
        return "no span"
    return min(covering, key=lambda e: e.dur).name[len(HOST_PREFIX):]


def longest_gaps(trace: Trace, device: int, n: int = 10) -> list:
    """The ``n`` longest idle gaps on one device as ``[label, seconds]``."""
    gaps = idle_gaps(trace.ops.get(device, []), trace.window)
    gaps.sort(key=lambda g: g[0] - g[1])
    return [[label_gap(g, trace.host), (g[1] - g[0]) * 1e-9]
            for g in gaps[:n]]


_SUFFIX = re.compile(r"[.\d]+$")


def op_family(name: str) -> str:
    """An op's name without its numeric suffix (``fusion.12`` ->
    ``fusion``), so that a program's many instances add up. TPU traces
    name an op by its HLO text: that becomes the instruction's name and
    its result type (``%fusion.12 = bf16[4,8]{1,0} fusion(...)`` ->
    ``fusion bf16[4,8]{1,0}``)."""
    if " = " not in name:
        return _SUFFIX.sub("", name) or name
    instr, rest = name.split(" = ", 1)
    instr = instr.lstrip("%")
    result = "(tuple)" if rest.startswith("(") else rest.split(" ", 1)[0]
    return f"{_SUFFIX.sub('', instr) or instr} {result}"


def top_ops(trace: Trace, device: int, n: int = 10) -> list:
    """Device time by op family on one device, largest first, as
    ``[name, seconds]``."""
    tot = {}
    for e in clip(trace.ops.get(device, []), trace.window):
        k = op_family(e.name)
        tot[k] = tot.get(k, 0.0) + e.dur
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])
    return [[k, v * 1e-9] for k, v in ranked[:n]]


def program_key(e: Event):
    """What identifies one compiled program in the modules line: its
    ``program_id`` stat where the profiler gives one, else its name."""
    return e.stat("program_id", e.name)
