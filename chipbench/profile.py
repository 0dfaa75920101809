"""Tracing one stretch of a run's window with the JAX profiler."""

from __future__ import annotations

import shutil
import tempfile
import time

from chipbench import trace as tr


class WindowTracer:
    """Starts the profiler ``t_on`` seconds into the window and stops it at
    ``t_off``; the loop calls :meth:`poll` with the seconds elapsed. The
    traced stretch is marked by a ``chipbench.window`` host span, whose
    host-clock ends (``perf_window``) tie the profiler's clock to
    ``time.perf_counter``."""

    def __init__(self, t_on: float, t_off: float):
        self.t_on, self.t_off = t_on, t_off
        self.dir = None
        self._span = None
        self.perf_window = None

    def poll(self, elapsed: float) -> None:
        if self.dir is None and elapsed >= self.t_on:
            self._start()
        elif self._span is not None and elapsed >= self.t_off:
            self.close()

    def _start(self) -> None:
        import jax
        self.dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._span = jax.profiler.TraceAnnotation(tr.HOST_PREFIX + "window")
        self._span.__enter__()
        self._t = time.perf_counter()

    def close(self) -> None:
        if self._span is None:
            return
        import jax
        t = time.perf_counter()
        self._span.__exit__(None, None, None)
        self._span = None
        jax.profiler.stop_trace()
        self.perf_window = (self._t, t)

    def load(self) -> tr.Trace:
        """Read the trace and delete it from disk."""
        try:
            return tr.load(tr.find_xplane(self.dir))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)

    def to_trace_ns(self, trace: tr.Trace, t: float) -> float:
        """A ``perf_counter`` time on the profiler's clock."""
        return trace.window[0] + (t - self.perf_window[0]) * 1e9
