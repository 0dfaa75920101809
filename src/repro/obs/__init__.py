"""Observability plane: tracing, collective probes, streaming metrics.

Three pillars, each costing next to nothing when off (docs/observability.md):

* :mod:`repro.obs.trace` — structured serving traces on the wall clock,
  with :func:`~repro.obs.trace.span` around each phase of a tick (also
  seen by the JAX profiler) and JSONL and Chrome-trace/Perfetto
  exporters; pure observation (bit-identity guaranteed by tests).
* :mod:`repro.obs.probe` + :mod:`repro.obs.fit` — collective timing
  samples ``(p, nbytes, dtype, method, num_blocks) -> wall time`` and the
  least-squares ``(alpha, beta)`` fitter that turns them into fresh
  CommModel constants with predicted-vs-measured residuals.
* :mod:`repro.obs.hist` — fixed-bucket TTFT/latency histograms that ride
  the same b=1 dual-root stats reduction (counts merge by the addition
  the tree already does).
"""

from repro.obs.fit import (FitResult, export_residuals, fit_alpha_beta,
                           fit_hier, flat_coeffs, residual_report)
from repro.obs.hist import DEFAULT_EDGES, StreamingMetrics, TickHistogram
from repro.obs.probe import (CollectiveProbe, ProbeSample, active, install,
                             predict_time, probing, uninstall)
from repro.obs.trace import (PROFILER_PREFIX, SPAN_NAMES, TraceEvent, Tracer,
                             span)

__all__ = [
    "PROFILER_PREFIX", "SPAN_NAMES", "TraceEvent", "Tracer", "span",
    "CollectiveProbe", "ProbeSample", "active", "install", "predict_time",
    "probing", "uninstall",
    "FitResult", "export_residuals", "fit_alpha_beta", "fit_hier",
    "flat_coeffs", "residual_report",
    "DEFAULT_EDGES", "StreamingMetrics", "TickHistogram",
]
