"""Fusion observability: structured launch traces, metrics, and reports.

The plan ladder (``x_slots`` / ``w_slots`` / ``c_tiles``, resident vs
streamed vs channel-tiled) is chosen by *modeled* cycles; this package is
the substrate that records what each launch planned and what it measurably
did, so the model-vs-hardware loop can be closed (ROADMAP).  Pieces:

* :mod:`repro.obs.trace` — the :class:`TraceCollector` host-span ring,
  launch-span, event and counter store, and the process-global tracer hook
  (:func:`get_tracer` / :func:`tracing`).  The default tracer is an
  always-on bounded recorder that the serving engine times its host path
  into; installing any tracer leaves the forward path unchanged.
* :mod:`repro.obs.timeline` — Chrome-trace (``chrome://tracing`` /
  Perfetto) JSON export: each launch's modeled fill/steady/drain
  DMA-vs-MXU timeline from the cycle model rendered alongside measured
  launch spans and host spans (one track per thread), plus the schema
  validator the CI smoke job runs.
* :mod:`repro.obs.report` — the model-vs-measured drift report joining
  modeled cycles against measured medians per launch.
* :mod:`repro.obs.explain` — the ``python -m repro.obs.explain`` CLI: the
  partition plan as a per-launch table, optionally run + traced.

See DESIGN.md §12 for the span schema and the timeline format.
"""

from .stats import percentile, timed_stats_ms
from .timeline import chrome_trace, validate_chrome_trace, write_chrome_trace
from .trace import (
    NULL_TRACER,
    HostSpan,
    LaunchSpan,
    TraceCollector,
    TraceEvent,
    get_tracer,
    set_tracer,
    tracing,
)

_REPORT_EXPORTS = (
    "drift_report", "drift_rows_from_bench", "drift_rows_from_spans",
)


def __getattr__(name: str):
    # lazy so `python -m repro.obs.report` doesn't import the module twice
    # (runpy would warn about the package __init__'s copy)
    if name in _REPORT_EXPORTS:
        from . import report

        return getattr(report, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")

__all__ = [
    "NULL_TRACER",
    "HostSpan",
    "LaunchSpan",
    "TraceCollector",
    "TraceEvent",
    "chrome_trace",
    "drift_report",
    "drift_rows_from_bench",
    "drift_rows_from_spans",
    "get_tracer",
    "percentile",
    "set_tracer",
    "timed_stats_ms",
    "tracing",
    "validate_chrome_trace",
    "write_chrome_trace",
]
