"""The program's own host spans, read from the process-wide recorder of
``repro.obs`` (``get_tracer()``), which the serving engine and front end
fill where the work happens: ``engine.admit``, ``engine.stage``,
``engine.dispatch``, ``engine.block``, ``engine.record``,
``frontend.idle``, ``frontend.deliver``.

A reader takes the spans that start in the part of the window before the
profiler starts, ``[window_start_s, window_end_s - TRACE_SECONDS - 1)`` on
the client's clock (``time.perf_counter``, the recorder's clock too), so
that the profiler's own cost stays out.  It gives no number where the
program keeps no such recorder, where the recorder no longer holds the
interval's start, or where no such span started in it.
"""

from __future__ import annotations

import math

from bench import harness


def window(ctx) -> tuple[float, float]:
    run = ctx.run
    return run.window_start_s, run.window_end_s - harness.TRACE_SECONDS - 1.0


def window_spans(ctx, name: str, *, open_end: bool = False) -> list | None:
    """The recorder's ``name`` spans that start in the window (or, with
    ``open_end``, at its start or later); ``None`` where it cannot tell."""
    try:
        from repro.obs.trace import get_tracer
    except ImportError:
        return None
    tracer = get_tracer()
    if not hasattr(tracer, "spans_between"):
        return None
    t0, t1 = window(ctx)
    if not tracer.holds(t0):
        return None
    spans = tracer.spans_between(t0, math.inf if open_end else t1)
    return [s for s in spans if s.name == name]


def mean_us(ctx, name: str) -> float | None:
    """Mean duration of the window's ``name`` spans, in microseconds."""
    spans = window_spans(ctx, name)
    if not spans:
        return None
    return 1e6 * sum(s.end_s - s.start_s for s in spans) / len(spans)
