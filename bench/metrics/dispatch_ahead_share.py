"""Serving engine: the share of the engine's fused batch dispatches made
while the previous batch was still in flight, in percent, from the
counters the drain loop bumps (``serve.dispatched_ahead`` over
``serve.batches`` in the process-wide recorder of ``repro.obs``).  No
number where the program keeps no such counters."""


def read(ctx):
    try:
        from repro.obs.trace import get_tracer
    except ImportError:
        return None
    counters = getattr(get_tracer(), "counters", None) or {}
    batches = counters.get("serve.batches")
    if not batches:
        return None
    return 100.0 * counters.get("serve.dispatched_ahead", 0) / batches
