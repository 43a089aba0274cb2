"""Serving engine: mean time a request waits between being admitted and
its batch starting to stage, per request, in microseconds: the end of its
``engine.admit`` span to the start of the ``engine.stage`` span of the
batch the admit span is linked to (its ``parent``)."""

from bench.spans import window_spans


def read(ctx):
    admits = window_spans(ctx, "engine.admit")
    stages = window_spans(ctx, "engine.stage", open_end=True)
    if not admits or not stages:
        return None
    start = {s.id: s.start_s for s in stages}
    waits = [start[a.parent] - a.end_s for a in admits if a.parent in start]
    return 1e6 * sum(waits) / len(waits) if waits else None
