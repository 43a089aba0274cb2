"""Serving engine: mean time from the drain thread resolving a request's
handle to ``RequestHandle.result()`` returning in the caller's thread, per
request, in microseconds, from the program's ``frontend.deliver`` spans."""

from bench.spans import mean_us


def read(ctx):
    return mean_us(ctx, "frontend.deliver")
