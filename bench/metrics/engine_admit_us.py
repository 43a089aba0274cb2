"""Serving engine: mean time ``ServingEngine.submit`` takes to admit a
request (shape and finiteness checks, queueing), per request, in
microseconds, from the program's ``engine.admit`` spans."""

from bench.spans import mean_us


def read(ctx):
    return mean_us(ctx, "engine.admit")
