"""Serving engine: mean time the call that runs a staged batch takes to
return on the host (the jitted forward's Python dispatch, not its device
time), per batch, in microseconds, from the program's ``engine.dispatch``
spans."""

from bench.spans import mean_us


def read(ctx):
    return mean_us(ctx, "engine.dispatch")
