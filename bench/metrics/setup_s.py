"""Seconds from the start of the benchmark process to the first timed
request: JAX start-up, weights, plan, compile or cache load, warm-up."""


def read(ctx):
    return ctx.setup_s
