"""Serving engine: mean time from a batch's logits being ready on the
device to every request of it completed (logits copied to the host,
results made, listeners called under the engine lock), per batch, in
microseconds, from the program's ``engine.record`` spans."""

from bench.spans import mean_us


def read(ctx):
    return mean_us(ctx, "engine.record")
