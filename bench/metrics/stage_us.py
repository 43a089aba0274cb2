"""Serving engine: mean time the drain thread takes to form a batch and
stage it on the device (concatenate, pad to the bucket, ``device_put``),
per batch, in microseconds, from the program's ``engine.stage`` spans."""

from bench.spans import mean_us


def read(ctx):
    return mean_us(ctx, "engine.stage")
