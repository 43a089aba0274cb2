"""Serving engine: real images over the rows its batches ran (batches times
bucket) over the window, from the engine's own counts, in percent."""


def read(ctx):
    c = ctx.counters
    return 100.0 * c["images"] / c["slots"] if c["slots"] else None
