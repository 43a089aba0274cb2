"""Median latency of every request sent inside the window, from the submit
call until the result reached the client."""

from bench.stats import percentile


def read(ctx):
    run = ctx.run
    lat = [(r.done_s - r.submit_s) * 1e3 for r in run.records
           if r.logits is not None and run.in_window(r.submit_s)]
    return percentile(lat, 50) if lat else None
