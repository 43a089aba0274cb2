"""Serving engine: mean time a request spends inside the front end's
submit call (admission checks and queueing), over the window, in
microseconds, from the client's span around the call."""


def read(ctx):
    run = ctx.run
    spans = [r.admitted_s - r.submit_s for r in run.records if run.in_window(r.submit_s)]
    return 1e6 * sum(spans) / len(spans) if spans else None
