"""Device: the share of the traced stretch in which no op ran on the chip,
in percent."""


def read(ctx):
    t = ctx.trace
    if t is None or not t.window_s:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
