"""Images whose logits reached the client inside the window, per second of
the window, on the client's clock."""


def read(ctx):
    run = ctx.run
    images = sum(len(r.images) for r in run.records
                 if r.logits is not None and run.in_window(r.done_s))
    return images / ctx.seconds
