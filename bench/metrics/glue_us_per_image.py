"""Forward: device time of every traced op that is neither a Pallas kernel
nor an XLA convolution (residual adds, activations, pooling head, dense
layers, pads, transposes, casts), per image that reached the client in the
traced stretch, in microseconds."""


def read(ctx):
    if ctx.trace is None or not ctx.trace_images:
        return None
    return 1e6 * ctx.trace.glue_s / ctx.trace_images
