"""Model step: the model's FLOPs per image (every conv and dense layer)
times the images that reached the client per second of the traced
stretch, over the chip's bf16 peak, in percent."""

from bench.model import flops_per_image


def read(ctx):
    t = ctx.trace
    if t is None or not ctx.trace_images:
        return None
    rate = ctx.trace_images / t.window_s
    peak = ctx.peak["bf16_flops_per_s"] * max(t.chips, 1)
    return 100.0 * flops_per_image(ctx.config) * rate / peak
