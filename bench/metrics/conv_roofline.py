"""Kernels: the least time the network's conv and pool work could take on
the chip, over the device time of the ops that do it (Pallas kernels and
any XLA convolution), in percent.

The least time is the larger of the conv FLOPs over the bf16 peak and the
least bytes over the HBM bandwidth, for the rows the traced forwards ran
(executions of the forward program times its bucket); both counts come
from the configuration's shapes (``bench/model.py``), so they do not
change with how the work is implemented."""

from bench.model import conv_stack_least_seconds


def read(ctx):
    t = ctx.trace
    if t is None or not t.forwards or not (t.kernel_s + t.conv_s):
        return None
    least, _ = conv_stack_least_seconds(ctx.config, t.forwards * ctx.bucket, ctx.peak)
    return 100.0 * least / (t.kernel_s + t.conv_s)
