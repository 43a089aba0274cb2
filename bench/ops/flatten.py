"""``flatten``: a map flattened row-major in ``(H, W, C)`` order; a flat
vector passes unchanged.  No parameters and no model FLOPs."""

FIELDS = ()
CONV_STACK = False


def out_shape(layer, ins):
    size, ch = ins[0]
    return 0, size * size * ch if size else ch


def param_shapes(layer, ins):
    return ()


def flops(layer, ins, out):
    return 0


def forward(layer, params, xs, int8):
    return xs[0].reshape(xs[0].shape[0], -1)
