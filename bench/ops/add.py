"""``add``: the residual sum of two maps of one shape, the first input's.
No parameters and no model FLOPs."""

FIELDS = ()
CONV_STACK = False


def out_shape(layer, ins):
    return ins[0]


def param_shapes(layer, ins):
    return ()


def flops(layer, ins, out):
    return 0


def forward(layer, params, xs, int8):
    return xs[0] + xs[1]
