"""``relu``: a stand-alone ReLU, as after a residual add.  No parameters
and no model FLOPs."""

import jax

FIELDS = ()
CONV_STACK = False


def out_shape(layer, ins):
    return ins[0]


def param_shapes(layer, ins):
    return ()


def flops(layer, ins, out):
    return 0


def forward(layer, params, xs, int8):
    return jax.nn.relu(xs[0])
