"""``gelu``: the exact GELU, ``x * Phi(x) = x / 2 * (1 + erf(x / sqrt 2))``
(not its tanh approximation), in float32.  No parameters and no model
FLOPs, as a ReLU has none."""

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
    return jax.nn.gelu(xs[0], approximate=False)
