"""``layer_scale``: each channel multiplied by its own gamma, as a ConvNeXt
block does before its residual add.  gamma is drawn as ``1 + 0.1 N(0, 1)``:
the training init of 1e-6 would make every block an identity and leave the
comparison blind to the blocks.  In float32; no model FLOPs."""

import jax
import jax.numpy as jnp

FIELDS = ()
CONV_STACK = False


def out_shape(layer, ins):
    return ins[0]


def param_shapes(layer, ins):
    return ((ins[0][1],),)


def init(keys, layer, ins):
    return (1.0 + 0.1 * jax.random.normal(keys[0], (ins[0][1],), jnp.float32),)


def flops(layer, ins, out):
    return 0


def forward(layer, params, xs, int8):
    (gamma,) = params
    return xs[0] * gamma
