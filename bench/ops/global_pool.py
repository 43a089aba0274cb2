"""``global_pool``: the spatial mean of a map, a flat vector of its
channels.  No parameters and no model FLOPs."""

import jax.numpy as jnp

FIELDS = ()
CONV_STACK = False


def out_shape(layer, ins):
    return 0, ins[0][1]


def param_shapes(layer, ins):
    return ()


def flops(layer, ins, out):
    return 0


def forward(layer, params, xs, int8):
    return jnp.mean(xs[0], axis=(1, 2))
