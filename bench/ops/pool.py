"""``pool``: a ``K x K`` max pool with stride ``S`` over a map padded with
``pad`` cells of minus infinity on each side; channels unchanged.  No
parameters and no model FLOPs; part of the conv stack that
``conv_roofline`` bounds."""

import jax
import jax.numpy as jnp

FIELDS = ("K", "S", "pad")
CONV_STACK = True


def out_shape(layer, ins):
    size, ch = ins[0]
    return (size + 2 * layer["pad"] - layer["K"]) // layer["S"] + 1, ch


def param_shapes(layer, ins):
    return ()


def flops(layer, ins, out):
    return 0


def forward(layer, params, xs, int8):
    p = layer["pad"]
    return jax.lax.reduce_window(
        xs[0], -jnp.inf, jax.lax.max, (1, layer["K"], layer["K"], 1),
        (1, layer["S"], layer["S"], 1), ((0, 0), (p, p), (p, p), (0, 0)),
    )
