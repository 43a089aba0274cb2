"""``layernorm``: each pixel of a map (or a flat vector) normalized over its
channels, ``(x - mean) / sqrt(var + eps) * gamma + beta`` with the biased
variance, in float32 (also in the int8 control: it has no product of
operands).  gamma is drawn as ``1 + 0.1 N(0, 1)`` and beta as
``0.1 N(0, 1)``, so that the comparison sees them.  No model FLOPs, as a
ReLU has none."""

import jax
import jax.numpy as jnp

FIELDS = ("eps",)
CONV_STACK = False


def out_shape(layer, ins):
    return ins[0]


def param_shapes(layer, ins):
    ch = ins[0][1]
    return (ch,), (ch,)


def init(keys, layer, ins):
    ch = ins[0][1]
    gamma = 1.0 + 0.1 * jax.random.normal(keys[0], (ch,), jnp.float32)
    return gamma, 0.1 * jax.random.normal(keys[1], (ch,), jnp.float32)


def flops(layer, ins, out):
    return 0


def forward(layer, params, xs, int8):
    gamma, beta = params
    x = xs[0]
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + layer["eps"]) * gamma + beta
