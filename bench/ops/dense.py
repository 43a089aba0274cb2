"""``dense``: a matrix product of a flat vector with bias and an optional
fused ReLU; weights ``C_in x n_out``."""

import jax
import jax.numpy as jnp

from bench.model import he_normal, quantize

FIELDS = ("n_out", "relu")
CONV_STACK = False


def out_shape(layer, ins):
    return 0, layer["n_out"]


def param_shapes(layer, ins):
    return (ins[0][1], layer["n_out"]), (layer["n_out"],)


def init(keys, layer, ins):
    return he_normal(keys, *param_shapes(layer, ins))


def flops(layer, ins, out):
    return 2 * ins[0][1] * layer["n_out"]


def forward(layer, params, xs, int8):
    w, b = params
    y = _dense(xs[0], w, int8) + b
    return jax.nn.relu(y) if layer["relu"] else y


def _dense(x, w, int8):
    if not int8:
        return jnp.dot(x, w, precision=jax.lax.Precision.HIGHEST)
    xq, xs = quantize(x, (1,))
    wq, ws = quantize(w, (0,))
    acc = jnp.dot(xq, wq, preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * xs * ws
