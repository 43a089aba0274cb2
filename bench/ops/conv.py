"""``conv``: a 2-D convolution with bias and an optional fused ReLU.

NHWC maps, HWIO weights of ``K x K x C_in x n_out``, stride ``S``,
``pad`` zeros on each side of both spatial axes; batch norm, where the
network has it, is folded into the bias as at inference.  Part of the
conv stack that ``conv_roofline`` bounds.
"""

import math

import jax
import jax.numpy as jnp

from bench.model import he_normal, quantize

FIELDS = ("K", "S", "pad", "n_out", "relu")
CONV_STACK = True


def out_shape(layer, ins):
    size, _ = ins[0]
    return (size + 2 * layer["pad"] - layer["K"]) // layer["S"] + 1, layer["n_out"]


def param_shapes(layer, ins):
    k = layer["K"]
    return (k, k, ins[0][1], layer["n_out"]), (layer["n_out"],)


def init(keys, layer, ins):
    return he_normal(keys, *param_shapes(layer, ins))


def flops(layer, ins, out):
    w_shape, _ = param_shapes(layer, ins)
    return 2 * math.prod(w_shape) * out[0] * out[0]


def forward(layer, params, xs, int8):
    w, b = params
    y = _conv(xs[0], w, layer, int8) + b
    return jax.nn.relu(y) if layer["relu"] else y


def _conv(x, w, layer, int8):
    dims = ("NHWC", "HWIO", "NHWC")
    pad = [(layer["pad"], layer["pad"])] * 2
    stride = (layer["S"], layer["S"])
    if not int8:
        return jax.lax.conv_general_dilated(
            x, w, stride, pad, dimension_numbers=dims,
            precision=jax.lax.Precision.HIGHEST,
        )
    # activations per image, weights per output channel: the usual int8
    # inference scheme, accumulated exactly in int32
    xq, xs = quantize(x, (1, 2, 3))
    wq, ws = quantize(w, (0, 1, 2))
    acc = jax.lax.conv_general_dilated(
        xq, wq, stride, pad, dimension_numbers=dims,
        preferred_element_type=jnp.int32,
    )
    return acc.astype(jnp.float32) * xs * ws.reshape(1, 1, 1, -1)
