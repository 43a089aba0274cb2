"""``depthwise``: a depthwise 2-D convolution with bias: one ``K x K`` filter
per channel (a grouped convolution with as many groups as channels),
stride ``S``, ``pad`` zeros on each side of both spatial axes; channels
unchanged.  NHWC maps, HWIO weights of ``K x K x 1 x C``, He-normal with a
fan-in of ``K * K``.  Part of the conv stack that ``conv_roofline`` bounds.
"""

import jax
import jax.numpy as jnp

from bench.model import he_normal, quantize

FIELDS = ("K", "S", "pad")
CONV_STACK = True


def out_shape(layer, ins):
    size, ch = ins[0]
    return (size + 2 * layer["pad"] - layer["K"]) // layer["S"] + 1, ch


def param_shapes(layer, ins):
    k, ch = layer["K"], ins[0][1]
    return (k, k, 1, ch), (ch,)


def init(keys, layer, ins):
    return he_normal(keys, *param_shapes(layer, ins))


def flops(layer, ins, out):
    size, ch = out
    return 2 * layer["K"] * layer["K"] * ch * size * size


def forward(layer, params, xs, int8):
    w, b = params
    return _depthwise(xs[0], w, layer, int8) + b


def _depthwise(x, w, layer, int8):
    args = dict(
        window_strides=(layer["S"], layer["S"]),
        padding=[(layer["pad"], layer["pad"])] * 2,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=x.shape[-1],
    )
    if not int8:
        return jax.lax.conv_general_dilated(
            x, w, precision=jax.lax.Precision.HIGHEST, **args
        )
    # activations per image, weights per channel, as conv.py's control,
    # accumulated exactly in int32
    xq, xs = quantize(x, (1, 2, 3))
    wq, ws = quantize(w, (0, 1, 2))
    acc = jax.lax.conv_general_dilated(
        xq, wq, preferred_element_type=jnp.int32, **args
    )
    return acc.astype(jnp.float32) * xs * ws.reshape(1, 1, 1, -1)
