"""The benchmark's own view of a model configuration.

Everything here reads ``bench/configs/<config>.json`` and nothing of the
system under test: the layer list, the operation and byte counts that the
roofline and utilization metrics divide by, the weights drawn from
``--seed``, the plain float32 reference that decides ``correct``, and the
int8 control that the comparison has to reject.

Op semantics follow the published networks: ``conv`` is a 2-D convolution
with bias and an optional fused ReLU, ``pool`` is a max pool, ``add`` a
residual sum, ``relu`` a stand-alone activation, ``global_pool`` a spatial
mean, ``flatten`` a row-major ``(H, W, C)`` flatten and ``dense`` a matrix
product with bias and optional ReLU.  Feature maps are NHWC.
"""

from __future__ import annotations

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

BENCH_DIR = Path(__file__).resolve().parent
INPUT = "image"  # the name every layer list gives the network input
DTYPE_BYTES = {"float32": 4, "bfloat16": 2}


def load_config(name: str) -> dict:
    """The configuration file ``bench/configs/<name>.json``."""
    with open(BENCH_DIR / "configs" / f"{name}.json") as f:
        return json.load(f)


def shapes(config: dict) -> dict[str, tuple[int, int]]:
    """``(size, channels)`` leaving every layer; ``size`` 0 is a flat vector."""
    out = {INPUT: (config["input_size"], config["in_channels"])}
    for layer in config["layers"]:
        size, ch = out[layer["inputs"][0]]
        op = layer["op"]
        if op in ("conv", "pool"):
            size = (size + 2 * layer["pad"] - layer["K"]) // layer["S"] + 1
            if op == "conv":
                ch = layer["n_out"]
        elif op == "global_pool":
            size = 0
        elif op == "flatten":
            size, ch = 0, size * size * ch if size else ch
        elif op == "dense":
            size, ch = 0, layer["n_out"]
        out[layer["name"]] = (size, ch)
    return out


def weight_shapes(config: dict) -> dict[str, tuple[tuple[int, ...], int]]:
    """``name -> (weight shape, fan_in)`` of every conv and dense layer."""
    sh = shapes(config)
    out = {}
    for layer in config["layers"]:
        c_in = sh[layer["inputs"][0]][1]
        if layer["op"] == "conv":
            k = layer["K"]
            out[layer["name"]] = ((k, k, c_in, layer["n_out"]), k * k * c_in)
        elif layer["op"] == "dense":
            out[layer["name"]] = ((c_in, layer["n_out"]), c_in)
    return out


def flops_per_image(config: dict, ops: tuple[str, ...] = ("conv", "dense")) -> int:
    """Multiply-adds times two of the listed layer kinds, for one image."""
    sh = shapes(config)
    ws = weight_shapes(config)
    total = 0
    for layer in config["layers"]:
        if layer["op"] not in ops:
            continue
        w_shape, _ = ws[layer["name"]]
        size = sh[layer["name"]][0]
        macs = int(np.prod(w_shape)) * (size * size if layer["op"] == "conv" else 1)
        total += 2 * macs
    return total


def conv_stack_least_bytes(config: dict, rows: int) -> int:
    """The fewest bytes the conv and pool work of one batch of ``rows``
    images can move: every conv weight and bias read once, the input images
    read once, and the last feature map of the conv/pool stack written once,
    at the configuration's compute dtype."""
    nbytes = DTYPE_BYTES[config["compute_dtype"]]
    sh = shapes(config)
    ws = weight_shapes(config)
    weights = sum(
        int(np.prod(ws[l["name"]][0])) + l["n_out"]
        for l in config["layers"] if l["op"] == "conv"
    )
    size, ch = sh[INPUT]
    images = rows * size * size * ch
    last = [l for l in config["layers"] if l["op"] in ("conv", "pool")][-1]
    size, ch = sh[last["name"]]
    return nbytes * (weights + images + rows * size * size * ch)


def conv_stack_least_seconds(config: dict, rows: int, peak: dict) -> tuple[float, str]:
    """The least time the conv and pool work of ``rows`` images can take on
    a chip with ``peak``, and which bound sets it (``compute`` or
    ``memory``)."""
    t_flops = rows * flops_per_image(config, ("conv",)) / peak["bf16_flops_per_s"]
    t_bytes = conv_stack_least_bytes(config, rows) / peak["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "memory")


def init_params(config: dict, seed: int):
    """He-normal float32 weights and small normal biases for every conv and
    dense layer, made on the device in one jitted call from ``seed``."""
    ws = weight_shapes(config)
    names = list(ws)

    @jax.jit
    def make(key):
        keys = jax.random.split(key, 2 * len(names))
        out = {}
        for i, name in enumerate(names):
            shape, fan_in = ws[name]
            w = jax.random.normal(keys[2 * i], shape, jnp.float32)
            b = jax.random.normal(keys[2 * i + 1], (shape[-1],), jnp.float32)
            out[name] = (w * (2.0 / fan_in) ** 0.5, b * 0.01)
        return out

    return make(seed_key(seed))


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any non-negative seed, 64-bit ones included."""
    seed = int(seed)
    if not 0 <= seed < 2**64:
        raise ValueError(f"seed {seed} is outside [0, 2**64)")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def make_images(config: dict, seed: int, count: int) -> np.ndarray:
    """``count`` standard-normal float32 images, the same for the same seed."""
    size, ch = config["input_size"], config["in_channels"]
    rng = np.random.default_rng([int(seed), 0x1A6E5])
    return rng.standard_normal((count, size, size, ch), dtype=np.float32)


# ---------------------------------------------------------------------------
# The plain reference and its int8 control
# ---------------------------------------------------------------------------


def _quantize(x, axes):
    """Symmetric int8 quantization of ``x`` with one scale per slice that
    ``axes`` reduces over; returns ``(int8 values, float32 scale)``."""
    scale = jnp.max(jnp.abs(x), axis=axes, keepdims=True) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def _conv(x, w, layer, int8: bool):
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
    xq, xs = _quantize(x, (1, 2, 3))
    wq, ws = _quantize(w, (0, 1, 2))
    acc = jax.lax.conv_general_dilated(
        xq, wq, stride, pad, dimension_numbers=dims,
        preferred_element_type=jnp.int32,
    )
    return acc.astype(jnp.float32) * xs * ws.reshape(1, 1, 1, -1)


def _dense(x, w, int8: bool):
    if not int8:
        return jnp.dot(x, w, precision=jax.lax.Precision.HIGHEST)
    xq, xs = _quantize(x, (1,))
    wq, ws = _quantize(w, (0,))
    acc = jnp.dot(xq, wq, preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * xs * ws


def forward(config: dict, params, x, *, int8: bool = False):
    """Layer-by-layer float32 forward with full intermediate maps.

    ``int8=False`` is the reference: every product at ``HIGHEST``
    precision.  ``int8=True`` is the control: each conv and dense layer
    takes int8 operands and accumulates in int32, the step below the
    configuration's bfloat16 operands; everything else stays float32."""
    values = {INPUT: x.astype(jnp.float32)}
    for layer in config["layers"]:
        op, name = layer["op"], layer["name"]
        a = values[layer["inputs"][0]]
        if op == "conv":
            w, b = params[name]
            y = _conv(a, w, layer, int8) + b
            y = jax.nn.relu(y) if layer["relu"] else y
        elif op == "pool":
            p = layer["pad"]
            y = jax.lax.reduce_window(
                a, -jnp.inf, jax.lax.max, (1, layer["K"], layer["K"], 1),
                (1, layer["S"], layer["S"], 1), ((0, 0), (p, p), (p, p), (0, 0)),
            )
        elif op == "relu":
            y = jax.nn.relu(a)
        elif op == "add":
            y = a + values[layer["inputs"][1]]
        elif op == "global_pool":
            y = jnp.mean(a, axis=(1, 2))
        elif op == "flatten":
            y = a.reshape(a.shape[0], -1)
        elif op == "dense":
            w, b = params[name]
            y = _dense(a, w, int8) + b
            y = jax.nn.relu(y) if layer["relu"] else y
        else:
            raise ValueError(f"layer {name}: unknown op {op!r}")
        values[name] = y
    return values[config["layers"][-1]["name"]]


def logits_in_blocks(config: dict, params, images: np.ndarray, *,
                     int8: bool = False, block: int = 8) -> np.ndarray:
    """``forward`` over ``images`` in blocks of ``block`` rows, so that the
    full-size maps of one block at a time fit on the device."""
    run = jax.jit(lambda p, x: forward(config, p, x, int8=int8))
    out = []
    for i in range(0, len(images), block):
        chunk = images[i:i + block]
        pad = block - len(chunk)
        if pad:
            chunk = np.concatenate([chunk, np.zeros((pad,) + chunk.shape[1:], chunk.dtype)])
        out.append(np.asarray(run(params, jnp.asarray(chunk)))[:block - pad])
    return np.concatenate(out)


def logit_rms_error(logits: np.ndarray, reference: np.ndarray) -> np.ndarray:
    """Per row: the root-mean-square logit difference as a share of the
    row's root-mean-square reference logit."""
    logits = np.asarray(logits, np.float32).reshape(len(reference), -1)
    gap = np.sqrt(np.mean((logits - reference) ** 2, axis=1))
    return gap / np.maximum(np.sqrt(np.mean(reference**2, axis=1)), 1e-30)
