"""The benchmark's own view of a model configuration.

Everything here reads ``bench/configs/<config>.json`` and nothing of the
system under test: the layer list, the operation and byte counts that the
roofline and utilization metrics divide by, the weights drawn from
``--seed``, the plain float32 reference that decides ``correct``, and the
int8 control that the comparison has to reject.

Each layer's ``op`` names a module ``bench/ops/<op>.py`` that states the
op's semantics and defines all that is known of it here:

- ``FIELDS``: the layer keys, beyond ``name``, ``op`` and ``inputs``,
  that ``harness.check_graph`` compares with the program's node;
- ``CONV_STACK``: whether the op is part of the conv stack whose least
  time ``conv_roofline`` takes;
- ``out_shape(layer, ins)``: the ``(size, channels)`` leaving the layer,
  from those of its inputs (``size`` 0 is a flat vector);
- ``param_shapes(layer, ins)``: the shapes of its parameters, ``()`` for
  none, and ``init(keys, layer, ins)``, which draws them from one key each;
- ``flops(layer, ins, out)``: its model FLOPs for one image;
- ``forward(layer, params, xs, int8)``: the float32 reference at
  ``HIGHEST`` precision, or with ``int8`` its control.

A new op is a new file there.  Feature maps are NHWC.
"""

from __future__ import annotations

import functools
import json
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from bench.registry import BenchError, load_module

BENCH_DIR = Path(__file__).resolve().parent
OPS_DIR = BENCH_DIR / "ops"
INPUT = "image"  # the name every layer list gives the network input
DTYPE_BYTES = {"float32": 4, "bfloat16": 2}
OP_INTERFACE = ("FIELDS", "CONV_STACK", "out_shape", "param_shapes", "flops", "forward")


def load_config(name: str) -> dict:
    """The configuration file ``bench/configs/<name>.json``."""
    with open(BENCH_DIR / "configs" / f"{name}.json") as f:
        return json.load(f)


def op(name: str):
    """The module ``<OPS_DIR>/<name>.py`` that defines the layer op ``name``."""
    return _load_op(OPS_DIR / f"{name}.py")


@functools.cache
def _load_op(path: Path):
    mod = load_module(path, f"op {path.stem}")
    missing = [n for n in OP_INTERFACE if not hasattr(mod, n)]
    if missing:
        raise BenchError(f"op {path.stem}: {path} does not define {missing}")
    return mod


def walk(config: dict):
    """``(layer, op module, input shapes, output shape)`` for every layer
    in order."""
    out = {INPUT: (config["input_size"], config["in_channels"])}
    for layer in config["layers"]:
        mod = op(layer["op"])
        ins = [out[i] for i in layer["inputs"]]
        out[layer["name"]] = mod.out_shape(layer, ins)
        yield layer, mod, ins, out[layer["name"]]


def shapes(config: dict) -> dict[str, tuple[int, int]]:
    """``(size, channels)`` leaving every layer; ``size`` 0 is a flat vector."""
    out = {INPUT: (config["input_size"], config["in_channels"])}
    out.update((layer["name"], shape) for layer, _, _, shape in walk(config))
    return out


def weight_shapes(config: dict) -> dict[str, tuple[tuple[int, ...], int]]:
    """``name -> (weight shape, fan_in)`` of every layer with parameters;
    the weight is the first parameter, its fan-in the product of all but
    its last axis."""
    out = {}
    for layer, mod, ins, _ in walk(config):
        params = mod.param_shapes(layer, ins)
        if params:
            out[layer["name"]] = (params[0], math.prod(params[0][:-1]))
    return out


def flops_per_image(config: dict, ops: tuple[str, ...] | None = None) -> int:
    """Model FLOPs of the listed layer kinds (every kind by default), for
    one image."""
    return sum(mod.flops(layer, ins, out) for layer, mod, ins, out in walk(config)
               if ops is None or layer["op"] in ops)


def conv_stack(config: dict) -> list:
    """``walk``'s entries for the layers of the conv stack, the work whose
    least time ``conv_roofline`` takes."""
    return [entry for entry in walk(config) if entry[1].CONV_STACK]


def conv_stack_least_bytes(config: dict, rows: int) -> int:
    """The fewest bytes the conv stack's work on one batch of ``rows``
    images can move: every parameter of its layers read once, the input
    images read once, and its last feature map written once, at the
    configuration's compute dtype."""
    stack = conv_stack(config)
    weights = sum(math.prod(p) for layer, mod, ins, _ in stack
                  for p in mod.param_shapes(layer, ins))
    size, ch = config["input_size"], config["in_channels"]
    images = rows * size * size * ch
    size, ch = stack[-1][3]
    return DTYPE_BYTES[config["compute_dtype"]] * (weights + images + rows * size * size * ch)


def conv_stack_least_seconds(config: dict, rows: int, peak: dict) -> tuple[float, str]:
    """The least time the conv stack's work on ``rows`` images can take on
    a chip with ``peak``, and which bound sets it (``compute`` or
    ``memory``)."""
    flops = sum(mod.flops(layer, ins, out) for layer, mod, ins, out in conv_stack(config))
    t_flops = rows * flops / peak["bf16_flops_per_s"]
    t_bytes = conv_stack_least_bytes(config, rows) / peak["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "memory")


def he_normal(keys, w_shape: tuple[int, ...], b_shape: tuple[int, ...]):
    """He-normal float32 weights (fan-in all but the last axis) and biases
    of standard deviation 0.01, one key each."""
    w = jax.random.normal(keys[0], w_shape, jnp.float32)
    b = jax.random.normal(keys[1], b_shape, jnp.float32)
    return w * (2.0 / math.prod(w_shape[:-1])) ** 0.5, b * 0.01


def init_params(config: dict, seed: int):
    """Every layer's parameters, made on the device in one jitted call from
    ``seed``: one split of the seed's key into one key per parameter, taken
    in layer order."""
    plan = [(layer, mod, ins, n) for layer, mod, ins, _ in walk(config)
            if (n := len(mod.param_shapes(layer, ins)))]

    @jax.jit
    def make(key):
        keys = jax.random.split(key, sum(n for *_, n in plan))
        out, at = {}, 0
        for layer, mod, ins, n in plan:
            out[layer["name"]] = mod.init(keys[at:at + n], layer, ins)
            at += n
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


def quantize(x, axes):
    """Symmetric int8 quantization of ``x`` with one scale per slice that
    ``axes`` reduces over; returns ``(int8 values, float32 scale)``."""
    scale = jnp.max(jnp.abs(x), axis=axes, keepdims=True) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def forward(config: dict, params, x, *, int8: bool = False):
    """Layer-by-layer float32 forward with full intermediate maps.

    ``int8=False`` is the reference: every product at ``HIGHEST``
    precision.  ``int8=True`` is the control: each op with a product takes
    int8 operands and accumulates in int32, the step below the
    configuration's bfloat16 operands; everything else stays float32."""
    values = {INPUT: x.astype(jnp.float32)}
    for layer in config["layers"]:
        xs = [values[i] for i in layer["inputs"]]
        values[layer["name"]] = op(layer["op"]).forward(layer, params.get(layer["name"]), xs, int8)
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
