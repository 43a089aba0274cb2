"""Measure how precise an f32 matmul inside a Mosaic kernel is.

The fused-pyramid kernel's f32 dots pass ``precision=HIGHEST`` because
Mosaic lowers an f32 ``jnp.dot`` at the default precision to one bf16 MXU
pass.  This probe shows the difference: it compiles a Pallas kernel that
computes one ``(M, K) @ (K, 128)`` product, the shape of a ResNet-18 3x3
conv row over 64 input channels, at both precisions.  For each it prints
the largest error against a float64 NumPy product, divided by the largest
|product|.  A TPU is required.  From the repository root::

    python tools/dot_precision_probe.py

The last line is one JSON object ``{"default": ..., "highest": ...}``.
"""

from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

M, K, N = 128, 9 * 64, 128


def _dot_kernel(precision):
    def kernel(a_ref, b_ref, o_ref):
        o_ref[...] = jnp.dot(
            a_ref[...], b_ref[...], precision=precision,
            preferred_element_type=jnp.float32,
        )

    return kernel


def main() -> None:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"no TPU (found {dev.platform}): nothing to probe")
    rng = np.random.default_rng(0)
    a = rng.standard_normal((M, K)).astype(np.float32)
    b = rng.standard_normal((K, N)).astype(np.float32)
    exact = a.astype(np.float64) @ b.astype(np.float64)
    errors = {}
    for name, precision in (
        ("default", None), ("highest", jax.lax.Precision.HIGHEST)
    ):
        y = pl.pallas_call(
            _dot_kernel(precision),
            out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        )(a, b)
        err = np.abs(np.asarray(y, np.float64) - exact).max()
        errors[name] = float(err / np.abs(exact).max())
        print(f"{name}: max |err| / max |product| = {errors[name]:.3e}"
              f" ({dev.device_kind})")
    print(json.dumps(errors))


if __name__ == "__main__":
    main()
