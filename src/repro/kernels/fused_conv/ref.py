"""Pure-jnp oracles for the fused pyramid kernel: the monolithic
layer-by-layer execution from :mod:`repro.core.executor`."""

from __future__ import annotations

import jax.numpy as jnp

from repro.core.executor import PyramidParams, reference_forward
from repro.core.fusion import FusionSpec


def fused_pyramid_ref(
    x: jnp.ndarray,
    spec: FusionSpec,
    weights: list,
    biases: list,
) -> jnp.ndarray:
    """Oracle for :func:`~repro.kernels.fused_conv.ops.fused_pyramid`."""
    params = PyramidParams(weights=list(weights), biases=list(biases))
    return reference_forward(x, spec, params)


def fused_conv2_ref(
    x: jnp.ndarray,
    spec: FusionSpec,
    w1: jnp.ndarray,
    b1: jnp.ndarray,
    w2: jnp.ndarray,
    b2: jnp.ndarray,
) -> jnp.ndarray:
    return fused_pyramid_ref(x, spec, [w1, w2], [b1, b2])
