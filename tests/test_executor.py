"""Fused executor vs monolithic reference — exactness on all networks."""

import dataclasses

import jax
import numpy as np
import pytest

from repro.core.cnn_models import (
    ALEXNET_FUSION,
    LENET5_FUSION,
    VGG_FUSION,
    resnet18_fusions,
)
from repro.core.executor import (
    conv_windows,
    fused_forward,
    init_pyramid_params,
    reference_forward,
)
from repro.core.fusion import FusedLevel, FusionSpec, lockstep_plan

KEY = jax.random.PRNGKey(0)


def _check(spec, region, batch=1, tol=1e-5):
    params = init_pyramid_params(spec, KEY)
    x = jax.random.normal(
        jax.random.PRNGKey(1),
        (batch, spec.input_size, spec.input_size, spec.levels[0].n_in),
    )
    ref = reference_forward(x, spec, params)
    fused = fused_forward(x, spec, params, lockstep_plan(spec, region))
    assert ref.shape == fused.shape
    np.testing.assert_allclose(np.asarray(fused), np.asarray(ref), atol=tol)


class TestFusedEqualsReference:
    def test_lenet(self):
        _check(LENET5_FUSION, 1)

    def test_lenet_batch(self):
        _check(LENET5_FUSION, 2, batch=3)

    def test_alexnet(self):
        _check(ALEXNET_FUSION, 1, tol=1e-4)

    def test_vgg_region19(self):
        _check(VGG_FUSION, 19, tol=1e-4)

    @pytest.mark.parametrize("blk", [0, 2, 4, 6])
    def test_resnet_blocks(self, blk):
        _check(resnet18_fusions()[blk], 4, tol=1e-4)

    def test_strided_inner_conv(self):
        spec = FusionSpec(
            levels=(
                FusedLevel("conv", 3, 2, 1, 2, 4),
                FusedLevel("conv", 3, 1, 1, 4, 4),
            ),
            input_size=17,
        )
        _check(spec, 3)

    def test_no_relu_mode(self):
        spec = FusionSpec(
            levels=tuple(
                dataclasses.replace(l, relu=False) for l in LENET5_FUSION.levels
            ),
            input_size=LENET5_FUSION.input_size,
        )
        params = init_pyramid_params(spec, KEY)
        x = jax.random.normal(jax.random.PRNGKey(2), (1, 32, 32, 1))
        ref = reference_forward(x, spec, params)
        fused = fused_forward(x, spec, params, lockstep_plan(spec, 1))
        np.testing.assert_allclose(np.asarray(fused), np.asarray(ref), atol=1e-5)


class TestConvWindows:
    def test_window_shape_and_content(self):
        spec = LENET5_FUSION
        x = jax.random.normal(jax.random.PRNGKey(3), (2, 32, 32, 1))
        win, n = conv_windows(x, spec, level=0)
        assert n == 28 * 28
        assert win.shape == (2, 28 * 28, 25)
        # first window must equal the top-left 5x5 patch
        np.testing.assert_allclose(
            np.asarray(win[0, 0]), np.asarray(x[0, :5, :5, 0]).reshape(-1), atol=1e-6
        )

    def test_subsampling(self):
        x = jax.random.normal(jax.random.PRNGKey(3), (1, 32, 32, 1))
        win, n = conv_windows(x, LENET5_FUSION, level=0, max_windows=100)
        assert win.shape[1] == 100
