"""Per-kernel validation: Pallas (interpret=True) vs pure-jnp oracles,
with shape/dtype sweeps per the kernel-deliverable contract."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.cnn_models import ALEXNET_FUSION, LENET5_FUSION
from repro.core.executor import init_pyramid_params
from repro.core.fusion import FusedLevel, FusionSpec
from repro.kernels.fused_conv.ops import fused_conv2, fused_pyramid
from repro.kernels.fused_conv.ref import fused_conv2_ref
from repro.kernels.online_sop.ops import online_sop_end
from repro.kernels.online_sop.ref import online_sop_end_ref

RNG = np.random.default_rng(3)
KEY = jax.random.PRNGKey(0)


class TestOnlineSopKernel:
    @pytest.mark.parametrize("m", [9, 25, 121, 363])
    @pytest.mark.parametrize("batch", [(7,), (3, 50)])
    def test_matches_ref_shapes(self, m, batch):
        x = (RNG.uniform(-0.9, 0.9, batch + (m,)) / m).astype(np.float32)
        y = (RNG.uniform(-0.9, 0.9, (m,))).astype(np.float32) / max(1, m // 8)
        sop_k, cyc_k, det_k = online_sop_end(jnp.asarray(x), jnp.asarray(y), 14)
        sop_r, cyc_r, det_r = online_sop_end_ref(jnp.asarray(x), jnp.asarray(y), 14)
        np.testing.assert_allclose(
            np.asarray(sop_k), np.asarray(sop_r), atol=1e-5
        )
        assert (np.asarray(det_k) == np.asarray(det_r)).all()
        assert (np.asarray(cyc_k) == np.asarray(cyc_r)).all()

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_dtype_sweep(self, dtype):
        x = (RNG.uniform(-0.5, 0.5, (64, 25)) / 25).astype(np.float32)
        y = RNG.uniform(-0.5, 0.5, (25,)).astype(np.float32) / 4
        sop_k, _, det_k = online_sop_end(
            jnp.asarray(x, dtype), jnp.asarray(y, dtype), 12
        )
        exact = (x * y).sum(-1)
        tol = 1e-5 if dtype == jnp.float32 else 5e-2
        np.testing.assert_allclose(np.asarray(sop_k), exact, atol=tol)

    def test_end_soundness_on_kernel(self):
        """Kernel-side Algorithm 2 must never flag a non-negative SOP."""
        x = (RNG.uniform(-0.9, 0.9, (2048, 25)) / 25).astype(np.float32)
        y = RNG.uniform(-0.9, 0.9, (25,)).astype(np.float32) / 4
        sop, _, det = online_sop_end(jnp.asarray(x), jnp.asarray(y), 16)
        sop, det = np.asarray(sop), np.asarray(det)
        assert not np.any(det & (sop >= 0))
        assert det[sop < -1e-3].mean() > 0.95  # detects clear negatives

    def test_n_digits_sweep(self):
        x = (RNG.uniform(-0.9, 0.9, (128, 9)) / 9).astype(np.float32)
        y = RNG.uniform(-0.9, 0.9, (9,)).astype(np.float32) / 2
        for nd in (8, 12, 20):
            _, cyc, det = online_sop_end(jnp.asarray(x), jnp.asarray(y), nd)
            assert int(np.asarray(cyc).max()) <= nd


def _input(spec, batch=1):
    return jax.random.normal(
        jax.random.PRNGKey(1),
        (batch, spec.input_size, spec.input_size, spec.levels[0].n_in),
    )


def _run_fused(spec, region, batch=1, end_skip=True, key=KEY, bias_shift=0.0):
    p = init_pyramid_params(spec, key)
    b1 = p.biases[0] + bias_shift
    x = _input(spec, batch)
    out, skip = fused_conv2(
        x, p.weights[0], b1, p.weights[1], p.biases[1],
        spec=spec, out_region=region, end_skip=end_skip,
    )
    ref = fused_conv2_ref(x, spec, p.weights[0], b1, p.weights[1], p.biases[1])
    return np.asarray(out), np.asarray(ref), np.asarray(skip)


class TestFusedConvKernel:
    def test_lenet_exact(self):
        out, ref, _ = _run_fused(LENET5_FUSION, 1, batch=2)
        np.testing.assert_allclose(out, ref, atol=1e-4)

    @pytest.mark.parametrize("region", [1, 13])
    def test_alexnet_regions(self, region):
        # the whole-image region lands all 259 padded rows of the 3-channel
        # input, lane-padded to 128: more than the default 16 MiB budget
        spec = ALEXNET_FUSION
        p = init_pyramid_params(spec, KEY)
        x = _input(spec)
        out, _ = fused_pyramid(
            x, p.weights, p.biases, spec=spec, out_region=region,
            vmem_budget=64 << 20,
        )
        ref = fused_conv2_ref(x, spec, p.weights[0], p.biases[0],
                              p.weights[1], p.biases[1])
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)

    @pytest.mark.parametrize(
        "k1,s1,p1,k2,s2,p2,size,region",
        [
            (3, 1, 1, 3, 1, 1, 16, 4),
            (5, 2, 0, 3, 1, 1, 21, 3),
            (3, 1, 1, 5, 1, 2, 12, 6),
            (1, 1, 0, 3, 2, 1, 15, 4),
        ],
    )
    def test_shape_sweep(self, k1, s1, p1, k2, s2, p2, size, region):
        spec = FusionSpec(
            levels=(
                FusedLevel("conv", k1, s1, p1, 3, 8),
                FusedLevel("conv", k2, s2, p2, 8, 4),
            ),
            input_size=size,
        )
        out_size = spec.feature_sizes()[-1]
        if out_size % region:
            pytest.skip("region does not tile output")
        out, ref, _ = _run_fused(spec, region)
        np.testing.assert_allclose(out, ref, atol=1e-4)

    def test_pool_variants(self):
        spec = FusionSpec(
            levels=(
                FusedLevel("conv", 3, 1, 1, 2, 6),
                FusedLevel("pool", 3, 2, 0, 6, 6),
                FusedLevel("conv", 3, 1, 1, 6, 8),
                FusedLevel("pool", 2, 2, 0, 8, 8),
            ),
            input_size=23,
        )
        out, ref, _ = _run_fused(spec, 1)
        np.testing.assert_allclose(out, ref, atol=1e-4)

    def test_end_skip_fires_and_stays_exact(self):
        """Strongly negative conv1 bias makes whole level-1 tiles zero after
        ReLU; the kernel must (a) fire skips and (b) remain bit-exact."""
        out, ref, skip = _run_fused(LENET5_FUSION, 1, bias_shift=-10.0)
        assert skip.sum() == skip.size  # every tile skipped
        np.testing.assert_allclose(out, ref, atol=1e-5)

    def test_end_skip_partial(self):
        """Spatially localized activity: tiles away from the active blob have
        all-zero post-ReLU level-1 tiles and skip; tiles over the blob
        compute — both paths must stay exact."""
        spec = LENET5_FUSION
        p = init_pyramid_params(spec, KEY)
        b1 = p.biases[0] - 0.5  # dead zones without input drive
        x = jnp.zeros((1, 32, 32, 1)).at[:, :8, :8, :].set(5.0)
        out, skip = fused_conv2(
            x, p.weights[0], b1, p.weights[1], p.biases[1],
            spec=spec, out_region=1,
        )
        ref = fused_conv2_ref(x, spec, p.weights[0], b1, p.weights[1], p.biases[1])
        skip = np.asarray(skip)
        assert 0 < skip.sum() < skip.size
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)

    def test_no_relu_disables_skip(self):
        """A level after a linear level never skips: its input is negative
        everywhere (bias shifted by -10), which a ReLU level would have
        zeroed and skipped."""
        spec = FusionSpec(
            levels=(
                FusedLevel("conv", 5, 1, 0, 1, 6, relu=False),
                FusedLevel("conv", 5, 1, 0, 6, 16, relu=False),
            ),
            input_size=32,
        )
        p = init_pyramid_params(spec, KEY)
        b1 = p.biases[0] - 10.0
        x = jax.random.normal(jax.random.PRNGKey(1), (1, 32, 32, 1))
        out, skip = fused_conv2(
            x, p.weights[0], b1, p.weights[1], p.biases[1],
            spec=spec, out_region=1,
        )
        ref = fused_conv2_ref(
            x, spec, p.weights[0], b1, p.weights[1], p.biases[1],
        )
        assert skip.sum() == 0
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


class TestFusedPyramidChain:
    def test_vgg_q4_chained_matches_single_launch(self):
        """The historical 2+2 chained path (USEFUSE's FPGA granularity,
        forced via ``max_convs_per_chunk=2``) and the new single-launch
        Q=4 path must both match the monolithic reference."""
        from repro.core.cnn_models import VGG_FUSION
        from repro.core.executor import reference_forward, PyramidParams
        from repro.kernels.fused_conv.ops import fused_pyramid_chain
        import dataclasses

        # reduced-size VGG-shaped chain (full 224x224 is slow in interpret)
        spec = dataclasses.replace(VGG_FUSION, input_size=32)
        p = init_pyramid_params(spec, KEY)
        x = jax.random.normal(jax.random.PRNGKey(5), (1, 32, 32, 3))
        y, skips = fused_pyramid_chain(
            x, p.weights, p.biases, spec=spec, out_regions=[8, 4],
            max_convs_per_chunk=2,
        )
        ref = reference_forward(x, spec, PyramidParams(p.weights, p.biases))
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=1e-3)
        assert len(skips) == 2

        y1, skips1 = fused_pyramid_chain(x, p.weights, p.biases, spec=spec)
        assert len(skips1) == 1, "VGG Q=4 must fit one kernel launch"
        np.testing.assert_allclose(np.asarray(y1), np.asarray(ref), atol=1e-3)
