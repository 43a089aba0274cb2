"""Variadic fusion-pyramid kernel: single-launch parity across depths
(Q=2/3/4, strided ResNet blocks), cascaded END skip flags vs reference
intermediates and Algorithm-2 END detection, and VMEM-driven chunking."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.cnn_models import (
    LENET5_FUSION,
    VGG_FUSION,
    resnet18_fusions,
)
from repro.core.end_detect import end_scan
from repro.core.executor import (
    PyramidParams,
    _conv2d,
    fused_forward,
    init_pyramid_params,
    reference_forward,
)
from repro.core.fusion import FusedLevel, FusionSpec, lockstep_plan
from repro.core.online_arith import to_digits
from repro.core.program import compile_program, pick_out_region
from repro.kernels.fused_conv.ops import (
    fused_pyramid,
    fused_pyramid_chain,
    plan_chunks,
)

KEY = jax.random.PRNGKey(0)

VGG_SMALL = dataclasses.replace(VGG_FUSION, input_size=32)  # Q=4, fast in interpret

# synthetic odd-Q chain: conv+pool, conv, conv (Q=3) — the shape the old
# 2-conv kernel could not express and the old chain rejected outright
Q3_CHAIN = FusionSpec(
    levels=(
        FusedLevel("conv", K=3, S=1, pad=1, n_in=2, n_out=6),
        FusedLevel("pool", K=2, S=2, pad=0, n_in=6, n_out=6),
        FusedLevel("conv", K=3, S=1, pad=1, n_in=6, n_out=8),
        FusedLevel("conv", K=3, S=1, pad=0, n_in=8, n_out=4),
    ),
    input_size=20,
)

# (spec, out_region, atol) — the acceptance set: each must run as ONE launch
PARITY_CASES = {
    "lenet_q2": (LENET5_FUSION, 1, 1e-5),
    "odd_q3": (Q3_CHAIN, 4, 1e-5),
    "vgg_q4": (VGG_SMALL, 4, 1e-5),
    "resnet18_strided_blk": (resnet18_fusions()[2], 14, 1e-4),
}


def _inputs(spec, batch=1, seed=1):
    return jax.random.normal(
        jax.random.PRNGKey(seed),
        (batch, spec.input_size, spec.input_size, spec.levels[0].n_in),
    )


class TestSingleLaunchParity:
    @pytest.mark.parametrize("name", sorted(PARITY_CASES))
    def test_kernel_vs_fused_vs_reference(self, name):
        """Kernel == fused executor == monolithic reference, one launch."""
        spec, region, atol = PARITY_CASES[name]
        assert len(plan_chunks(spec)) == 1, "must fit a single kernel launch"
        p = init_pyramid_params(spec, KEY)
        x = _inputs(spec)
        y, skip = fused_pyramid(x, p.weights, p.biases, spec=spec, out_region=region)
        ref = reference_forward(x, spec, PyramidParams(p.weights, p.biases))
        fused = fused_forward(
            x, spec, PyramidParams(p.weights, p.biases), lockstep_plan(spec, region)
        )
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=atol)
        np.testing.assert_allclose(np.asarray(fused), np.asarray(ref), atol=atol)
        alpha = spec.feature_sizes()[-1] // region
        assert skip.shape == (1, alpha, alpha, spec.q_convs)  # per-level maps

    def test_full_scale_specs_plan_single_launch(self):
        """At paper scale (224^2 VGG, all ResNet-18 blocks) the compiler still
        finds a VMEM-feasible single-launch program — no forced chunking."""
        assert len(plan_chunks(VGG_FUSION)) == 1
        for spec in resnet18_fusions():
            assert len(plan_chunks(spec)) == 1

    def test_resnet_last_block_streams_weights(self):
        """ResNet-18's 512-channel block busts resident VMEM (two 3x3x512x512
        weight tensors alone > 16 MiB) but fits with per-level streaming, and
        the streamed kernel stays exact."""
        spec = resnet18_fusions()[7]
        region = pick_out_region(spec)
        prog = compile_program(spec, region)
        assert prog.vmem_bytes() > 16 * 1024 * 1024
        assert prog.vmem_stream_bytes() < 16 * 1024 * 1024
        p = init_pyramid_params(spec, KEY)
        x = _inputs(spec)
        y, _ = fused_pyramid(x, p.weights, p.biases, spec=spec, out_region=region)
        ref = reference_forward(x, spec, PyramidParams(p.weights, p.biases))
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=1e-4)


def _conv_group_ends(spec):
    """Spec-level index just past each conv's group (conv + trailing pools)."""
    ends, cur = [], 0
    for l, lvl in enumerate(spec.levels):
        if lvl.kind == "conv" and cur:
            ends.append(cur)
        cur = l + 1
    ends.append(cur)
    return ends


def _expected_skip_maps(spec, weights, biases, x, region):
    """Dead-tile maps from reference intermediates: the kernel must flag conv
    level l+1 exactly where the post-level-l tile (mask + pool applied, i.e.
    the window of the reference map clipped to the valid range) is all zero."""
    prog = compile_program(spec, region)
    ends = _conv_group_ends(spec)
    maps = []
    for ci, end in enumerate(ends):
        sub = FusionSpec(levels=spec.levels[:end], input_size=spec.input_size)
        params = PyramidParams(list(weights[: ci + 1]), list(biases[: ci + 1]))
        maps.append(np.asarray(reference_forward(x, sub, params)))
    expected = np.zeros((prog.alpha, prog.alpha, prog.q_convs), np.int32)
    for l in range(prog.q_convs - 1):
        p = prog.levels[l]
        if p.pool is not None:
            ob, os_, n, valid = p.pool_o_base, p.pool_o_step, p.pool_out, p.pool_valid
        else:
            ob, os_, n, valid = p.o_base, p.o_step, p.out_size, p.valid
        for i in range(prog.alpha):
            for j in range(prog.alpha):
                r0, c0 = ob + i * os_, ob + j * os_
                sub = maps[l][
                    0,
                    max(r0, 0) : min(r0 + n, valid),
                    max(c0, 0) : min(c0 + n, valid),
                    :,
                ]
                if sub.size == 0 or sub.max() <= 0.0:
                    expected[i, j, l + 1] = 1
    return expected, prog


class TestEndCascade:
    def test_full_cascade_all_levels_skip(self):
        """Strongly negative biases kill every level: level 1's input tile is
        all zero, its closed form relu(b) is zero too, so the cascade
        short-circuits the whole remaining pyramid — and stays bit-exact."""
        spec = Q3_CHAIN
        p = init_pyramid_params(spec, KEY)
        bs = [b - 10.0 for b in p.biases]
        x = _inputs(spec)
        y, skip = fused_pyramid(x, p.weights, bs, spec=spec, out_region=4)
        ref = reference_forward(x, spec, PyramidParams(p.weights, bs))
        skip = np.asarray(skip)
        assert (skip[..., 0] == 0).all()  # level 0 always computes
        assert (skip[..., 1] == 1).all()
        assert (skip[..., 2] == 1).all()  # cascaded: const tile is zero too
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=1e-5)

    @pytest.mark.parametrize(
        "spec,region,shift",
        [(LENET5_FUSION, 1, -0.5), (Q3_CHAIN, 1, -0.4)],
        ids=["lenet_q2", "odd_q3"],
    )
    def test_skip_flags_match_reference_dead_tiles(self, spec, region, shift):
        """Per-level skip flags == dead-tile maps from reference
        intermediates, on spatially sparse input with mixed live/dead tiles;
        output stays exact on both paths."""
        p = init_pyramid_params(spec, KEY)
        bs = [b + shift for b in p.biases]
        blob = spec.input_size // 3
        x = jnp.zeros(
            (1, spec.input_size, spec.input_size, spec.levels[0].n_in)
        ).at[:, :blob, :blob, :].set(5.0)
        y, skip = fused_pyramid(x, p.weights, bs, spec=spec, out_region=region)
        expected, _ = _expected_skip_maps(spec, p.weights, bs, x, region)
        np.testing.assert_array_equal(np.asarray(skip)[0], expected)
        assert 0 < expected[..., 1].sum() < expected[..., 1].size, (
            "test needs mixed live/dead tiles to be meaningful"
        )
        ref = reference_forward(x, spec, PyramidParams(p.weights, bs))
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=1e-5)

    def test_skip_flags_agree_with_end_detect(self):
        """A tile skips at level 1 iff no SOP of conv level 0 in its window is
        positive — exactly the population Algorithm 2 (END) classifies.  The
        kernel's skip count must equal the count of tiles whose every SOP is
        END-detected-negative or non-positive, and END must stay sound."""
        spec = LENET5_FUSION
        p = init_pyramid_params(spec, KEY)
        bs = [p.biases[0] - 0.5, p.biases[1]]
        blob = spec.input_size // 3
        x = jnp.zeros((1, spec.input_size, spec.input_size, 1))
        x = x.at[:, :blob, :blob, :].set(5.0)
        region = 1
        _, skip = fused_pyramid(x, p.weights, bs, spec=spec, out_region=region)
        skip = np.asarray(skip)[0]
        prog = compile_program(spec, region)
        lvl0, p0 = prog.levels[0], spec.levels[0]
        # pre-ReLU SOPs of conv level 0 over the whole map
        z0 = np.asarray(_conv2d(x, p.weights[0], bs[0], p0.S, p0.pad))[0]
        end_dead = np.zeros((prog.alpha, prog.alpha), np.int32)
        for i in range(prog.alpha):
            for j in range(prog.alpha):
                r0 = lvl0.o_base + i * lvl0.o_step
                c0 = lvl0.o_base + j * lvl0.o_step
                sub = z0[
                    max(r0, 0) : min(r0 + lvl0.out_size, lvl0.valid),
                    max(c0, 0) : min(c0 + lvl0.out_size, lvl0.valid),
                    :,
                ].reshape(-1)
                if sub.size == 0:
                    end_dead[i, j] = 1
                    continue
                scale = 2.0 * max(1.0, float(np.abs(sub).max()))
                det, _ = end_scan(to_digits(jnp.asarray(sub / scale), 24))
                det = np.asarray(det)
                # Algorithm 2 soundness: a flagged SOP is strictly negative
                assert not np.any(det & (sub >= 0))
                # tile is END-dead iff every SOP is detected-negative or <= 0
                end_dead[i, j] = int(np.all(det | (sub <= 0)))
        np.testing.assert_array_equal(skip[..., 1], end_dead)
        assert skip[..., 1].sum() == end_dead.sum()
        assert 0 < end_dead.sum() < end_dead.size


class TestChainChunking:
    def test_odd_q_single_chunk_regression(self):
        """Regression for the old hard error: `fused_pyramid_chain` asserted
        an even conv count, so any odd-Q chain died.  Odd Q now runs — as a
        single launch when VMEM allows."""
        p = init_pyramid_params(Q3_CHAIN, KEY)
        x = _inputs(Q3_CHAIN)
        y, skips = fused_pyramid_chain(x, p.weights, p.biases, spec=Q3_CHAIN)
        assert len(skips) == 1 and skips[0].shape[-1] == 3
        ref = reference_forward(x, Q3_CHAIN, PyramidParams(p.weights, p.biases))
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=1e-5)

    def test_odd_q_capped_chunks_leave_remainder(self):
        """With an explicit Q=2 cap the odd conv becomes a final Q=1 chunk
        instead of a hard error."""
        p = init_pyramid_params(Q3_CHAIN, KEY)
        x = _inputs(Q3_CHAIN)
        chunks = plan_chunks(Q3_CHAIN, max_convs_per_chunk=2)
        assert [c.q_convs for c in chunks] == [2, 1]
        y, skips = fused_pyramid_chain(
            x, p.weights, p.biases, spec=Q3_CHAIN, max_convs_per_chunk=2
        )
        assert len(skips) == 2
        ref = reference_forward(x, Q3_CHAIN, PyramidParams(p.weights, p.biases))
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=1e-5)

    def test_infeasible_budget_raises_clearly(self):
        """A budget too small for even one conv group is a planning error,
        not a crash inside the launch with circular 'go chunk' advice."""
        with pytest.raises(ValueError, match="does not fit .* even alone"):
            plan_chunks(LENET5_FUSION, vmem_budget=1024)

    def test_tiny_vmem_budget_forces_chunking(self):
        """The chain chunks exactly when the budget forces it: a budget too
        small for the fused working set splits the chain, and the chunked
        result still matches the reference."""
        spec = Q3_CHAIN
        single = plan_chunks(spec)
        assert len(single) == 1
        out_size = spec.feature_sizes()[-1]
        # one byte under the smallest working set of any single launch:
        # with level 0 in patch form the streamed ring slot (sized for the
        # largest K and the largest Cin) can outgrow the resident weights
        budget = min(
            min(prog.vmem_bytes(), prog.vmem_stream_bytes(),
                *(prog.vmem_stream_bytes(1, 1, ct)
                  for ct in prog.c_tile_options()))
            for prog in (
                compile_program(spec, r)
                for r in range(1, out_size + 1)
                if out_size % r == 0
            )
        ) - 1
        forced = plan_chunks(spec, vmem_budget=budget)
        assert len(forced) > 1
        p = init_pyramid_params(spec, KEY)
        x = _inputs(spec)
        y, skips = fused_pyramid_chain(
            x, p.weights, p.biases, spec=spec, vmem_budget=budget
        )
        assert len(skips) == len(forced)
        ref = reference_forward(x, spec, PyramidParams(p.weights, p.biases))
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=1e-5)
