"""Kernel memory-movement contracts (the halo-tiled dataflow PR):

* halo-tile byte model — per-launch input HBM traffic is
  ``alpha^2 * tile0^2 * C`` (tile + halo), not the retired whole-image
  ``alpha^2 * Hp * Wp * C``; ``launch_dataflow`` components sum to
  ``TileProgram.hbm_bytes`` so the OI bridge and the partitioner DP consume
  the same model;
* halo-tile correctness at image borders — per-grid-cell DMA fetches match
  the reference on edge tiles (i=0, i=alpha-1), strided + pooled levels, and
  batch > 1 (the manual DMA indexes the batch axis itself);
* streamed double-buffer parity — the two-slot prefetch pipeline is
  bit-identical to resident weights and to the single-slot fallback across
  Q=2/3/4, including END-cascade and mixed live/dead tiles (the speculative
  prefetch-drain and on-demand-fetch paths);
* the ``interpret=None`` resolver and once-per-model param preparation.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import resolve_interpret
from repro.core.cnn_models import LENET5_FUSION, VGG_FUSION, resnet18_fusions
from repro.core.executor import (
    PyramidParams,
    init_pyramid_params,
    reference_forward,
)
from repro.core.fusion import FusedLevel, FusionSpec
from repro.core.intensity import launch_dataflow
from repro.core.program import (
    VMEM_BUDGET_BYTES,
    compile_program,
    plan_launch,
)
from repro.kernels.fused_conv.ops import fused_pyramid
from repro.net.graph import lenet5, vgg16
from repro.net.partition import auto_partition
from repro.net.runner import (
    init_network_params,
    prepare_network_params,
    run_network,
)

KEY = jax.random.PRNGKey(0)

VGG_SMALL = dataclasses.replace(VGG_FUSION, input_size=32)

# conv+pool, conv, conv — strided pool epilogue plus an unpadded tail level
Q3_CHAIN = FusionSpec(
    levels=(
        FusedLevel("conv", K=3, S=1, pad=1, n_in=2, n_out=6),
        FusedLevel("pool", K=2, S=2, pad=0, n_in=6, n_out=6),
        FusedLevel("conv", K=3, S=1, pad=1, n_in=6, n_out=8),
        FusedLevel("conv", K=3, S=1, pad=0, n_in=8, n_out=4),
    ),
    input_size=20,
)

# strided conv (S=2) + pool: exercises non-unit o_step masking at borders
STRIDED_CHAIN = FusionSpec(
    levels=(
        FusedLevel("conv", K=3, S=2, pad=1, n_in=3, n_out=8),
        FusedLevel("pool", K=2, S=2, pad=0, n_in=8, n_out=8),
        FusedLevel("conv", K=3, S=1, pad=1, n_in=8, n_out=4),
    ),
    input_size=24,
)


def _inputs(spec, batch=1, seed=1):
    return jax.random.normal(
        jax.random.PRNGKey(seed),
        (batch, spec.input_size, spec.input_size, spec.levels[0].n_in),
    )


def _streamed(plan):
    """``plan`` with every pyramid pinned to double-buffered streamed
    weights.  Under the padded VMEM model LeNet's tiny weights never force
    streaming (a weight slot costs what the resident tensor does), so tests
    of the streamed-params path pin it."""
    return dataclasses.replace(plan, pyramids=tuple(
        dataclasses.replace(
            p, launch=dataclasses.replace(p.launch, streamed=True, w_slots=2)
        )
        for p in plan.pyramids
    ))


class TestHaloByteModel:
    def test_vgg16_input_traffic_drops_to_halo_tiles(self):
        """Acceptance: VGG-16 blocks 1-2 at 224^2 — modeled per-launch input
        HBM traffic is alpha^2*tile0^2*C*4 (halo-only overlap), down from the
        whole-image alpha^2*Hp*Wp*C*4."""
        lp = plan_launch(VGG_FUSION)
        prog = lp.program
        c0 = prog.levels[0].n_in
        halo = 4 * prog.alpha ** 2 * prog.tile0 ** 2 * c0
        whole = 4 * prog.alpha ** 2 * prog.padded_input ** 2 * c0
        assert prog.input_hbm_bytes() == halo
        assert prog.input_hbm_bytes(whole_image=True) == whole
        assert prog.alpha > 1 and halo < whole  # a real multi-cell reduction

    @pytest.mark.parametrize("streamed", [False, True])
    def test_launch_dataflow_components_sum_to_hbm_bytes(self, streamed):
        """The OI-bridge byte breakdown and the DP's cost model agree."""
        for spec in (LENET5_FUSION, VGG_FUSION, resnet18_fusions()[7]):
            prog = plan_launch(spec).program
            for batch in (1, 3):
                flow = launch_dataflow(prog, batch, streamed=streamed)
                total = (
                    flow["input_bytes_halo"]
                    + flow["weight_bytes"]
                    + flow["output_bytes"]
                    + flow["skip_bytes"]
                )
                assert total == prog.hbm_bytes(batch, streamed=streamed)

    def test_partitioner_consumes_halo_model(self):
        """The auto plan's modeled HBM is the sum of its launches' halo-model
        traffic — the DP optimizes the dataflow the kernel actually runs."""
        plan = auto_partition(vgg16())
        total = sum(
            p.launch.program.hbm_bytes(1, streamed=p.launch.streamed)
            for p in plan.pyramids
        )
        assert plan.hbm_bytes() == total
        halo_in = sum(
            p.launch.program.input_hbm_bytes(1) for p in plan.pyramids
        )
        whole_in = sum(
            p.launch.program.input_hbm_bytes(1, whole_image=True)
            for p in plan.pyramids
        )
        assert halo_in <= whole_in

    def test_double_buffer_costed_as_overlap(self):
        """Cycle model: double-buffered streaming (w_slots=2) is never slower
        than the blocking single slot, and resident pays no DMA term."""
        spec = resnet18_fusions()[7]
        lp = plan_launch(spec)
        assert lp.streamed
        db = dataclasses.replace(lp, w_slots=2)
        sb = dataclasses.replace(lp, w_slots=1)
        res = dataclasses.replace(lp, streamed=False, w_slots=1)
        assert db.modeled_cycles() <= sb.modeled_cycles()
        assert res.modeled_cycles() <= db.modeled_cycles()

    def test_stream_slots_ladder(self):
        """plan_launch prefers resident, then 2-slot streaming, then
        channel-tiled 2-slot streaming, then 1-slot; ResNet-18's 512-channel
        block cannot hold two whole copies of one 9.4 MB weight level in
        16 MiB, but two quarter slices fit — it lands on the channel-tiled
        double-buffered rung instead of the blocking single slot."""
        lp = plan_launch(resnet18_fusions()[7])
        assert lp.streamed and lp.w_slots == 2 and lp.c_tiles > 1
        # region preference stays primary: the largest region fits this
        # rung, so a smaller region must not be chosen to afford more slots
        assert lp.out_region == lp.spec.feature_sizes()[-1]
        prog = lp.program
        assert prog.vmem_stream_bytes(2) > VMEM_BUDGET_BYTES
        assert prog.vmem_stream_bytes(2, 1, lp.c_tiles) <= VMEM_BUDGET_BYTES
        assert prog.vmem_stream_bytes(1) <= VMEM_BUDGET_BYTES
        # the blocking single slot remains the terminal rung: under a budget
        # where even the finest channel slices bust two slots, w_slots == 1
        floor = prog.vmem_stream_bytes(1)
        tight = plan_launch(resnet18_fusions()[7], vmem_budget=floor)
        if tight is not None and tight.streamed and tight.c_tiles == 1:
            assert tight.w_slots == 1
        # a small chain that streams fits both slots untiled: 2 is chosen
        tiny = plan_launch(LENET5_FUSION, vmem_budget=40_000)
        if tiny is not None and tiny.streamed:
            assert tiny.w_slots == 2


@pytest.mark.slow
class TestHaloBorders:
    """Per-grid-cell halo DMA vs the monolithic reference at image borders:
    every (i, j) cell — including i=0 / i=alpha-1 edge tiles whose halos land
    in padding — must reproduce the reference exactly."""

    @pytest.mark.parametrize(
        "spec,region",
        [(Q3_CHAIN, 1), (Q3_CHAIN, 2), (STRIDED_CHAIN, 1), (STRIDED_CHAIN, 3)],
        ids=["q3_r1", "q3_r2", "strided_r1", "strided_r3"],
    )
    def test_edge_tiles_match_reference(self, spec, region):
        prog = compile_program(spec, region)
        assert prog.alpha > 1, "border test needs a multi-cell grid"
        p = init_pyramid_params(spec, KEY)
        x = _inputs(spec, batch=2)
        y, _ = fused_pyramid(
            x, p.weights, p.biases, spec=spec, out_region=region
        )
        ref = reference_forward(x, spec, PyramidParams(p.weights, p.biases))
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=1e-5)

    def test_batch_axis_dma_indexing(self):
        """Batch elements differ; the manual halo DMA must index batch b —
        a constant-index bug would smear batch 0 over the whole output."""
        spec = Q3_CHAIN
        p = init_pyramid_params(spec, KEY)
        x = jnp.stack(
            [jnp.zeros((20, 20, 2)), jnp.ones((20, 20, 2)), _inputs(spec)[0]]
        )
        y, _ = fused_pyramid(x, p.weights, p.biases, spec=spec, out_region=2)
        ref = reference_forward(x, spec, PyramidParams(p.weights, p.biases))
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=1e-5)
        assert not np.allclose(np.asarray(y)[0], np.asarray(y)[1])


@pytest.mark.slow
class TestStreamedDoubleBufferParity:
    """The double-buffered weight pipeline must be bit-identical to resident
    weights — same MXU inputs, only the movement schedule differs."""

    CASES = {
        "lenet_q2": (LENET5_FUSION, 1),
        "odd_q3": (Q3_CHAIN, 4),
        "vgg_q4": (VGG_SMALL, 4),
    }

    @pytest.mark.parametrize("name", sorted(CASES))
    @pytest.mark.parametrize("w_slots", [1, 2])
    def test_streamed_matches_resident_bitwise(self, name, w_slots):
        spec, region = self.CASES[name]
        p = init_pyramid_params(spec, KEY)
        x = _inputs(spec)
        y_res, s_res = fused_pyramid(
            x, p.weights, p.biases, spec=spec, out_region=region,
            streamed=False,
        )
        y_str, s_str = fused_pyramid(
            x, p.weights, p.biases, spec=spec, out_region=region,
            streamed=True, w_slots=w_slots,
        )
        np.testing.assert_array_equal(np.asarray(y_str), np.asarray(y_res))
        np.testing.assert_array_equal(np.asarray(s_str), np.asarray(s_res))

    def test_end_cascade_under_double_buffer(self):
        """Full END cascade with the prefetch pipeline: skipped levels take
        the drain path, output stays bit-identical, flags all set."""
        spec = Q3_CHAIN
        p = init_pyramid_params(spec, KEY)
        bs = [b - 10.0 for b in p.biases]
        x = _inputs(spec)
        y_res, _ = fused_pyramid(
            x, p.weights, bs, spec=spec, out_region=4, streamed=False
        )
        y_db, skip = fused_pyramid(
            x, p.weights, bs, spec=spec, out_region=4, streamed=True,
            w_slots=2,
        )
        np.testing.assert_array_equal(np.asarray(y_db), np.asarray(y_res))
        skip = np.asarray(skip)
        assert (skip[..., 1] == 1).all() and (skip[..., 2] == 1).all()

    def test_mixed_live_dead_tiles_under_double_buffer(self):
        """Sparse input yields a mix of live and dead tiles: exercises the
        speculative-prefetch drain (live level feeding a dead one) and the
        on-demand fetch (dead level feeding a live one via a positive-bias
        constant tile)."""
        spec = LENET5_FUSION
        p = init_pyramid_params(spec, KEY)
        bs = [p.biases[0] - 0.5, p.biases[1] + 0.3]
        blob = spec.input_size // 3
        x = jnp.zeros(
            (1, spec.input_size, spec.input_size, 1)
        ).at[:, :blob, :blob, :].set(5.0)
        y_res, s_res = fused_pyramid(
            x, p.weights, bs, spec=spec, out_region=1, streamed=False
        )
        y_db, s_db = fused_pyramid(
            x, p.weights, bs, spec=spec, out_region=1, streamed=True,
            w_slots=2,
        )
        np.testing.assert_array_equal(np.asarray(y_db), np.asarray(y_res))
        np.testing.assert_array_equal(np.asarray(s_db), np.asarray(s_res))
        frac = float(np.asarray(s_res)[..., 1].mean())
        assert 0.0 < frac < 1.0, "test needs mixed live/dead tiles"


class TestInterpretResolver:
    def test_explicit_values_pass_through(self):
        assert resolve_interpret(True) is True
        assert resolve_interpret(False) is False

    def test_none_resolves_from_backend(self):
        expect = jax.default_backend() != "tpu"
        assert resolve_interpret(None) is expect
        assert resolve_interpret() is expect


class TestPreparedParams:
    def test_prepare_network_params_roundtrip(self):
        """run_network with prepared params == without: preparation only
        casts, once, and adds no entries."""
        graph = lenet5()
        plan = _streamed(auto_partition(graph))
        params = init_network_params(graph, KEY)
        prepped = prepare_network_params(plan, params)
        assert prepped.keys() == params.keys()
        x = jax.random.normal(jax.random.PRNGKey(2), (2, 32, 32, 1))
        y0, _ = run_network(x, params, plan=plan)
        y1, _ = run_network(x, prepped, plan=plan)
        np.testing.assert_array_equal(np.asarray(y1), np.asarray(y0))

    def test_prepare_casts_without_touching_masters(self):
        graph = lenet5()
        plan = auto_partition(graph)
        params = init_network_params(graph, KEY)
        prepped = prepare_network_params(plan, params, "bfloat16")
        for k, (w, b) in prepped.items():
            assert w.dtype == b.dtype == jnp.bfloat16, k
            assert params[k][0].dtype == jnp.float32, k
