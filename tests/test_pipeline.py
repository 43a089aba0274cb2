"""Software-pipelined fusion pyramid (the cross-cell prefetch PR):

* bitwise parity — the revolving two-slot input landing buffer (``x_slots=2``)
  must be bit-identical to the serial fetch-then-compute path (``x_slots=1``)
  for Q=1 and Q=4, batch > 1, a 1x1 grid (``alpha=1``: no successor cell to
  prefetch), and an all-zero input whose END cascade skips every level >= 1
  (skipped cells still issue their successor's prefetch);
* the pipeline-aware cycle model — ``grid_pipeline_cycles`` timeline
  (warm-up fill, steady state, drain), pipelined <= serial on every zoo
  workload, equality at ``alpha == 1``, VMEM accounting of the extra landing
  slot, and the ``plan_launch`` ladder pinning ``x_slots``;
* the memoized ``auto_partition`` (same plan object back, distinct keys
  distinct) and the launch's one-tensor-per-level weight contract.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.cnn_models import (
    ALEXNET_FUSION,
    LENET5_FUSION,
    VGG_FUSION,
    resnet18_fusions,
)
from repro.core.cycle_model import grid_pipeline_cycles
from repro.core.executor import init_pyramid_params
from repro.core.fusion import FusedLevel, FusionSpec
from repro.core.program import compile_program, padded_bytes, plan_launch
from repro.kernels.fused_conv.ops import fused_pyramid
from repro.net.graph import MODELS, lenet5
from repro.net.partition import (
    auto_partition,
    clear_partition_cache,
    partition_cache_info,
)

KEY = jax.random.PRNGKey(0)

VGG_SMALL = dataclasses.replace(VGG_FUSION, input_size=32)

Q1_CHAIN = FusionSpec(
    levels=(FusedLevel("conv", K=3, S=1, pad=1, n_in=3, n_out=8),),
    input_size=12,
)

# conv+pool, conv, conv — at out_region=4 its input halo tile outweighs the
# largest weight level, the regime where w/x slot feasibility interact
Q3_CHAIN = FusionSpec(
    levels=(
        FusedLevel("conv", K=3, S=1, pad=1, n_in=2, n_out=6),
        FusedLevel("pool", K=2, S=2, pad=0, n_in=6, n_out=6),
        FusedLevel("conv", K=3, S=1, pad=1, n_in=6, n_out=8),
        FusedLevel("conv", K=3, S=1, pad=0, n_in=8, n_out=4),
    ),
    input_size=20,
)

ZOO_SPECS = {
    "lenet": LENET5_FUSION,
    "alexnet": ALEXNET_FUSION,
    "vgg_blocks12": VGG_FUSION,
    **{f"resnet18_b{i}": s for i, s in enumerate(resnet18_fusions())},
}


def _inputs(spec, batch=1, seed=1):
    return jax.random.normal(
        jax.random.PRNGKey(seed),
        (batch, spec.input_size, spec.input_size, spec.levels[0].n_in),
    )


def _run(spec, x, region, *, x_slots, streamed=False, w_slots=None,
         biases=None):
    p = init_pyramid_params(spec, KEY)
    return fused_pyramid(
        x, p.weights, biases if biases is not None else p.biases, spec=spec,
        out_region=region, x_slots=x_slots, streamed=streamed,
        w_slots=w_slots,
    )


@pytest.mark.slow
class TestPipelinedParity:
    """x_slots=2 must be bit-identical to x_slots=1 — same MXU inputs, only
    the input-tile movement schedule differs."""

    CASES = {
        "q1": (Q1_CHAIN, 3),
        "q2_lenet": (LENET5_FUSION, 1),
        "q4_vgg": (VGG_SMALL, 4),
    }

    @pytest.mark.parametrize("name", sorted(CASES))
    @pytest.mark.parametrize("batch", [1, 3])
    def test_pipelined_matches_serial_bitwise(self, name, batch):
        spec, region = self.CASES[name]
        x = _inputs(spec, batch=batch)
        y1, s1 = _run(spec, x, region, x_slots=1)
        y2, s2 = _run(spec, x, region, x_slots=2)
        np.testing.assert_array_equal(np.asarray(y2), np.asarray(y1))
        np.testing.assert_array_equal(np.asarray(s2), np.asarray(s1))

    @pytest.mark.parametrize("w_slots", [1, 2])
    def test_pipelined_with_streamed_weights(self, w_slots):
        """Both DMA pipelines at once: revolving input landing buffer plus
        double-buffered (or blocking) weight streaming."""
        spec, region = VGG_SMALL, 4
        x = _inputs(spec, batch=2)
        y_res, s_res = _run(spec, x, region, x_slots=1)
        y_pipe, s_pipe = _run(
            spec, x, region, x_slots=2, streamed=True, w_slots=w_slots
        )
        np.testing.assert_array_equal(np.asarray(y_pipe), np.asarray(y_res))
        np.testing.assert_array_equal(np.asarray(s_pipe), np.asarray(s_res))

    def test_alpha1_no_successor_cell(self):
        """A 1x1 grid has no successor: the pipelined kernel degenerates to
        warm-up + compute and must still match (per batch element)."""
        spec = LENET5_FUSION
        out_size = spec.feature_sizes()[-1]
        assert compile_program(spec, out_size).alpha == 1
        x = _inputs(spec, batch=2)
        y1, s1 = _run(spec, x, out_size, x_slots=1)
        y2, s2 = _run(spec, x, out_size, x_slots=2)
        np.testing.assert_array_equal(np.asarray(y2), np.asarray(y1))
        np.testing.assert_array_equal(np.asarray(s2), np.asarray(s1))

    def test_all_zero_input_end_skips_every_level(self):
        """An all-zero image with non-positive biases END-skips every level
        >= 1 of every cell; skipped cells must still chain the successor
        prefetch (a stalled pipeline would deadlock/mismatch)."""
        spec = VGG_SMALL
        p = init_pyramid_params(spec, KEY)
        bs = [b - 10.0 for b in p.biases]
        x = jnp.zeros((2, spec.input_size, spec.input_size, 3))
        y1, s1 = fused_pyramid(
            x, p.weights, bs, spec=spec, out_region=4, x_slots=1
        )
        y2, s2 = fused_pyramid(
            x, p.weights, bs, spec=spec, out_region=4, x_slots=2
        )
        np.testing.assert_array_equal(np.asarray(y2), np.asarray(y1))
        np.testing.assert_array_equal(np.asarray(s2), np.asarray(s1))
        assert (np.asarray(s2)[..., 1:] == 1).all(), "cascade must skip all"

    def test_batch_boundary_chain_reset(self):
        """Batch elements differ; the prefetch chain resets at every batch
        boundary, so no batch element may see its neighbour's tiles."""
        spec = LENET5_FUSION
        p = init_pyramid_params(spec, KEY)
        x = jnp.stack(
            [jnp.zeros((32, 32, 1)), jnp.ones((32, 32, 1)), _inputs(spec)[0]]
        )
        y1, _ = fused_pyramid(x, p.weights, p.biases, spec=spec, out_region=1,
                              x_slots=1)
        y2, _ = fused_pyramid(x, p.weights, p.biases, spec=spec, out_region=1,
                              x_slots=2)
        np.testing.assert_array_equal(np.asarray(y2), np.asarray(y1))
        assert not np.allclose(np.asarray(y2)[0], np.asarray(y2)[1])


class TestPipelineCycleModel:
    def test_timeline_phases(self):
        """warm-up fill + drain + steady state: the pipelined timeline is
        fill + body + (cells-1)*max(body, fill)."""
        assert grid_pipeline_cycles(4, 10, 3, pipelined=False) == 4 * 13
        assert grid_pipeline_cycles(4, 10, 3, pipelined=True) == 3 + 10 + 3 * 10
        # DMA-bound grid: compute hides behind the fetch instead
        assert grid_pipeline_cycles(4, 3, 10, pipelined=True) == 10 + 3 + 3 * 10
        # degenerate single-cell grid: nothing to overlap
        assert grid_pipeline_cycles(1, 10, 3, pipelined=True) == 13
        assert grid_pipeline_cycles(1, 10, 3, pipelined=False) == 13

    def test_saving_is_min_term(self):
        serial = grid_pipeline_cycles(9, 7, 5, pipelined=False)
        pipe = grid_pipeline_cycles(9, 7, 5, pipelined=True)
        assert serial - pipe == (9 - 1) * min(7, 5)

    @pytest.mark.parametrize("name", sorted(ZOO_SPECS))
    def test_pipelined_never_slower_on_zoo(self, name):
        """Acceptance: modeled_cycles(pipelined) <= serial model on every zoo
        workload, strictly better whenever there is a successor cell."""
        lp = plan_launch(ZOO_SPECS[name])
        assert lp is not None
        pipe = dataclasses.replace(lp, x_slots=2)
        serial = dataclasses.replace(lp, x_slots=1)
        for batch in (1, 4):
            assert pipe.modeled_cycles(batch) <= serial.modeled_cycles(batch)
            if lp.program.alpha > 1:
                assert pipe.modeled_cycles(batch) < serial.modeled_cycles(batch)
            else:
                assert pipe.modeled_cycles(batch) == serial.modeled_cycles(batch)

    def test_serial_model_charges_input_dma(self):
        """The serial regime now costs (input_dma + body) per cell — the
        input fetch is no longer modeled as free."""
        lp = plan_launch(VGG_FUSION)
        serial = dataclasses.replace(lp, x_slots=1)
        cells = lp.program.alpha ** 2
        body_only = serial.modeled_cycles() - cells * lp.program.input_dma_cycles()
        assert body_only > 0
        assert serial.modeled_cycles() > body_only

    def test_vmem_accounts_extra_landing_slot(self):
        prog = plan_launch(VGG_FUSION).program
        extra = padded_bytes(
            (prog.tile0, prog.input_window(), prog.input_lanes()), "float32"
        )
        assert prog.vmem_bytes(2) - prog.vmem_bytes(1) == extra
        assert (
            prog.vmem_stream_bytes(1, 2) - prog.vmem_stream_bytes(1, 1) == extra
        )

    def test_plan_launch_pins_x_slots(self):
        """Ladder: multi-cell grids that fit the extra slot get x_slots=2;
        a 1x1 grid pins x_slots=1 (nothing to prefetch)."""
        vgg = plan_launch(VGG_FUSION)
        assert vgg.program.alpha > 1 and vgg.x_slots == 2
        lenet = plan_launch(LENET5_FUSION)
        assert lenet.program.alpha == 1 and lenet.x_slots == 1

    def test_pinned_x_slots_derives_jointly_feasible_w_slots(self):
        """With x_slots pinned to 2 and w_slots left to derive, the derived
        weight regime must be feasible *jointly* with the extra landing slot:
        under a budget where (w=2, x=2) busts but (w=1, x=2) fits, the
        launch must fall back to w_slots=1 instead of dying on the VMEM
        assert."""
        region = 4
        prog = compile_program(Q3_CHAIN, region)
        budget = prog.vmem_stream_bytes(1, 2)
        assert prog.vmem_stream_bytes(2, 1) <= budget  # x1 accounting says w2
        assert prog.vmem_stream_bytes(2, 2) > budget  # but jointly it busts
        p = init_pyramid_params(Q3_CHAIN, KEY)
        x = _inputs(Q3_CHAIN)
        y, s = fused_pyramid(
            x, p.weights, p.biases, spec=Q3_CHAIN, out_region=region,
            streamed=True, x_slots=2, vmem_budget=budget,
        )
        y_ref, s_ref = fused_pyramid(
            x, p.weights, p.biases, spec=Q3_CHAIN, out_region=region,
            streamed=False,
        )
        np.testing.assert_array_equal(np.asarray(y), np.asarray(y_ref))
        np.testing.assert_array_equal(np.asarray(s), np.asarray(s_ref))

    def test_pinned_x_slots_flows_into_stream_decision(self):
        """With x_slots pinned to 2 and streamed left to derive, the
        resident-vs-streamed decision must charge the extra landing slot:
        under a budget where resident+x2 busts but streamed+x2 fits, the
        launch must stream instead of dying on the VMEM assert."""
        region = 4
        # level 0 in direct form (9 x 64 input lanes > PATCH_MAX_LANES): its
        # weights set the streamed ring slot, so streaming frees more VMEM
        # than it takes, as the regime ladder assumes
        spec = dataclasses.replace(
            Q3_CHAIN,
            levels=(
                dataclasses.replace(Q3_CHAIN.levels[0], n_in=64),
                *Q3_CHAIN.levels[1:],
            ),
        )
        prog = compile_program(spec, region)
        assert not prog.patch
        budget = prog.vmem_bytes(2) - 4
        assert prog.vmem_bytes(1) <= budget  # x1 accounting says resident
        assert prog.vmem_stream_bytes(1, 2) <= budget  # streamed+x2 fits
        p = init_pyramid_params(spec, KEY)
        x = _inputs(spec)
        y, s = fused_pyramid(
            x, p.weights, p.biases, spec=spec, out_region=region,
            x_slots=2, vmem_budget=budget,
        )
        y_ref, s_ref = fused_pyramid(
            x, p.weights, p.biases, spec=spec, out_region=region,
        )
        np.testing.assert_array_equal(np.asarray(y), np.asarray(y_ref))
        np.testing.assert_array_equal(np.asarray(s), np.asarray(s_ref))

    def test_with_input_pipeline_respects_buildability(self):
        """The serial-vs-pipelined benchmark comparison uses the planner's
        own ladder rule: alpha == 1 or a busted landing slot returns the
        plan unchanged."""
        vgg = plan_launch(VGG_FUSION)
        assert vgg.with_input_pipeline().x_slots == 2
        lenet = plan_launch(LENET5_FUSION)  # alpha == 1
        assert lenet.with_input_pipeline() is lenet
        # a budget with no headroom for the extra slot keeps x_slots=1
        serial = dataclasses.replace(vgg, x_slots=1)
        assert serial.with_input_pipeline(serial.vmem_bytes()) is serial

    def test_partition_dp_consumes_pipelined_cost(self):
        """The DP's latency tiebreaker sums the launches' pipeline-aware
        cycles (not a stale serial model)."""
        plan = auto_partition(MODELS["vgg16"]())
        assert plan.modeled_cycles() == sum(
            p.launch.modeled_cycles(plan.batch) for p in plan.pyramids
        )
        serial = sum(
            dataclasses.replace(p.launch, x_slots=1).modeled_cycles(plan.batch)
            for p in plan.pyramids
        )
        assert plan.modeled_cycles() <= serial


class TestPartitionMemoization:
    def test_same_key_returns_same_plan_object(self):
        clear_partition_cache()
        g = lenet5()
        p1 = auto_partition(g)
        p2 = auto_partition(g)
        assert p1 is p2  # cache hit: identical object, stable jit identity
        info = partition_cache_info()
        assert info.hits >= 1 and info.misses >= 1

    def test_structurally_equal_graphs_share_a_plan(self):
        """Graphs are frozen dataclasses: two independently-built but equal
        graphs hash alike, so the DP runs once for both."""
        clear_partition_cache()
        p1 = auto_partition(lenet5())
        p2 = auto_partition(lenet5())
        assert p1 is p2

    def test_distinct_keys_distinct_plans(self):
        g = lenet5()
        p1 = auto_partition(g)
        p2 = auto_partition(g, batch=4)
        p3 = auto_partition(g, vmem_budget=8 << 20)
        assert p1 is not p2 and p1 is not p3
        assert p2.batch == 4 and p3.vmem_budget == 8 << 20


class TestWeightsAPI:
    def test_streamed_matches_resident(self):
        """The same per-level weight tensors feed both regimes, bitwise."""
        spec = LENET5_FUSION
        p = init_pyramid_params(spec, KEY)
        x = _inputs(spec)
        y0, s0 = fused_pyramid(
            x, p.weights, p.biases, spec=spec, out_region=1, streamed=False
        )
        y1, s1 = fused_pyramid(
            x, p.weights, p.biases, spec=spec, out_region=1, streamed=True
        )
        np.testing.assert_array_equal(np.asarray(y1), np.asarray(y0))
        np.testing.assert_array_equal(np.asarray(s1), np.asarray(s0))

    @pytest.mark.parametrize("streamed", [False, True])
    def test_one_weight_tensor_per_level(self, streamed):
        spec = LENET5_FUSION
        p = init_pyramid_params(spec, KEY)
        x = _inputs(spec)
        with pytest.raises(AssertionError, match="one weight tensor"):
            fused_pyramid(
                x, p.weights[:1], p.biases, spec=spec, out_region=1,
                streamed=streamed,
            )
