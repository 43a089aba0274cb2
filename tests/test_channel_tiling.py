"""Output-channel-tiled fusion grid (the ``c_tiles`` PR):

* bitwise parity — the channel-tiled ``(B, alpha, alpha, c_tiles)`` grid
  must be bit-identical to the untiled ``c_tiles=1`` path across Q=1/3/4,
  resident and streamed weights, both ``w_slots`` regimes, both ``x_slots``
  regimes, the END cascade (all-dead and mixed live/dead tiles), and
  ``alpha == 1`` grids;
* the planner ladder — ResNet-18 b7 (whose two 9.4 MB weight levels bust
  double-buffered streaming untiled) now lands on the channel-tiled
  ``streamed w_slots=2`` rung with ``pipeline_cycles_saved > 0`` at ``alpha
  == 1``, the regime PR 4's cross-cell prefetch could not touch;
* the k-axis cost model — ``channel_tiled_body_cycles`` fill/steady/drain
  timeline, the ds1 mid/last compute split, HBM-traffic invariance of
  channel tiling, and VMEM accounting of the slice slots;
* zoo-wide feasibility — ``plan_launch`` never returns a plan whose
  ``vmem_bytes()`` exceeds the budget it was given (hypothesis sweep over
  random budgets plus the default-budget zoo);
* the hypothesis regime sweep — random Q in 1..4 pyramids, random
  ``(x_slots, w_slots, c_tiles)``, bitwise equal to the resident untiled
  serial path;
* the launch's argument checks (tile count, landing slots, bias count).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.cnn_models import (
    ALEXNET_FUSION,
    LENET5_FUSION,
    VGG_FUSION,
    resnet18_fusions,
)
from repro.core.cycle_model import (
    channel_tiled_body_cycles,
    ds1_cycles_per_movement,
    ds1_split_cycles_per_movement,
)
from repro.core.executor import init_pyramid_params
from repro.core.fusion import FusedLevel, FusionSpec
from repro.core.program import (
    LANES,
    VMEM_BUDGET_BYTES,
    channel_blocks,
    compile_program,
    plan_launch,
)
from repro.kernels.fused_conv.ops import fused_pyramid
from repro.net.graph import lenet5
from repro.net.partition import auto_partition
from repro.net.runner import (
    init_network_params,
    prepare_network_params,
    run_network,
)

KEY = jax.random.PRNGKey(0)

VGG_SMALL = dataclasses.replace(VGG_FUSION, input_size=32)

Q1_CHAIN = FusionSpec(
    levels=(FusedLevel("conv", K=3, S=1, pad=1, n_in=3, n_out=8),),
    input_size=12,
)

# conv+pool, conv, conv — the odd-Q chain of the dataflow suites
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


def _run(spec, x, region, *, biases=None, **kw):
    p = init_pyramid_params(spec, KEY)
    return fused_pyramid(
        x, p.weights, biases if biases is not None else p.biases, spec=spec,
        out_region=region, **kw,
    )


@pytest.mark.slow
class TestChannelTiledParity:
    """c_tiles > 1 must be bit-identical to the untiled path — same MXU
    inputs per channel block, only the movement schedule differs."""

    CASES = {
        "q1": (Q1_CHAIN, 3, 2),
        "q3": (Q3_CHAIN, 4, 2),
        "q4_vgg": (VGG_SMALL, 4, 4),
    }

    @pytest.mark.parametrize("name", sorted(CASES))
    @pytest.mark.parametrize("streamed", [False, True])
    @pytest.mark.parametrize("w_slots", [1, 2])
    def test_tiled_matches_untiled_bitwise(self, name, streamed, w_slots):
        spec, region, ct = self.CASES[name]
        x = _inputs(spec, batch=2)
        y0, s0 = _run(spec, x, region, x_slots=1)
        y1, s1 = _run(
            spec, x, region, x_slots=2, streamed=streamed,
            w_slots=w_slots if streamed else None, c_tiles=ct,
        )
        np.testing.assert_array_equal(np.asarray(y1), np.asarray(y0))
        np.testing.assert_array_equal(np.asarray(s1), np.asarray(s0))

    def test_finest_legal_tiling(self):
        """c_tiles == Cout/2: the finest legal slicing (two channels per k;
        one-channel slices are excluded — the degenerate one-column dot
        reassociates and would break bit parity)."""
        spec, region = Q1_CHAIN, 3
        ct = compile_program(spec, region).c_tile_options()[-1]
        assert ct == spec.levels[-1].n_out // 2
        x = _inputs(spec)
        y0, s0 = _run(spec, x, region, x_slots=1)
        y1, s1 = _run(spec, x, region, streamed=True, w_slots=2, c_tiles=ct)
        np.testing.assert_array_equal(np.asarray(y1), np.asarray(y0))
        np.testing.assert_array_equal(np.asarray(s1), np.asarray(s0))

    def test_one_channel_slices_rejected(self):
        with pytest.raises(AssertionError, match=">= 2 channels"):
            _run(Q1_CHAIN, _inputs(Q1_CHAIN), 3, streamed=True, w_slots=2,
                 c_tiles=8)

    def test_alpha1_grid(self):
        """alpha == 1 + c_tiles > 1: the k axis is the only multi-step grid
        dimension — exactly the launches channel tiling exists for."""
        spec = LENET5_FUSION
        out_size = spec.feature_sizes()[-1]
        assert compile_program(spec, out_size).alpha == 1
        x = _inputs(spec, batch=2)
        y0, s0 = _run(spec, x, out_size, x_slots=1)
        y1, s1 = _run(
            spec, x, out_size, streamed=True, w_slots=2, c_tiles=4
        )
        np.testing.assert_array_equal(np.asarray(y1), np.asarray(y0))
        np.testing.assert_array_equal(np.asarray(s1), np.asarray(s0))

    def test_end_cascade_all_dead(self):
        """All-zero input with non-positive biases: every level >= 1 of every
        cell skips; the per-k slice fetches drain unconditionally and the
        flag vector (written once at k == 0) must match the untiled path."""
        spec = VGG_SMALL
        p = init_pyramid_params(spec, KEY)
        bs = [b - 10.0 for b in p.biases]
        x = jnp.zeros((2, spec.input_size, spec.input_size, 3))
        y0, s0 = _run(spec, x, 4, biases=bs, x_slots=1)
        y1, s1 = _run(
            spec, x, 4, biases=bs, x_slots=2, streamed=True, w_slots=2,
            c_tiles=4,
        )
        np.testing.assert_array_equal(np.asarray(y1), np.asarray(y0))
        np.testing.assert_array_equal(np.asarray(s1), np.asarray(s0))
        assert (np.asarray(s1)[..., 1:] == 1).all(), "cascade must skip all"

    def test_end_cascade_mixed_live_dead(self):
        """Sparse input mixes live and dead tiles per cell: the last level's
        k-invariant liveness predicate must agree with the untiled flags."""
        spec = LENET5_FUSION
        p = init_pyramid_params(spec, KEY)
        bs = [p.biases[0] - 0.5, p.biases[1] + 0.3]
        blob = spec.input_size // 3
        x = jnp.zeros(
            (1, spec.input_size, spec.input_size, 1)
        ).at[:, :blob, :blob, :].set(5.0)
        y0, s0 = fused_pyramid(
            x, p.weights, bs, spec=spec, out_region=1, x_slots=1
        )
        y1, s1 = fused_pyramid(
            x, p.weights, bs, spec=spec, out_region=1, streamed=True,
            w_slots=2, c_tiles=2,
        )
        np.testing.assert_array_equal(np.asarray(y1), np.asarray(y0))
        np.testing.assert_array_equal(np.asarray(s1), np.asarray(s0))
        frac = float(np.asarray(s0)[..., 1].mean())
        assert 0.0 < frac < 1.0, "test needs mixed live/dead tiles"

    def test_c_tiles_must_divide_cout(self):
        with pytest.raises(AssertionError, match="must divide"):
            _run(Q3_CHAIN, _inputs(Q3_CHAIN), 4, streamed=True, w_slots=2,
                 c_tiles=3)

    def test_run_network_with_channel_tiled_plan(self):
        """The runner threads c_tiles from the plan: a LeNet plan pinned to
        the channel-tiled streamed regime is bit-identical end to end."""
        graph = lenet5()
        plan = auto_partition(graph)
        tiled = dataclasses.replace(
            plan,
            pyramids=tuple(
                dataclasses.replace(
                    p,
                    launch=dataclasses.replace(
                        p.launch, streamed=True, w_slots=2,
                        c_tiles=p.launch.program.c_tile_options()[0],
                    ),
                )
                for p in plan.pyramids
            ),
        )
        params = init_network_params(graph, KEY)
        x = jax.random.normal(jax.random.PRNGKey(2), (2, 32, 32, 1))
        y0, _ = run_network(x, params, plan=plan)
        y1, _ = run_network(
            x, prepare_network_params(tiled, params), plan=tiled
        )
        np.testing.assert_array_equal(np.asarray(y1), np.asarray(y0))


@st.composite
def conv_chain(draw):
    """Random conv(/pool) pyramid, Q in 1..4 convs, sized for interpret-mode
    kernel launches (small spatial dims, composite channel counts so the
    last level has nontrivial Cout divisors)."""
    size = draw(st.integers(10, 18))
    q = draw(st.integers(1, 4))
    levels = []
    c = draw(st.integers(1, 3))
    cur = size
    for qi in range(q):
        K = draw(st.integers(1, 3))
        S = draw(st.integers(1, 2))
        pad = draw(st.integers(0, max(0, K // 2)))
        nxt = (cur + 2 * pad - K) // S + 1
        if nxt < 2:
            break
        c2 = draw(st.sampled_from([2, 4, 6, 8]))
        levels.append(FusedLevel("conv", K, S, pad, c, c2))
        c, cur = c2, nxt
        if cur >= 4 and draw(st.booleans()):
            levels.append(FusedLevel("pool", 2, 2, 0, c, c))
            cur = (cur - 2) // 2 + 1
    if not levels:
        levels = [FusedLevel("conv", 3, 1, 1, c, 4)]
    return FusionSpec(levels=tuple(levels), input_size=size)


@pytest.mark.slow
class TestRegimeSweepProperty:
    @given(
        conv_chain(),
        st.integers(1, 2),  # x_slots
        st.integers(1, 2),  # w_slots
        st.integers(0, 3),  # c_tiles divisor index
        st.integers(0, 50),
    )
    @settings(max_examples=10, deadline=None)
    def test_any_regime_matches_resident_untiled(
        self, spec, x_slots, w_slots, ct_idx, seed
    ):
        """THE parity invariant of the channel-tiled grid: every
        (x_slots, w_slots, c_tiles) combination computes bitwise what the
        resident untiled serial kernel computes."""
        out_size = spec.feature_sizes()[-1]
        if out_size < 1:
            return
        region = next(r for r in range(2, 0, -1) if out_size % r == 0)
        divisors = (1,) + compile_program(spec, region).c_tile_options()
        c_tiles = divisors[min(ct_idx, len(divisors) - 1)]
        params = init_pyramid_params(spec, jax.random.PRNGKey(seed))
        x = jax.random.normal(
            jax.random.PRNGKey(seed + 1),
            (1, spec.input_size, spec.input_size, spec.levels[0].n_in),
        )
        y0, s0 = fused_pyramid(
            x, params.weights, params.biases, spec=spec, out_region=region,
            x_slots=1,
        )
        y1, s1 = fused_pyramid(
            x, params.weights, params.biases, spec=spec, out_region=region,
            x_slots=x_slots, streamed=True, w_slots=w_slots, c_tiles=c_tiles,
        )
        np.testing.assert_array_equal(
            np.asarray(y1), np.asarray(y0),
            err_msg=f"spec={spec} region={region} x={x_slots} w={w_slots}"
                    f" ct={c_tiles}",
        )
        np.testing.assert_array_equal(np.asarray(s1), np.asarray(s0))


class TestPlannerLadder:
    def test_b7_selects_channel_tiled_double_buffer(self):
        """Acceptance: ResNet-18 b7 — untiled double-buffered streaming
        busts VMEM, so the ladder lands on channel-tiled w_slots=2, and the
        k-axis pipeline saves cycles at alpha == 1 (the launch PR 4's
        cross-cell prefetch could not touch)."""
        lp = plan_launch(resnet18_fusions()[7])
        assert lp.streamed and lp.w_slots == 2 and lp.c_tiles > 1
        assert lp.program.alpha == 1 and lp.x_slots == 1
        prog = lp.program
        assert prog.vmem_stream_bytes(2) > VMEM_BUDGET_BYTES
        assert (
            prog.vmem_stream_bytes(2, 1, lp.c_tiles) <= VMEM_BUDGET_BYTES
        )
        # coarsest feasible slicing: no smaller c_tiles fits two slots
        for ct in prog.c_tile_options():
            if ct >= lp.c_tiles:
                break
            assert prog.vmem_stream_bytes(2, 1, ct) > VMEM_BUDGET_BYTES
        blocking = dataclasses.replace(lp, x_slots=1, w_slots=1)
        assert lp.modeled_cycles() < blocking.modeled_cycles()
        untiled_w1 = dataclasses.replace(lp, w_slots=1, c_tiles=1)
        assert lp.modeled_cycles() <= untiled_w1.modeled_cycles()

    def test_pinned_w_slots_adopts_feasible_c_tiles(self):
        """A caller pinning only w_slots=2 on a spec whose untiled double
        buffer busts VMEM must land on the planner's channel-tiled rung
        instead of dying on the working-set assert (resolve_stream_regime
        is the single rung-order source shared with plan_launch)."""
        prog = plan_launch(resnet18_fusions()[7]).program
        ws, ct = prog.resolve_stream_regime(VMEM_BUDGET_BYTES, 1, 2, None)
        assert ws == 2 and ct > 1
        assert prog.vmem_stream_bytes(ws, 1, ct) <= VMEM_BUDGET_BYTES
        # fully-open knobs reproduce plan_launch's own choice
        lp = plan_launch(resnet18_fusions()[7])
        assert prog.resolve_stream_regime(VMEM_BUDGET_BYTES, 1) == (
            lp.w_slots, lp.c_tiles,
        )
        # pinned values pass through untouched
        assert prog.resolve_stream_regime(VMEM_BUDGET_BYTES, 1, 1, 8) == (1, 8)

    def test_vmem_model_counts_mid_scratch(self):
        """The channel-tiled kernel re-reads the last level's input (mid)
        tile at every k for Q > 1; the byte models must charge it so a
        near-budget plan cannot overflow real VMEM."""
        prog = plan_launch(resnet18_fusions()[7]).program
        last = prog.levels[-1]
        cb, cl = channel_blocks(last.n_in)
        for ct in (1, 2):
            mids = [s for n, s, _ in prog.vmem_buffers(1, ct) if n == "mid"]
            assert mids == [(cb, last.in_size, last.in_size, cl)]
        # tiling shrinks only the last level's working tile and output block
        assert prog.vmem_bytes(1, 2) < prog.vmem_bytes(1)
        # Q=1 chains have no mid pyramid to carry
        prog1 = compile_program(Q1_CHAIN, 3)
        assert not [n for n, _, _ in prog1.vmem_buffers(1, 2) if n == "mid"]

    def test_untiled_double_buffer_still_preferred_when_it_fits(self):
        """The channel-tiled rung sits below plain w_slots=2: chains whose
        two largest-level copies fit keep c_tiles == 1."""
        for spec in (VGG_FUSION, resnet18_fusions()[0]):
            lp = plan_launch(spec)
            if lp.streamed and lp.w_slots == 2:
                assert lp.c_tiles == 1

    def test_c_tile_options_are_divisors_with_two_channel_floor(self):
        prog = plan_launch(Q3_CHAIN).program
        n_out = Q3_CHAIN.levels[-1].n_out
        assert prog.c_tile_options() == tuple(
            c for c in range(2, n_out // 2 + 1) if n_out % c == 0
        )
        assert all(n_out // c >= 2 for c in prog.c_tile_options())

    def test_regime_label(self):
        lp = plan_launch(resnet18_fusions()[7])
        assert lp.regime == f"streamed_w2_c{lp.c_tiles}"
        assert dataclasses.replace(lp, streamed=False).regime == "resident"
        assert (
            dataclasses.replace(lp, w_slots=1, c_tiles=1).regime
            == "streamed_w1"
        )

    @pytest.mark.parametrize("name", sorted(ZOO_SPECS))
    def test_zoo_plans_respect_default_budget(self, name):
        """Zoo-wide acceptance: plan_launch never hands out a plan whose
        own VMEM accounting exceeds the budget it was given."""
        lp = plan_launch(ZOO_SPECS[name])
        assert lp is not None
        assert lp.vmem_bytes() <= VMEM_BUDGET_BYTES

    @given(st.sampled_from(sorted(ZOO_SPECS)), st.integers(14, 24))
    @settings(max_examples=40, deadline=None)
    def test_zoo_plans_respect_any_budget(self, name, budget_log2):
        """The same invariant under random budgets from 16 KiB to 16 MiB:
        every returned plan fits, across every ladder rung."""
        budget = 1 << budget_log2
        lp = plan_launch(ZOO_SPECS[name], vmem_budget=budget)
        if lp is not None:
            assert lp.vmem_bytes() <= budget


class TestChannelTiledCostModel:
    def test_body_timeline_phases(self):
        """Blocking pays every slice fetch; pipelined exposes only the fill
        behind the mid pyramid and the steady-state max."""
        # compute_mid=10, compute_last=40, dma_mid=5, dma_slice=7, ct=4
        assert channel_tiled_body_cycles(
            10, 40, 5, 7, 4, pipelined=False
        ) == 5 + 10 + 4 * (7 + 10)
        assert channel_tiled_body_cycles(
            10, 40, 5, 7, 4, pipelined=True
        ) == 5 + max(10, 7) + 10 + 3 * max(10, 7)

    def test_pipelined_saving_is_min_terms(self):
        for cm, cl, dm, dk, ct in [(10, 40, 5, 7, 4), (3, 100, 0, 50, 2),
                                   (0, 8, 9, 1, 8)]:
            serial = channel_tiled_body_cycles(cm, cl, dm, dk, ct,
                                               pipelined=False)
            pipe = channel_tiled_body_cycles(cm, cl, dm, dk, ct,
                                             pipelined=True)
            ck = -(-cl // ct)
            assert serial - pipe == min(cm, dk) + (ct - 1) * min(ck, dk)
            assert pipe <= serial

    @pytest.mark.parametrize("name", sorted(ZOO_SPECS))
    def test_ds1_split_sums_to_total(self, name):
        spec = ZOO_SPECS[name]
        mid, last = ds1_split_cycles_per_movement(spec)
        assert mid + last == ds1_cycles_per_movement(spec)
        assert last > 0
        if spec.q_convs == 1:
            assert mid == 0

    def test_hbm_traffic_invariant_under_tiling(self):
        """Channel tiling re-schedules weight movement, it never adds HBM
        traffic: each k reads 1/c_tiles of the slice across c_tiles steps."""
        lp = plan_launch(resnet18_fusions()[7])
        prog = lp.program
        for ct in (1, 2, 4, 8):
            assert prog.hbm_bytes(2, streamed=True, c_tiles=ct) == \
                prog.hbm_bytes(2, streamed=True)
        untiled = dataclasses.replace(lp, w_slots=1, c_tiles=1)
        assert lp.hbm_bytes(4) == untiled.hbm_bytes(4)

    def test_vmem_slice_accounting(self):
        """Among channel-tiled options with vreg-wide slices
        vmem_stream_bytes shrinks monotonically in c_tiles (smaller slice
        slots + smaller last-level working tile; the mid tile is
        c_tiles-invariant), and
        slice_bytes is the per-k DMA granule.  (No monotonicity across the
        1 -> 2 boundary: tiling swaps the shared revolving slots for a
        blocking mid slot + sliced slots + the carry, which can exceed the
        untiled set when the mid level rivals the last — the ladder relies
        on feasibility only.)"""
        prog = plan_launch(resnet18_fusions()[7]).program
        # slices narrower than one 128-lane vreg occupy a whole one
        opts = [
            ct for ct in prog.c_tile_options()
            if prog.levels[-1].n_out // ct >= LANES
        ]
        sizes = [prog.vmem_stream_bytes(2, 1, ct) for ct in opts]
        assert all(a >= b for a, b in zip(sizes, sizes[1:]))
        lp = plan_launch(resnet18_fusions()[7])
        cnt = prog.level_weight_counts()[-1]
        assert lp.slice_bytes() == 4 * cnt // lp.c_tiles
        assert dataclasses.replace(lp, streamed=False).slice_bytes() == 0

    def test_partition_dp_consumes_channel_tiled_cost(self):
        """The DP's plan objects carry c_tiles and their summed cycle model
        matches the per-launch channel-tiled bodies."""
        from repro.net.graph import MODELS

        plan = auto_partition(MODELS["resnet18"]())
        tiled = [p for p in plan.pyramids if p.launch.c_tiles > 1]
        assert tiled, "resnet18's b7 pyramid should be channel-tiled"
        assert plan.modeled_cycles() == sum(
            p.launch.modeled_cycles(plan.batch) for p in plan.pyramids
        )
        assert "streamed_w2_c" in plan.summary()


class TestLaunchArgChecks:
    def test_x_slots_must_be_one_or_two(self):
        with pytest.raises(AssertionError, match="x_slots"):
            _run(LENET5_FUSION, _inputs(LENET5_FUSION), 1, x_slots=3)

    def test_one_bias_per_level(self):
        spec = LENET5_FUSION
        p = init_pyramid_params(spec, KEY)
        with pytest.raises(AssertionError, match="one bias per conv level"):
            fused_pyramid(
                _inputs(spec), p.weights, p.biases[:1], spec=spec,
                out_region=1,
            )
