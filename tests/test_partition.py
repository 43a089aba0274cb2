"""Auto-partitioner: DP optimality vs brute force, VMEM-budget respect,
residual-cut legality, channel-chain validation, and the VGG-16 acceptance
comparison (auto <= layer-by-layer and <= paper's blocks-1-2 fusion)."""

import pytest

from repro.core.fusion import FusedLevel, FusionSpec
from repro.core.program import VMEM_BUDGET_BYTES, plan_launch
from repro.net.graph import (
    MODELS,
    Segment,
    fusable_segments,
    infer_shapes,
    resnet18,
    vgg16,
)
from repro.net.partition import (
    auto_partition,
    brute_force_segment,
    layerwise_partition,
    paper_partition,
    partition_segment,
)


def _chain_segment(channels, size, k=3, pad=1, pools=()):
    """Linear conv chain (optional pools after given conv indices) as a
    Segment, for direct DP testing without a whole graph."""
    from repro.net.graph import Node

    nodes, prev = [], "in"
    for i, ch in enumerate(channels):
        nodes.append(Node("conv", f"c{i}", (prev,), K=k, S=1, pad=pad, n_out=ch))
        prev = f"c{i}"
        if i in pools:
            nodes.append(Node("pool", f"p{i}", (prev,), K=2, S=2))
            prev = f"p{i}"
    return Segment(nodes=tuple(nodes), input_size=size, in_channels=2)


class TestChannelChainValidation:
    """Satellite: malformed chains fail at FusionSpec construction with a
    named level, not deep inside the kernel wrapper."""

    def test_conv_channel_mismatch_raises(self):
        with pytest.raises(ValueError, match="CONVB.*does not chain.*8"):
            FusionSpec(
                levels=(
                    FusedLevel("conv", 3, 1, 1, 2, 8, name="CONVA"),
                    FusedLevel("conv", 3, 1, 1, 4, 4, name="CONVB"),
                ),
                input_size=8,
            )

    def test_pool_must_preserve_channels(self):
        with pytest.raises(ValueError, match="pools preserve channels"):
            FusionSpec(
                levels=(
                    FusedLevel("conv", 3, 1, 1, 2, 8),
                    FusedLevel("pool", 2, 2, 0, 8, 4),
                ),
                input_size=8,
            )

    def test_pool_must_consume_previous_channels(self):
        with pytest.raises(ValueError, match="does not chain"):
            FusionSpec(
                levels=(
                    FusedLevel("conv", 3, 1, 1, 2, 8),
                    FusedLevel("pool", 2, 2, 0, 4, 4),
                ),
                input_size=8,
            )

    def test_empty_chain_raises(self):
        with pytest.raises(ValueError, match="at least one level"):
            FusionSpec(levels=(), input_size=8)

    def test_unknown_kind_raises(self):
        with pytest.raises(ValueError, match="unknown level kind"):
            FusionSpec(
                levels=(FusedLevel("norm", 3, 1, 1, 2, 2),), input_size=8
            )


class TestSegmentDP:
    BUDGETS = [64 * 1024, 256 * 1024, 1024 * 1024]

    @pytest.mark.parametrize("budget", BUDGETS)
    @pytest.mark.parametrize(
        "channels,size,pools",
        [
            ((8, 8, 8), 16, ()),
            ((4, 16, 16, 8), 20, (1,)),
            ((16, 32, 32), 12, (0,)),
            ((8, 8, 8, 8, 8), 24, (2,)),
        ],
    )
    def test_dp_matches_brute_force(self, channels, size, pools, budget):
        """DP minimum == exhaustive minimum over all 2^(G-1) cut sets."""
        seg = _chain_segment(channels, size, pools=pools)
        bf = brute_force_segment(seg, vmem_budget=budget)
        try:
            launches = partition_segment(seg, vmem_budget=budget)
        except ValueError:
            assert bf[0] == float("inf")
            return
        hbm = sum(lp.hbm_bytes(1) for lp in launches)
        cyc = sum(lp.modeled_cycles(1) for lp in launches)
        assert (hbm, cyc) == (pytest.approx(bf[0]), pytest.approx(bf[1]))

    def test_launches_tile_the_segment(self):
        seg = _chain_segment((8, 8, 16), 16, pools=(1,))
        launches = partition_segment(seg, vmem_budget=256 * 1024)
        total_levels = sum(len(lp.spec.levels) for lp in launches)
        assert total_levels == len(seg.nodes)

    def test_infeasible_group_raises_clearly(self):
        seg = _chain_segment((64, 64), 32)
        with pytest.raises(ValueError, match="fits no launch regime"):
            partition_segment(seg, vmem_budget=1024)

    def test_max_convs_1_is_layerwise(self):
        seg = _chain_segment((8, 8, 8), 16)
        launches = partition_segment(seg, max_convs=1)
        assert len(launches) == 3
        assert all(lp.spec.q_convs == 1 for lp in launches)


class TestWholeGraphPartitions:
    @pytest.mark.parametrize("name", sorted(MODELS))
    def test_vmem_budget_respected(self, name):
        """Every chosen launch — streamed or resident — fits the budget."""
        plan = auto_partition(MODELS[name]())
        for p in plan.pyramids:
            assert p.launch.vmem_bytes() <= VMEM_BUDGET_BYTES, p.name

    @pytest.mark.parametrize("name", sorted(MODELS))
    def test_pyramids_cover_all_segment_nodes_exactly_once(self, name):
        graph = MODELS[name]()
        seen = []
        for p in auto_partition(graph).pyramids:
            seen.extend(p.node_names)
        want = [n for s in fusable_segments(graph) for n in s.node_names]
        assert sorted(seen) == sorted(want)
        assert len(seen) == len(set(seen))

    def test_residual_joins_are_cut_points(self):
        """No pyramid spans an add / fork: every pyramid's nodes lie inside
        one fusable segment of the ResNet graph."""
        graph = resnet18()
        seg_of = {
            n: i
            for i, s in enumerate(fusable_segments(graph))
            for n in s.node_names
        }
        for p in auto_partition(graph).pyramids:
            owners = {seg_of[n] for n in p.node_names}
            assert len(owners) == 1, p.name
        # adds and relus are never inside any pyramid
        covered = auto_partition(graph).covered()
        for n in graph.nodes:
            if n.op in ("add", "relu"):
                assert n.name not in covered

    def test_projection_shortcuts_are_solo_pyramids(self):
        plan = auto_partition(resnet18())
        projs = [p for p in plan.pyramids if p.node_names[0].endswith("_proj")]
        assert len(projs) == 3
        for p in projs:
            assert p.q_convs == 1 and p.spec.levels[0].relu is False

    def test_vgg16_acceptance_auto_beats_both_baselines(self):
        """The PR's acceptance comparison: modeled HBM of the auto plan <=
        layer-by-layer AND <= the paper's hand-picked blocks-1-2 fusion."""
        g = vgg16()
        auto = auto_partition(g).hbm_bytes()
        assert auto <= layerwise_partition(g).hbm_bytes()
        assert auto <= paper_partition(g).hbm_bytes()

    @pytest.mark.parametrize("name", sorted(MODELS))
    def test_auto_never_worse_than_layerwise_or_paper(self, name):
        g = MODELS[name]()
        auto = auto_partition(g).hbm_bytes()
        assert auto <= layerwise_partition(g).hbm_bytes()
        assert auto <= paper_partition(g).hbm_bytes()

    def test_paper_partition_vgg_head_is_blocks_1_2(self):
        plan = paper_partition(vgg16())
        head = plan.pyramids[0]
        assert head.q_convs == 4
        assert head.node_names == (
            "CONV1", "CONV2", "POOL1", "CONV3", "CONV4", "POOL2"
        )

    def test_min_vmem_budget_is_tight(self):
        """Partitioning succeeds at the reported floor and fails below it."""
        from repro.net.partition import min_vmem_budget

        g = resnet18(input_size=32, num_classes=10)
        floor = min_vmem_budget(g)
        plan = auto_partition(g, vmem_budget=floor)
        for p in plan.pyramids:
            assert p.launch.vmem_bytes() <= floor
        with pytest.raises(ValueError, match="fits no launch regime"):
            auto_partition(g, vmem_budget=floor - 1)

    def test_smallest_region_preference(self):
        """prefer_region='smallest' yields maximal tile grids (finer END
        granularity) without changing pyramid legality."""
        g = MODELS["lenet"]()
        big = auto_partition(g)
        small = auto_partition(g, prefer_region="smallest")
        assert small.covered() == big.covered()
        for p in small.pyramids:
            assert p.launch.out_region == 1
            assert p.launch.vmem_bytes() <= VMEM_BUDGET_BYTES

    def test_batch_scales_hbm(self):
        g = vgg16()
        h1 = auto_partition(g, batch=1).hbm_bytes()
        h8 = auto_partition(g, batch=8).hbm_bytes()
        assert h1 < h8 < 8 * h1  # weights are read once, maps scale with B


class TestGraphValidation:
    def test_bad_reference_raises(self):
        from repro.net.graph import Graph, Node

        with pytest.raises(ValueError, match="not an earlier node"):
            Graph(
                "bad", 8, 1,
                (
                    Node("input", "x"),
                    Node("conv", "c", ("nope",), K=3, S=1, pad=1, n_out=4),
                ),
            )

    def test_shrunk_to_nothing_raises(self):
        from repro.net.graph import Graph, Node

        with pytest.raises(ValueError, match="leaves no"):
            Graph(
                "bad", 4, 1,
                (
                    Node("input", "x"),
                    Node("conv", "c", ("x",), K=7, S=2, n_out=4),
                ),
            )

    def test_add_shape_mismatch_raises(self):
        from repro.net.graph import Graph, Node

        with pytest.raises(ValueError, match="add operands disagree"):
            Graph(
                "bad", 8, 1,
                (
                    Node("input", "x"),
                    Node("conv", "a", ("x",), K=3, S=1, pad=1, n_out=4),
                    Node("conv", "b", ("x",), K=3, S=2, pad=1, n_out=4),
                    Node("add", "s", ("a", "b")),
                ),
            )

    def test_zoo_shapes(self):
        shp = infer_shapes(vgg16())
        assert shp["POOL5"].size == 7 and shp["POOL5"].channels == 512
        shp = infer_shapes(resnet18())
        assert shp["maxpool"].size == 56
        assert shp["b7_relu"].size == 7 and shp["b7_relu"].channels == 512

    def test_streamed_regime_appears_at_full_scale(self):
        """ResNet-18's 512-channel pair busts resident VMEM and the planner
        must fall back to streamed weights, never over budget."""
        plan = auto_partition(resnet18())
        b7 = [p for p in plan.pyramids if p.node_names[0] == "b7_convA"]
        assert b7 and b7[0].launch.streamed
        lp = plan_launch(b7[0].spec)
        assert lp.program.vmem_bytes() > VMEM_BUDGET_BYTES
        assert lp.vmem_bytes() <= VMEM_BUDGET_BYTES
