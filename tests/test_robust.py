"""Guarded-runtime unit tests: typed errors, preflight validation, guard
dispatch, and the replan entry point (DESIGN.md §13).

The chaos suite (``tests/test_chaos.py``) proves the degradation ladder end
to end; this file pins the pieces: every preflight rejection carries a
typed error naming the offending node/launch, the error hierarchy stays
compatible with the historical ``ValueError`` call sites, and — critically
— with guards off ``run_network`` dispatches to the unchanged jit fast
path.
"""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.core.fusion import FusedLevel, FusionSpec
from repro.core.program import compile_program, plan_launch
from repro.net.graph import MODELS, Node, Segment, fusable_segments
from repro.net.partition import (
    auto_partition, partition_segment, replan_pyramid,
)
from repro.net.runner import (
    _head_op,
    init_network_params,
    prepare_network_params,
    run_network,
)
from repro.robust import (
    BudgetError,
    GuardConfig,
    NumericError,
    PlanError,
    PreflightError,
    RobustError,
    guarding,
    preflight,
)
from repro.robust.faults import corrupt_params
from repro.robust.guard import get_guard, sentinel_stats, sentinel_trips


@pytest.fixture(scope="module")
def lenet_setup():
    g = MODELS["lenet"]()
    params = init_network_params(g, jax.random.PRNGKey(0))
    plan = auto_partition(g, batch=2)
    prepped = prepare_network_params(plan, params)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 32, 1))
    return g, params, plan, prepped, x


class TestErrorHierarchy:
    def test_valueerror_compat(self):
        """Typed errors must keep historical except-clauses working."""
        assert issubclass(PreflightError, ValueError)
        assert issubclass(BudgetError, ValueError)
        assert issubclass(PlanError, PreflightError)
        assert issubclass(NumericError, FloatingPointError)
        assert issubclass(PreflightError, RobustError)

    def test_context_rides_in_message_and_attr(self):
        e = PreflightError("bad node", node="CL1", graph="lenet")
        assert e.context == {"node": "CL1", "graph": "lenet"}
        assert "CL1" in str(e) and "lenet" in str(e)


class TestPreflight:
    def test_clean_setup_passes(self, lenet_setup):
        g, params, plan, prepped, x = lenet_setup
        assert preflight(x, prepped, plan=plan) == "float32"

    def test_bad_input_rank(self, lenet_setup):
        g, params, plan, prepped, x = lenet_setup
        with pytest.raises(PreflightError, match="B, H, W, C"):
            preflight(x[0], prepped, plan=plan)

    def test_bad_spatial(self, lenet_setup):
        g, params, plan, prepped, x = lenet_setup
        with pytest.raises(PreflightError, match="spatial"):
            preflight(x[:, :16], prepped, plan=plan)

    def test_bad_channels(self, lenet_setup):
        g, params, plan, prepped, x = lenet_setup
        bad = jnp.concatenate([x, x], axis=-1)
        with pytest.raises(PreflightError, match="channels"):
            preflight(bad, prepped, plan=plan)

    def test_unknown_dtype(self, lenet_setup):
        g, params, plan, prepped, x = lenet_setup
        with pytest.raises(PreflightError, match="unknown compute dtype"):
            preflight(x, prepped, plan=plan, dtype="float8_e4m3")

    def test_int8_is_modeled_only(self, lenet_setup):
        """int8 hits the EXEC_DTYPES gate at preflight, not as a kernel
        NotImplementedError three layers down."""
        g, params, plan, prepped, x = lenet_setup
        with pytest.raises(PreflightError, match="not executable"):
            preflight(x, prepped, plan=plan, dtype="int8")

    def test_missing_node_params(self, lenet_setup):
        g, params, plan, prepped, x = lenet_setup
        short = {k: v for k, v in prepped.items() if k != "CL2"}
        with pytest.raises(PreflightError, match="missing params.*CL2"):
            preflight(x, short, plan=plan)

    def test_wrong_weight_shape(self, lenet_setup):
        g, params, plan, prepped, x = lenet_setup
        w, b = prepped["CL1"]
        bad = dict(prepped)
        bad["CL1"] = (w[..., :-1], b)
        with pytest.raises(PreflightError, match="weight shape"):
            preflight(x, bad, plan=plan)

    def test_nonfinite_params_localized(self, lenet_setup):
        g, params, plan, prepped, x = lenet_setup
        bad = corrupt_params(prepped, "CL2", kind="inf")
        with pytest.raises(NumericError) as ei:
            preflight(x, bad, plan=plan)
        assert ei.value.context["nodes"] == ["CL2"]

    def test_wrong_bias_shape(self, lenet_setup):
        g, params, plan, prepped, x = lenet_setup
        w, b = prepped["CL2"]
        bad = dict(prepped)
        bad["CL2"] = (w, b[:-1])
        with pytest.raises(PreflightError, match="bias shape") as ei:
            preflight(x, bad, plan=plan)
        assert ei.value.context["node"] == "CL2"

    def test_integer_params_rejected(self, lenet_setup):
        g, params, plan, prepped, x = lenet_setup
        w, b = prepped["CL1"]
        bad = dict(prepped)
        bad["CL1"] = (w.astype(jnp.int32), b)
        with pytest.raises(PreflightError, match="must be floating"):
            preflight(x, bad, plan=plan)

    def test_plan_covering_foreign_node(self, lenet_setup):
        g, params, plan, prepped, x = lenet_setup
        pyr = plan.pyramids[0]
        bad = dataclasses.replace(plan, pyramids=(
            dataclasses.replace(pyr, node_names=pyr.node_names + ("NOPE",)),
        ) + plan.pyramids[1:])
        with pytest.raises(PreflightError, match="not in graph"):
            preflight(x, prepped, plan=bad)

    def test_params_dtype_independent_of_run_dtype(self, lenet_setup):
        """Params are cast per launch, so f32-prepared params preflight
        clean for a bf16 run."""
        g, params, plan, prepped, x = lenet_setup
        assert preflight(x, prepped, plan=plan, dtype="bfloat16") == "bfloat16"

    def test_budget_headroom(self, lenet_setup):
        g, params, plan, prepped, x = lenet_setup
        with pytest.raises(BudgetError) as ei:
            preflight(x, prepped, plan=plan, vmem_budget=1024)
        assert ei.value.context["vmem_budget"] == 1024

    def test_run_network_guarded_preflights(self, lenet_setup):
        """The guarded runner rejects a dtype-mismatched request with the
        typed error, end to end through run_network."""
        g, params, plan, prepped, x = lenet_setup
        with guarding(GuardConfig()):
            with pytest.raises(PreflightError, match="not executable"):
                run_network(x, prepped, plan=plan, dtype="int8")


class TestTypedErrorsReplaceAsserts:
    def test_head_op_unhandled(self):
        n = Node("pool", "P1", ("x",), K=2, S=2)
        with pytest.raises(PreflightError, match="P1"):
            _head_op({}, n, {})

    def test_compile_program_pool_first(self):
        spec = FusionSpec(
            levels=(FusedLevel("pool", K=2, S=2, pad=0, n_in=4, n_out=4),),
            input_size=8,
        )
        with pytest.raises(PlanError, match="start with a conv"):
            compile_program(spec, 4)

    def test_compile_program_region_must_tile(self):
        g = MODELS["lenet"]()
        seg = fusable_segments(g)[0]
        with pytest.raises(PlanError, match="must tile"):
            compile_program(seg.spec(), 3)  # lenet's 5x5 output: 5 % 3 != 0

    def test_plan_launch_prefer_region_typo(self):
        g = MODELS["lenet"]()
        seg = fusable_segments(g)[0]
        with pytest.raises(PreflightError, match="prefer_region"):
            plan_launch(seg.spec(), prefer_region="biggest")

    def test_partition_infeasible_budget(self):
        g = MODELS["lenet"]()
        seg = fusable_segments(g)[0]
        with pytest.raises(BudgetError, match="fits no launch regime"):
            partition_segment(seg, vmem_budget=256)
        # and the historical except-clause still catches it
        with pytest.raises(ValueError):
            partition_segment(seg, vmem_budget=256)


class TestReplanPyramid:
    def test_tighter_budget_chains_launches(self, lenet_setup):
        g, params, plan, prepped, x = lenet_setup
        pyr = plan.pyramids[0]
        budget = pyr.launch.vmem_bytes() * 2 // 3
        subs = replan_pyramid(g, pyr, vmem_budget=budget, batch=2)
        # sub-pyramids tile the original chain exactly, each under budget
        covered = tuple(n for sp in subs for n in sp.node_names)
        assert covered == pyr.node_names
        assert all(sp.launch.vmem_bytes() <= budget for sp in subs)
        assert len(subs) >= 2

    def test_exhausted_budget_raises(self, lenet_setup):
        g, params, plan, prepped, x = lenet_setup
        with pytest.raises(BudgetError):
            replan_pyramid(g, plan.pyramids[0], vmem_budget=128, batch=2)


class TestGuardDispatch:
    def test_guard_off_takes_jit_fast_path(self, lenet_setup, monkeypatch):
        """With no guard installed, run_network must not touch the guarded
        path at all — same contract as tracing-off."""
        g, params, plan, prepped, x = lenet_setup
        import repro.robust.degrade as degrade

        def boom(*a, **k):
            raise AssertionError("guarded path must not run")

        monkeypatch.setattr(degrade, "run_network_guarded", boom)
        assert not get_guard().enabled
        logits, skips = run_network(x, prepped, plan=plan)
        assert logits.shape == (2, 10)

    def test_guard_on_reports(self, lenet_setup):
        g, params, plan, prepped, x = lenet_setup
        base, _ = run_network(x, prepped, plan=plan)
        with guarding(GuardConfig()) as guard:
            y, skips = run_network(x, prepped, plan=plan)
        rep = guard.last_report
        assert rep is not None and not rep.degraded
        assert rep.clean_launches == rep.launches == plan.n_launches()
        assert float(jnp.max(jnp.abs(y - base))) == 0.0
        assert set(skips) == {p.name for p in plan.pyramids}

    def test_guarding_nests_and_restores(self):
        assert not get_guard().enabled
        with guarding(GuardConfig(max_replans=1)) as outer:
            assert get_guard() is outer
            with guarding(GuardConfig(max_replans=5)) as inner:
                assert get_guard() is inner
            assert get_guard() is outer
        assert not get_guard().enabled


class TestSentinels:
    def test_clean_tensor(self):
        stats = sentinel_stats(jnp.ones((4, 4)))
        assert sentinel_trips(stats, None) is None
        assert float(stats["max_abs"]) == 1.0

    def test_nan_and_inf_trip(self):
        bad = jnp.ones((4,)).at[2].set(jnp.nan)
        assert sentinel_trips(sentinel_stats(bad), None) == "non-finite"
        worse = jnp.ones((4,)).at[1].set(jnp.inf)
        assert sentinel_trips(sentinel_stats(worse), None) == "non-finite"

    def test_magnitude_limit(self):
        big = jnp.full((4,), 1e6)
        assert sentinel_trips(sentinel_stats(big), None) is None
        assert sentinel_trips(sentinel_stats(big), 1e3) == "magnitude"

    def test_bf16_cast_safe(self):
        stats = sentinel_stats(jnp.ones((4,), jnp.bfloat16))
        assert sentinel_trips(stats, None) is None


class TestSegmentReluThreading:
    def test_replan_preserves_relu_mode(self):
        """resnet18 shortcut pyramids are relu-free; a replan must not
        reintroduce the activation."""
        g = MODELS["resnet18"](input_size=32, num_classes=10)
        plan = auto_partition(g, batch=1)
        no_relu = [p for p in plan.pyramids
                   if not any(l.relu for l in p.spec.levels)]
        assert no_relu, "expected relu-free shortcut pyramids"
        pyr = no_relu[0]
        subs = replan_pyramid(
            g, pyr, vmem_budget=plan.vmem_budget, batch=1
        )
        assert all(not l.relu for sp in subs for l in sp.spec.levels)

    def test_segment_requires_relu_field(self):
        """The activation rides on each level of the segment's spec."""
        g = MODELS["lenet"]()
        seg = fusable_segments(g)[0]
        assert isinstance(seg, Segment)
        assert all(l.relu is True for l in seg.spec().levels if l.kind == "conv")
