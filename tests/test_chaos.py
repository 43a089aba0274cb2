"""Chaos suite: seeded fault injection proving the degradation ladder.

Every fault class the guarded runtime claims to absorb is injected here
deterministically (``repro.robust.faults``) against LeNet and a reduced
ResNet-18, and every case must terminate at a successful forward whose
logits match the reference oracle — with the rung that fired visible in
the :class:`RunReport` and, when a tracer is installed, as ``"degrade"``
trace events.  This is the acceptance test of DESIGN.md §13: no fault
class may escape as a crash or as silently wrong logits.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.net.graph import MODELS
from repro.net.partition import auto_partition
from repro.net.runner import (
    init_network_params,
    prepare_network_params,
    reference_network,
    run_network,
)
from repro.obs import tracing
from repro.robust import (
    GuardConfig,
    NumericError,
    corrupt_params,
    guarding,
    inject,
)

# LeNet's single fused pyramid: 1.29 MB of padded VMEM (conv1 in patch
# form), its two one-conv pyramids at most 1.18 MB.  These factors of the
# 16 MiB budget bracket the replan rung: GENTLE leaves ~1.17 MB (the fused
# launch fails, the split fits), HARSH leaves ~1.7 kB (nothing fits, the
# ladder must bottom out at the reference path).
SQUEEZE_GENTLE = 0.07
SQUEEZE_HARSH = 0.0001


def _setup(model):
    if model == "lenet":
        g = MODELS["lenet"]()
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 32, 1))
    else:
        g = MODELS["resnet18"](input_size=32, num_classes=10)
        x = jax.random.normal(jax.random.PRNGKey(1), (1, 32, 32, 3))
    params = init_network_params(g, jax.random.PRNGKey(0))
    plan = auto_partition(g, batch=x.shape[0])
    prepped = prepare_network_params(plan, params)
    ref = reference_network(x, g, params)
    return g, x, params, plan, prepped, ref


@pytest.fixture(scope="module")
def lenet():
    return _setup("lenet")


@pytest.fixture(scope="module")
def resnet():
    return _setup("resnet18")


def _assert_correct(y, ref, tag=""):
    err = float(jnp.max(jnp.abs(y - ref)))
    assert err < 1e-4, f"{tag}: logits diverge from reference by {err}"


class TestWeightCorruption:
    @pytest.mark.parametrize("kind", ["nan", "inf"])
    def test_corrupt_weights_healed_from_source(self, lenet, kind):
        g, x, params, plan, prepped, ref = lenet
        bad = corrupt_params(prepped, "CL1", kind=kind, seed=3)
        with guarding(GuardConfig(), source_params=params) as guard:
            y, _ = run_network(x, bad, plan=plan)
        _assert_correct(y, ref, f"heal-{kind}")
        rep = guard.last_report
        assert rep.fallback_counts() == {"heal": 1}
        assert rep.events[0].detail["nodes"] == ["CL1"]

    def test_corrupt_weights_without_source_raise(self, lenet):
        g, x, params, plan, prepped, ref = lenet
        bad = corrupt_params(prepped, "CL2", kind="nan", seed=3)
        with guarding(GuardConfig()):
            with pytest.raises(NumericError) as ei:
                run_network(x, bad, plan=plan)
        assert ei.value.context["nodes"] == ["CL2"]

    def test_corrupt_source_too_raises(self, lenet):
        """Healing is bounded: when the master copy is corrupt as well, the
        run must fail loudly, not loop."""
        g, x, params, plan, prepped, ref = lenet
        bad_prep = corrupt_params(prepped, "CL1", seed=3)
        bad_src = corrupt_params(params, "CL1", seed=3)
        with guarding(GuardConfig(), source_params=bad_src):
            with pytest.raises(NumericError, match="master copy"):
                run_network(x, bad_prep, plan=plan)

    def test_corruption_is_deterministic(self, lenet):
        g, x, params, plan, prepped, ref = lenet
        a = corrupt_params(prepped, "CL1", kind="nan", seed=7)
        b = corrupt_params(prepped, "CL1", kind="nan", seed=7)
        np.testing.assert_array_equal(
            np.isnan(np.asarray(a["CL1"][0], dtype=np.float32)),
            np.isnan(np.asarray(b["CL1"][0], dtype=np.float32)),
        )


class TestOutputPoisoning:
    @pytest.mark.parametrize("kind", ["nan", "inf"])
    def test_poisoned_launch_quarantined(self, lenet, kind):
        """A kernel miscompute (poisoned launch output) trips the numeric
        sentinel; the launch is quarantined to the reference segment and
        the logits stay correct."""
        g, x, params, plan, prepped, ref = lenet
        with guarding(GuardConfig(), source_params=params) as guard:
            with inject(seed=0) as inj:
                inj.poison_output(kind=kind)
                y, skips = run_network(x, prepped, plan=plan)
        _assert_correct(y, ref, f"poison-{kind}")
        rep = guard.last_report
        assert rep.fallback_counts() == {"reference": 1}
        assert "sentinel tripped: non-finite" in rep.events[0].reason
        # the fault did not reproduce on the reference walk: kernel-only
        assert rep.events[0].detail["level"] == "kernel-only"
        # quarantined launches report a neutral zero skip map
        q = plan.pyramids[0]
        assert np.asarray(skips[q.name]).sum() == 0

    def test_magnitude_sentinel(self, lenet):
        """A tight magnitude limit quarantines a launch whose output is
        finite but implausibly large — here the 'blow-up' is the injected
        Inf replaced by the limit check on a clean output."""
        g, x, params, plan, prepped, ref = lenet
        with guarding(
            GuardConfig(magnitude_limit=1e-6), source_params=params
        ) as guard:
            with pytest.raises(NumericError, match="even on the reference"):
                # every real activation exceeds 1e-6, and so does the
                # reference recompute: the fault is localized to a level
                # and surfaced, not swallowed
                run_network(x, prepped, plan=plan)
        rep = guard.last_report  # report not stored on raise
        assert rep is None

    def test_poison_specific_resnet_launch(self, resnet):
        g, x, params, plan, prepped, ref = resnet
        target = plan.pyramids[3].name
        with guarding(GuardConfig(), source_params=params) as guard:
            with inject(seed=0) as inj:
                inj.poison_output(launch=target, kind="nan")
                y, _ = run_network(x, prepped, plan=plan)
        _assert_correct(y, ref, "resnet-poison")
        rep = guard.last_report
        assert rep.fallback_counts() == {"reference": 1}
        assert rep.events[0].launch == target
        assert rep.clean_launches == plan.n_launches() - 1


class TestBudgetSqueeze:
    def test_squeeze_replans_to_chained_launches(self, lenet):
        g, x, params, plan, prepped, ref = lenet
        with guarding(GuardConfig(), source_params=params) as guard:
            with inject(seed=0) as inj:
                inj.squeeze_budget(SQUEEZE_GENTLE)
                y, _ = run_network(x, prepped, plan=plan)
        _assert_correct(y, ref, "squeeze")
        rep = guard.last_report
        assert rep.fallback_counts() == {"replan": 1}
        ev = rep.events[0]
        assert len(ev.detail["sub_launches"]) >= 2  # tighter cuts: a chain
        assert ev.detail["budget"] <= int(
            plan.vmem_budget * SQUEEZE_GENTLE
        )

    def test_harsh_squeeze_bottoms_out_at_reference(self, lenet):
        g, x, params, plan, prepped, ref = lenet
        cfg = GuardConfig(max_replans=2)
        with guarding(cfg, source_params=params) as guard:
            with inject(seed=0) as inj:
                inj.squeeze_budget(SQUEEZE_HARSH)
                y, _ = run_network(x, prepped, plan=plan)
        _assert_correct(y, ref, "squeeze-harsh")
        rep = guard.last_report
        assert rep.fallback_counts() == {"reference": 1}
        assert "replan exhausted" in rep.events[0].reason

    def test_squeeze_resnet(self, resnet):
        """The multi-pyramid plan degrades only the launches that no longer
        fit; everything else stays on the fast path."""
        g, x, params, plan, prepped, ref = resnet
        vmems = sorted(p.launch.vmem_bytes() for p in plan.pyramids)
        # squeeze to just under the largest working set: only the biggest
        # launch(es) go over budget (next-largest distinct size still fits)
        below = [v for v in vmems if v < vmems[-1]]
        target = (vmems[-1] + (below[-1] if below else 0)) // 2
        factor = target / plan.vmem_budget
        effective = int(plan.vmem_budget * factor)
        n_over = sum(1 for v in vmems if v > effective)
        assert 1 <= n_over < len(vmems)
        with guarding(GuardConfig(), source_params=params) as guard:
            with inject(seed=0) as inj:
                inj.squeeze_budget(factor)
                y, _ = run_network(x, prepped, plan=plan)
        _assert_correct(y, ref, "resnet-squeeze")
        rep = guard.last_report
        degraded = sum(rep.fallback_counts().values())
        assert degraded == n_over
        assert rep.clean_launches == plan.n_launches() - n_over


class TestStageFaults:
    def test_plan_fault_goes_to_reference(self, lenet):
        g, x, params, plan, prepped, ref = lenet
        with guarding(GuardConfig(), source_params=params) as guard:
            with inject(seed=0) as inj:
                inj.raise_at("plan")
                y, _ = run_network(x, prepped, plan=plan)
        _assert_correct(y, ref, "plan-fault")
        assert guard.last_report.fallback_counts() == {"reference": 1}

    @pytest.mark.parametrize("stage", ["compile", "run"])
    def test_transient_fault_retries_interpret(self, lenet, stage):
        """A single-shot compile/run failure retries once with
        interpret=True and succeeds — the fused output still lands."""
        g, x, params, plan, prepped, ref = lenet
        with guarding(GuardConfig(), source_params=params) as guard:
            with inject(seed=0) as inj:
                inj.raise_at(stage)
                y, _ = run_network(x, prepped, plan=plan)
        _assert_correct(y, ref, f"{stage}-fault")
        rep = guard.last_report
        assert rep.fallback_counts() == {"interpret": 1}
        assert inj.fired == [(stage, plan.pyramids[0].name, "raise")]

    def test_persistent_fault_falls_to_reference(self, lenet):
        g, x, params, plan, prepped, ref = lenet
        with guarding(GuardConfig(), source_params=params) as guard:
            with inject(seed=0) as inj:
                inj.raise_at("run", times=4)
                y, _ = run_network(x, prepped, plan=plan)
        _assert_correct(y, ref, "persistent-fault")
        rep = guard.last_report
        assert rep.fallback_counts() == {"reference": 1}
        assert "interpret retry failed too" in rep.events[0].reason

    def test_resnet_stage_fault_on_named_launch(self, resnet):
        g, x, params, plan, prepped, ref = resnet
        target = plan.pyramids[5].name
        with guarding(GuardConfig(), source_params=params) as guard:
            with inject(seed=0) as inj:
                inj.raise_at("run", launch=target, times=4)
                y, _ = run_network(x, prepped, plan=plan)
        _assert_correct(y, ref, "resnet-stage-fault")
        rep = guard.last_report
        assert [e.launch for e in rep.events] == [target]


class TestObservability:
    def test_rungs_visible_as_trace_events(self, lenet):
        g, x, params, plan, prepped, ref = lenet
        with tracing() as collector:
            with guarding(GuardConfig(), source_params=params):
                with inject(seed=0) as inj:
                    inj.poison_output(kind="nan")
                    y, _ = run_network(x, prepped, plan=plan)
        _assert_correct(y, ref, "traced-poison")
        degrades = [e for e in collector.events if e.name == "degrade"]
        assert len(degrades) == 1
        assert degrades[0].args["rung"] == "reference"
        assert degrades[0].args["launch"] == plan.pyramids[0].name
        summary = [e for e in collector.events if e.name == "guarded_run"]
        assert summary and summary[0].args["fallbacks"] == {"reference": 1}

    def test_clean_guarded_run_emits_summary_only(self, lenet):
        g, x, params, plan, prepped, ref = lenet
        with tracing() as collector:
            with guarding(GuardConfig(), source_params=params):
                y, _ = run_network(x, prepped, plan=plan)
        _assert_correct(y, ref, "traced-clean")
        assert not [e for e in collector.events if e.name == "degrade"]
        summary = [e for e in collector.events if e.name == "guarded_run"]
        assert summary[0].args["clean_launches"] == plan.n_launches()


class TestGuardOffUnaffected:
    def test_injector_ignored_without_guard(self, lenet):
        """Armed faults are consumed only by the guarded runner: the plain
        jit path never consults the injector."""
        g, x, params, plan, prepped, ref = lenet
        base, _ = run_network(x, prepped, plan=plan)
        with inject(seed=0) as inj:
            inj.poison_output(kind="nan")
            inj.raise_at("run", times=99)
            y, _ = run_network(x, prepped, plan=plan)
        assert not inj.fired
        assert float(jnp.max(jnp.abs(y - base))) == 0.0

    def test_determinism_across_repeats(self, lenet):
        """Same seed, same faults, same rungs, same logits — twice."""
        g, x, params, plan, prepped, ref = lenet

        def once():
            with guarding(GuardConfig(), source_params=params) as guard:
                with inject(seed=5) as inj:
                    inj.poison_output(kind="inf")
                    inj.squeeze_budget(SQUEEZE_GENTLE)
                    y, _ = run_network(x, prepped, plan=plan)
            return np.asarray(y), guard.last_report.fallback_counts(), \
                list(inj.fired)

        y1, f1, log1 = once()
        y2, f2, log2 = once()
        np.testing.assert_array_equal(y1, y2)
        assert f1 == f2 and log1 == log2
