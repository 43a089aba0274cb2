"""ResNet-50 v1.5: bottleneck bodies as three-level pyramids whose last
level is linear.

* the ``1x1 relu → 3x3/2 relu → 1x1 linear`` pyramid against
  ``executor.reference_forward`` in f32 and bf16, END skip on and off,
  resident and channel-tiled streamed, with negative values out of the
  linear level that a ReLU or a wrong skip would zero;
* the graph: one ``[convA, convB, convC]`` chain per block, the published
  4.09 GMAC and 25.53 M parameters at 224², and the plan-build counters;
* ``resnet50(input_size=32)`` through ``run_network`` and through
  ``ServingEngine`` against ``runner.reference_network`` (one compile,
  shared by both through the jit cache);
* a body replanned under a smaller VMEM budget keeps its linear last level
  and still matches the reference.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.executor import (
    PyramidParams,
    init_pyramid_params,
    reference_forward,
)
from repro.core.fusion import FusedLevel, FusionSpec
from repro.core.program import compile_program
from repro.kernels.fused_conv.ops import fused_pyramid
from repro.net.graph import _Builder, fusable_segments, infer_shapes, resnet50
from repro.net.partition import (
    CHAINED_CONV_MACS,
    CONV_MACS,
    auto_partition,
    clear_partition_cache,
    replan_pyramid,
)
from repro.net.runner import (
    prepare_network_params,
    reference_network,
    run_network,
)
from repro.net.serve import ServeConfig, ServingEngine
from repro.obs.trace import tracing
from repro.robust.degrade import _run_subplan
from repro.robust.errors import PlanError

KEY = jax.random.PRNGKey(0)

BOTTLENECK = FusionSpec(
    levels=(
        FusedLevel("conv", 1, 1, 0, 8, 4, name="convA"),
        FusedLevel("conv", 3, 2, 1, 4, 4, name="convB"),
        FusedLevel("conv", 1, 1, 0, 4, 16, name="convC", relu=False),
    ),
    input_size=16,
)
REGIMES = {
    "resident": dict(streamed=False, c_tiles=1),
    "streamed_c2": dict(streamed=True, w_slots=2, c_tiles=2),
}


def _bottleneck_case():
    """A blob of input in one corner; convA and convB biases shifted down
    so tiles away from it are dead after each ReLU level, and convC's
    shifted down so its linear output is negative there."""
    p = init_pyramid_params(BOTTLENECK, KEY)
    biases = [p.biases[0] - 0.5, p.biases[1] - 0.5, p.biases[2] - 1.0]
    x = jnp.zeros((2, 16, 16, 8)).at[:, :6, :6, :].set(
        jax.random.normal(jax.random.PRNGKey(1), (2, 6, 6, 8)) + 2.0
    )
    return x, p.weights, biases


@pytest.mark.parametrize("regime", sorted(REGIMES))
@pytest.mark.parametrize("end_skip", [True, False])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_bottleneck_pyramid_matches_reference(dtype, end_skip, regime):
    x, weights, biases = _bottleneck_case()
    if dtype == "bfloat16":  # the reference sees the operands the kernel sees
        rnd = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731
        x, weights, biases = rnd(x), [rnd(w) for w in weights], [rnd(b) for b in biases]
    ref = np.asarray(
        reference_forward(x, BOTTLENECK, PyramidParams(weights, biases))
    )
    out, skip = fused_pyramid(
        x, weights, biases, spec=BOTTLENECK, out_region=2, end_skip=end_skip,
        compute_dtype=dtype, **REGIMES[regime],
    )
    out = np.asarray(out, np.float32)
    skip = np.asarray(skip)
    assert out.shape == ref.shape == (2, 8, 8, 16)
    assert (ref < -0.5).mean() > 0.5  # the linear level's output survives
    tol = 1e-5 if dtype == "float32" else 2e-2
    np.testing.assert_allclose(out, ref, atol=tol * np.abs(ref).max())
    assert skip[..., 0].sum() == 0  # level 0 never skips
    if end_skip:
        # dead tiles skip convB and convC (each after a ReLU level) ...
        assert 0 < skip[..., 2].sum() < skip[..., 2].size
        assert skip[..., 1].sum() > 0
    else:
        assert skip.sum() == 0


def test_every_block_is_one_chain():
    g = resnet50()
    segs = [s.node_names for s in fusable_segments(g)]
    bodies = [s for s in segs if len(s) == 3]
    assert bodies == [
        (f"b{i}_convA", f"b{i}_convB", f"b{i}_convC") for i in range(16)
    ]
    assert segs[0] == ("conv1", "maxpool")
    assert [s for s in segs if len(s) == 1] == [
        (f"b{i}_proj",) for i in (0, 3, 7, 13)
    ]
    for seg in fusable_segments(g):
        if len(seg.nodes) == 3:
            assert [l.relu for l in seg.spec().levels] == [True, True, False]


def test_pool_after_a_linear_conv_is_not_fused():
    """The kernel pads and masks with zeros, which a max pool ignores only
    over non-negative values: a pool after a linear conv is cut off."""
    b = _Builder()
    b.conv("c0", 3, 1, 1, 4, relu=False)
    b.pool("p0", 2, 2)
    b.conv("c1", 3, 1, 1, 4)
    b.pool("p1", 2, 2)
    g = b.graph("mixed", 16, 3)
    assert [s.node_names for s in fusable_segments(g)] == [("c0",), ("c1", "p1")]
    spec = FusionSpec(
        levels=(FusedLevel("conv", 3, 1, 1, 3, 4, relu=False),
                FusedLevel("pool", 2, 2, 0, 4, 4)),
        input_size=16,
    )
    with pytest.raises(PlanError, match="ReLU conv"):
        compile_program(spec, 8)


def test_published_totals():
    """He et al. 2016, Table 1 (50-layer): 4.09 GMAC at 224² with the
    stride on the 3x3 (v1.5), 25.53 M parameters with folded batch norm."""
    g = resnet50()
    shapes = infer_shapes(g)
    macs = params = convs = 0
    for n in g.nodes:
        if n.op not in ("conv", "dense"):
            continue
        c_in = shapes[n.inputs[0]].channels
        k2 = n.K * n.K if n.op == "conv" else 1
        side = shapes[n.name].size if n.op == "conv" else 1
        macs += k2 * c_in * n.n_out * side * side
        params += k2 * c_in * n.n_out + n.n_out
        convs += n.op == "conv"
    assert convs == 53 and len(g.nodes) == 89
    assert macs / 1e9 == pytest.approx(4.09, abs=0.005)
    assert params / 1e6 == pytest.approx(25.53, abs=0.005)
    strided = [n.name for n in g.nodes if n.op == "conv" and n.S == 2]
    assert strided == ["conv1"] + [
        f"b{i}_{c}" for i in (3, 7, 13) for c in ("convB", "proj")
    ]


def test_plan_counters_read_the_chained_share():
    """At 224² in bf16 every body is one pyramid: the conv MACs outside
    multi-conv pyramids are the stem's and the four projections'."""
    clear_partition_cache()
    with tracing() as collector:
        plan = auto_partition(resnet50(), batch=8, compute_dtype="bfloat16")
    total = collector.counters[CONV_MACS]
    chained = collector.counters[CHAINED_CONV_MACS]
    assert total == 4_087_136_256
    assert [p.q_convs for p in plan.pyramids].count(3) == 16
    assert 100 * chained / total == pytest.approx(88.3, abs=0.05)


def _he_params(graph, seed):
    """``init_network_params``'s He-normal weights, drawn with numpy: the
    25 M of them take seconds with JAX's generator on a CPU."""
    rng = np.random.default_rng(seed)
    shapes = infer_shapes(graph)
    params = {}
    for n in graph.nodes:
        if n.op not in ("conv", "dense"):
            continue
        c_in = shapes[n.inputs[0]].channels
        shape = (n.K, n.K, c_in, n.n_out) if n.op == "conv" else (c_in, n.n_out)
        fan_in = int(np.prod(shape[:-1]))
        w = rng.standard_normal(shape, np.float32) * np.float32((2.0 / fan_in) ** 0.5)
        b = rng.standard_normal(n.n_out, np.float32) * np.float32(0.01)
        params[n.name] = (jnp.asarray(w), jnp.asarray(b))
    return params


@pytest.fixture(scope="module")
def small():
    graph = resnet50(input_size=32, num_classes=10)
    params = _he_params(graph, 0)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 32, 3))
    ref = np.asarray(reference_network(x, graph, params))
    return graph, params, x, ref


def test_run_network_matches_reference(small):
    graph, params, x, ref = small
    plan = auto_partition(graph, batch=2)
    assert any(
        [l.relu for l in p.spec.levels] == [True, True, False]
        for p in plan.pyramids
    )
    logits, skips = run_network(
        x, prepare_network_params(plan, params), plan=plan
    )
    np.testing.assert_allclose(
        np.asarray(logits), ref, atol=1e-5 * np.abs(ref).max()
    )
    assert set(skips) == {p.name for p in plan.pyramids}


def test_serving_engine_matches_reference(small):
    graph, params, x, ref = small
    engine = ServingEngine(graph, params, ServeConfig(buckets=(2,)))
    results = engine.serve([np.asarray(x[:1]), np.asarray(x[1:])])
    assert all(r.ok for r in results)
    logits = np.concatenate([r.logits for r in results])
    np.testing.assert_allclose(logits, ref, atol=1e-5 * np.abs(ref).max())


def test_replanned_body_keeps_its_linear_level(small):
    graph, params, x, _ = small
    plan = auto_partition(graph, batch=1)
    body = plan.pyramid_at("b1_convA")
    assert body.q_convs == 3
    budget = body.launch.vmem_bytes() - 1
    subs = replan_pyramid(graph, body, vmem_budget=budget, batch=1)
    assert [(s.name, s.launch.out_region) for s in subs] != [
        (body.name, body.launch.out_region)
    ]
    levels = [l for s in subs for l in s.spec.levels]
    assert [l.relu for l in levels] == [True, True, False]
    x_in = jax.random.normal(jax.random.PRNGKey(2), (1, 8, 8, 256))
    y, _ = _run_subplan(
        x_in, subs, params, graph, "float32", end_skip=True, interpret=None,
        vmem_budget=budget,
    )
    names = body.node_names
    ref = reference_forward(
        x_in, body.spec,
        PyramidParams([params[n][0] for n in names],
                      [params[n][1] for n in names]),
    )
    ref = np.asarray(ref)
    assert (ref < 0).any()
    np.testing.assert_allclose(np.asarray(y), ref, atol=1e-5 * np.abs(ref).max())
