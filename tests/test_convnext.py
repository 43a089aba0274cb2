"""ConvNeXt-T: each block's ``7x7 depthwise → LayerNorm → 1x1 GELU → 1x1 →
layer scale`` as one fused three-level pyramid.

* one block pyramid against ``executor.reference_forward`` in f32 and bf16,
  resident, streamed (the double-buffered weight ring, the depthwise level
  staged at a per-cell offset) and channel-tiled streamed, at C = 96 (the
  LayerNorm's mean and variance must leave out the lanes a 96-channel row
  pads to) and C = 128;
* the kernel's rational erf against ``jax.scipy.special.erf`` over the
  float32 range, and its GELU against the exact one (not tanh-GELU);
* ``convnext_tiny(input_size=32)`` at the published widths, one block a
  stage, through ``run_network`` and through ``ServingEngine`` against
  ``runner.reference_network`` (one compile, shared through the jit cache);
* at 224²: the published 4.4555 GMAC and 28,589,128 parameters, one
  fusable chain per block, one launch per block, and the plan counters.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.scipy.special import erf

from repro.core.executor import (
    PyramidParams,
    init_pyramid_params,
    reference_forward,
)
from repro.core.fusion import FusedLevel, FusionSpec
from repro.kernels.fused_conv.fused_conv import ERF_MAX_ERR, erf_f32, gelu_exact
from repro.kernels.fused_conv.ops import fused_pyramid
from repro.net.graph import convnext_tiny, fusable_segments, infer_shapes
from repro.net.partition import (
    CHAINED_CONV_MACS,
    CHAINED_DW_MACS,
    CONV_MACS,
    DW_MACS,
    auto_partition,
    clear_partition_cache,
)
from repro.net.runner import (
    init_network_params,
    param_shapes,
    prepare_network_params,
    reference_network,
    run_network,
)
from repro.net.serve import ServeConfig, ServingEngine
from repro.obs.trace import tracing


def block_spec(c: int, size: int = 8) -> FusionSpec:
    return FusionSpec(
        levels=(
            FusedLevel("depthwise", 7, 1, 3, c, c, name="dw", relu=False,
                       norm_eps=1e-6),
            FusedLevel("conv", 1, 1, 0, c, 4 * c, name="pw1", relu=False,
                       gelu=True),
            FusedLevel("conv", 1, 1, 0, 4 * c, c, name="pw2", relu=False,
                       scale=True),
        ),
        input_size=size,
    )


REGIMES = {
    "resident": dict(streamed=False, out_region=8),
    "streamed_w2": dict(streamed=True, w_slots=2, out_region=4),
    "streamed_c2": dict(streamed=True, w_slots=2, c_tiles=2, out_region=8),
}


@pytest.mark.parametrize("regime", sorted(REGIMES))
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("c", [96, 128])
def test_block_pyramid_matches_reference(c, dtype, regime):
    spec = block_spec(c)
    p = init_pyramid_params(spec, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 8, c))
    if dtype == "bfloat16":  # the reference sees the operands the kernel sees
        rnd = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731
        x = rnd(x)
        p = PyramidParams(
            [rnd(w) for w in p.weights], [rnd(b) for b in p.biases],
            [tuple(rnd(e) for e in extra) for extra in p.epilogues],
        )
    ref = np.asarray(reference_forward(x, spec, p))
    out, skip = fused_pyramid(
        x, p.weights, p.biases, p.epilogues, spec=spec, compute_dtype=dtype,
        **REGIMES[regime],
    )
    out = np.asarray(out, np.float32)
    assert out.shape == ref.shape == (2, 8, 8, c)
    assert (ref < -0.5).mean() > 0.1  # the layer scale's output is signed
    tol = 1e-5 if dtype == "float32" else 2e-2
    np.testing.assert_allclose(out, ref, atol=tol * np.abs(ref).max())
    # no level follows a ReLU level: the END cascade never skips
    assert np.asarray(skip).sum() == 0


def test_layernorm_reads_only_the_real_channels():
    """A 96-channel LayerNorm with the pad lanes counted would divide by
    128 and subtract a mean 3/4 of the true one: the block's output would
    then not match at all."""
    spec = block_spec(96)
    p = init_pyramid_params(spec, jax.random.PRNGKey(2))
    x = 3.0 + jax.random.normal(jax.random.PRNGKey(3), (1, 8, 8, 96))
    ref = np.asarray(reference_forward(x, spec, p))
    out, _ = fused_pyramid(x, p.weights, p.biases, p.epilogues, spec=spec,
                           out_region=8)
    np.testing.assert_allclose(np.asarray(out), ref,
                               atol=1e-5 * np.abs(ref).max())


def _float32_sample() -> np.ndarray:
    grid = np.linspace(-6.0, 6.0, 400_001, dtype=np.float32)
    bits = np.random.default_rng(0).integers(0, 2**32, 400_000, dtype=np.uint64)
    rand = bits.astype(np.uint32).view(np.float32)
    edges = np.float32([0.0, -0.0, 1e-38, -1e-38, 3.4e38, -3.4e38, np.inf, -np.inf])
    return np.concatenate([grid, rand[~np.isnan(rand)], edges])


def test_erf_is_within_its_stated_error():
    x = _float32_sample()
    ours = np.asarray(jax.jit(erf_f32)(x), np.float64)
    assert np.abs(ours - np.asarray(erf(x), np.float64)).max() <= ERF_MAX_ERR
    assert ours[-2] == 1.0 and ours[-1] == -1.0


def test_gelu_is_the_exact_one():
    x = np.linspace(-8.0, 8.0, 100_001, dtype=np.float32)
    ours = np.asarray(jax.jit(gelu_exact)(x))
    exact = np.asarray(jax.nn.gelu(x, approximate=False))
    tanh = np.asarray(jax.nn.gelu(x, approximate=True))
    assert np.abs(ours - exact).max() <= 4 * ERF_MAX_ERR
    assert np.abs(ours - tanh).max() > 100 * ERF_MAX_ERR  # a different function


def test_published_totals_and_chains():
    """torchvision's convnext_tiny: 4.46 GFLOPS (multiply-adds) and 28.6 M
    parameters at 224²; each block one chain of its six nodes."""
    g = convnext_tiny()
    shapes = infer_shapes(g)
    macs = params = 0
    for n in g.nodes[1:]:
        c_in = shapes[n.inputs[0]].channels
        params += sum(math.prod(s) for s in param_shapes(n, c_in))
        side = shapes[n.name].size
        if n.op == "conv":
            macs += n.K * n.K * c_in * n.n_out * side * side
        elif n.op == "depthwise":
            macs += n.K * n.K * c_in * side * side
        elif n.op == "dense":
            macs += c_in * n.n_out
    assert macs == 4_455_531_264
    assert params == 28_589_128
    segs = [s.node_names for s in fusable_segments(g)]
    blocks = [s for s in segs if len(s) == 6]
    assert blocks == [
        tuple(f"b{i}_{k}" for k in ("dw", "ln", "pw1", "gelu", "pw2", "scale"))
        for i in range(18)
    ]
    assert [s for s in segs if len(s) != 6] == [
        ("stem", "stem_ln"), ("s1_down",), ("s2_down",), ("s3_down",),
    ]


def test_plan_runs_each_block_as_one_launch():
    """At 224² in bf16, bucket 8: 22 launches, each block's three levels
    in one, so every depthwise MAC is chained to its block's first 1x1."""
    clear_partition_cache()
    with tracing() as collector:
        plan = auto_partition(convnext_tiny(), batch=8, compute_dtype="bfloat16")
    names = [p.name for p in plan.pyramids]
    assert names == (
        ["stem..stem_ln"]
        + [f"b{i}_dw..b{i}_scale" for i in range(3)] + ["s1_down"]
        + [f"b{i}_dw..b{i}_scale" for i in range(3, 6)] + ["s2_down"]
        + [f"b{i}_dw..b{i}_scale" for i in range(6, 15)] + ["s3_down"]
        + [f"b{i}_dw..b{i}_scale" for i in range(15, 18)]
    )
    blocks = [p for p in plan.pyramids if p.q_convs == 3]
    assert [lvl.kind for lvl in blocks[0].spec.levels] == ["depthwise", "conv", "conv"]
    assert plan.pyramid_at("stem").launch.program.patch
    counters = collector.counters
    assert counters[DW_MACS] == counters[CHAINED_DW_MACS] == 105_106_176
    assert counters[CONV_MACS] == 4_349_657_088
    assert counters[CHAINED_CONV_MACS] == 4_161_798_144


@pytest.fixture(scope="module")
def small():
    graph = convnext_tiny(input_size=32, num_classes=10, depths=(1, 1, 1, 1))
    params = init_network_params(graph, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 32, 3))
    ref = np.asarray(reference_network(x, graph, params))
    return graph, params, x, ref


def test_run_network_matches_reference(small):
    graph, params, x, ref = small
    plan = auto_partition(graph, batch=2)
    # in f32 a whole 768-channel block would stream its 18.9 MB of 1x1
    # weights once per image; the planner splits it after the GELU instead
    assert [p.name for p in plan.pyramids] == [
        "stem..stem_ln", "b0_dw..b0_scale", "s1_down", "b1_dw..b1_scale",
        "s2_down", "b2_dw..b2_scale", "s3_down", "b3_dw..b3_gelu",
        "b3_pw2..b3_scale",
    ]
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
