"""Patch form of level 0 (``core/program.patch_spec``): a narrow-input first
conv runs as a 1x1 conv over its ``K*K*Cin`` patch tensor, which XLA builds
ahead of the kernel (``kernels/fused_conv/ops.patch_tensor``).

* the patch-form launch matches the float32 reference for a VGG-CONV1-shaped
  pyramid, a ResNet-stem-shaped one and LeNet-5, on a 1x1 grid and a larger
  one, with one and two input landing slots; a 64-channel first level keeps
  the direct form and matches too;
* resident and streamed launches stay bitwise equal in the patch form;
* the rule picks exactly the image-input convs of the zoo;
* ``plan_launch`` and ``compile_program`` price the same rewritten program;
* each planned patch-form pyramid bumps ``fused.patch_levels`` once.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.cnn_models import LENET5_FUSION
from repro.core.executor import init_pyramid_params
from repro.core.fusion import FusedLevel, FusionSpec
from repro.core.program import (
    PATCH_MAX_LANES,
    compile_program,
    patch_lanes,
    patch_spec,
    plan_launch,
)
from repro.kernels.fused_conv.ops import (
    fused_pyramid,
    patch_tensor,
    patch_weights,
)
from repro.net.graph import MODELS, infer_shapes
from repro.net.partition import auto_partition, clear_partition_cache
from repro.obs import tracing

KEY = jax.random.PRNGKey(0)


def _conv(K, S, pad, n_in, n_out):
    return FusedLevel("conv", K=K, S=S, pad=pad, n_in=n_in, n_out=n_out)


def _pool(K, S, pad, c):
    return FusedLevel("pool", K=K, S=S, pad=pad, n_in=c, n_out=c)


SHAPES = {
    # VGG-16 CONV1: 3x3 s1 p1 over the image, a second conv, a 2/2 pool
    "vgg_conv1": FusionSpec(
        levels=(_conv(3, 1, 1, 3, 8), _conv(3, 1, 1, 8, 8), _pool(2, 2, 0, 8)),
        input_size=16,
    ),
    # ResNet-18 stem: 7x7 s2 p3 over the image, maxpool 3/2 p1
    "resnet_stem": FusionSpec(
        levels=(_conv(7, 2, 3, 3, 8), _pool(3, 2, 1, 8)), input_size=32
    ),
    # LeNet-5: 5x5 over a one-channel image, both conv+pool groups
    "lenet5": LENET5_FUSION,
}
# a first level fed by a 64-channel activation (9 * 64 > 512): direct form
DIRECT = FusionSpec(
    levels=(_conv(3, 1, 1, 64, 8), _conv(3, 1, 1, 8, 8), _pool(2, 2, 0, 8)),
    input_size=16,
)


def _reference(x, spec, weights, biases):
    """Layer by layer at float32 ``highest`` precision, pools padded with
    -inf (ResNet's maxpool pads)."""
    convs = iter(zip(weights, biases))
    with jax.default_matmul_precision("highest"):
        for lvl in spec.levels:
            pad = ((0, 0), (lvl.pad, lvl.pad), (lvl.pad, lvl.pad), (0, 0))
            if lvl.kind == "conv":
                w, b = next(convs)
                x = jax.lax.conv_general_dilated(
                    x, w, (lvl.S, lvl.S), pad[1:3],
                    dimension_numbers=("NHWC", "HWIO", "NHWC"),
                )
                x = jax.nn.relu(x + b)
            else:
                x = jax.lax.reduce_window(
                    x, -jnp.inf, jax.lax.max, (1, lvl.K, lvl.K, 1),
                    (1, lvl.S, lvl.S, 1), pad,
                )
    return x


def _inputs(spec, batch=2, seed=1):
    c = spec.levels[0].n_in
    return jax.random.normal(
        jax.random.PRNGKey(seed), (batch, spec.input_size, spec.input_size, c)
    )


def _regions(spec):
    """The 1x1 grid's region and the smallest region with alpha > 1."""
    out = spec.feature_sizes()[-1]
    return {"one": out, "many": min(r for r in range(1, out) if out % r == 0)}


CASES = [
    (name, grid, xs)
    for name in (*SHAPES, "direct")
    for grid, xs in (("one", 1), ("many", 1), ("many", 2))
]


@pytest.mark.parametrize("name,grid,x_slots", CASES)
def test_matches_float32_reference(name, grid, x_slots):
    spec = DIRECT if name == "direct" else SHAPES[name]
    region = _regions(spec)[grid]
    prog = compile_program(spec, region)
    assert prog.patch == (name != "direct")
    assert (prog.alpha > 1) == (grid == "many")
    p = init_pyramid_params(spec, KEY)
    x = _inputs(spec)
    y, skip = fused_pyramid(
        x, p.weights, p.biases, spec=spec, out_region=region,
        x_slots=x_slots, streamed=False,
    )
    ref = _reference(x, spec, p.weights, p.biases)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=1e-4)
    assert skip.shape == (2, prog.alpha, prog.alpha, spec.q_convs)
    assert not np.asarray(skip)[..., 0].any()  # level 0 never skips


@pytest.mark.parametrize("name", SHAPES)
def test_streamed_bitwise_equals_resident(name):
    spec = SHAPES[name]
    region = _regions(spec)["many"]
    p = init_pyramid_params(spec, KEY)
    x = _inputs(spec)
    runs = [
        fused_pyramid(
            x, p.weights, p.biases, spec=spec, out_region=region,
            compute_dtype="bfloat16", **knobs,
        )
        for knobs in (
            dict(streamed=False), dict(streamed=True, w_slots=2),
            dict(streamed=True, w_slots=1),
        )
    ]
    for y, skip in runs[1:]:
        np.testing.assert_array_equal(np.asarray(y), np.asarray(runs[0][0]))
        np.testing.assert_array_equal(np.asarray(skip), np.asarray(runs[0][1]))


@pytest.mark.parametrize("K,S,pad", [(5, 1, 2), (7, 2, 3), (11, 4, 0)])
def test_patch_channels_follow_the_weight_rows(K, S, pad):
    """``patch_tensor`` against ``patch_weights`` is the convolution: the
    channel order of the phase-split taps is the row order of the weights,
    and the taps past ``K`` meet zero rows."""
    lvl = _conv(K, S, pad, 3, 4)
    x = _inputs(FusionSpec(levels=(lvl,), input_size=27))
    w = jax.random.normal(KEY, (K, K, 3, 4))
    with jax.default_matmul_precision("highest"):
        got = patch_tensor(x, lvl) @ patch_weights(w, lvl)[0, 0]
        want = jax.lax.conv_general_dilated(
            x, w, (S, S), ((pad, pad), (pad, pad)),
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )
    assert patch_tensor(x, lvl).shape[-1] == patch_lanes(lvl)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)


def test_rule_picks_the_image_input_convs():
    """Every conv of the zoo, put first in a pyramid: the patch form takes
    the convs over the image (VGG-16 CONV1 27 lanes, ResNet-18's stride-2
    stem 192, AlexNet's stride-4 conv1 432, LeNet-5 conv1 25) and LeNet-5's
    conv2 over its six channels (150); no conv over a 64-or-more-channel
    activation and no 1x1 projection."""
    taken = {}
    for model in ("lenet", "alexnet", "vgg16", "resnet18"):
        g = MODELS[model]()
        shapes = infer_shapes(g)
        for n in g.nodes:
            if n.op != "conv":
                continue
            cin = shapes[n.inputs[0]].channels
            spec = FusionSpec(
                levels=(_conv(n.K, n.S, n.pad, cin, n.n_out),),
                input_size=shapes[n.inputs[0]].size,
            )
            rewritten = patch_spec(spec)
            if rewritten is not spec:
                taken[f"{model}.{n.name}"] = rewritten.levels[0].n_in
    assert taken == {
        "lenet.CL1": 25, "lenet.CL2": 150, "alexnet.CONV1": 432,
        "vgg16.CONV1": 27, "resnet18.conv1": 192,
    }
    assert PATCH_MAX_LANES == 512


@pytest.mark.parametrize("name", SHAPES)
def test_planner_prices_the_launched_program(name):
    """The plan's program, ``compile_program`` at the plan's region, and
    ``compile_program`` of the already-rewritten spec allocate the same
    buffers from the same level-0 tile."""
    spec = SHAPES[name]
    lp = plan_launch(spec)
    prog = compile_program(spec, lp.out_region)
    rewritten = compile_program(patch_spec(spec), lp.out_region)
    assert lp.program.patch and prog.patch and not rewritten.patch
    for other in (prog, rewritten):
        assert other.tile0 == lp.program.tile0
        assert other.alpha == lp.program.alpha
        assert other.vmem_buffers(lp.x_slots) == lp.program.vmem_buffers(
            lp.x_slots
        )
        assert other.hbm_bytes(3) == lp.program.hbm_bytes(3)
    assert lp.describe()["patch"] is True


@pytest.mark.parametrize("model", ["vgg16", "resnet18"])
def test_one_patch_pyramid_per_benchmark_plan(model, capsys):
    from repro.obs.explain import main

    clear_partition_cache()
    with tracing() as collector:
        plan = auto_partition(
            MODELS[model](), batch=8, compute_dtype="bfloat16"
        )
    assert collector.counters["fused.patch_levels"] == 1
    assert [p.launch.program.patch for p in plan.pyramids].count(True) == 1
    assert plan.pyramids[0].launch.program.patch
    clear_partition_cache()
    assert main(["--model", model, "--dtype", "bfloat16", "--batch", "8"]) == 0
    text = capsys.readouterr().out
    assert f"level 0 in patch form: {plan.pyramids[0].name} " in text
    assert "fused.patch_levels +1" in text
